"""Readings that the correctness limits of a cell are set from.

    python3 -m chipbench.calibrate --workload exp1.train --seeds 1,2,...,12 \
        --control-seeds 1,2,3 [--fault-seeds 1,2,3] [--seconds 2]

In one process on the cell's chips, each reading a short run of the
cell through the same path as ``chipbench.run``: the program's on
``--seeds`` (the largest sound reading is a limit's lower end); the
control's on ``--control-seeds``, the plain reference put in the
program's place at the precision below the one the configuration
states (``high``, three bf16 passes, for float32 at ``highest``; its
smallest reading is the upper end); and, with ``--fault-seeds``, each
fault of ``chipbench.faults`` that the cell's driver can have.  Prints
one JSON line per run and a summary line last.
"""
from __future__ import annotations

import argparse
import functools
import json

import pytest

from chipbench import faults, harness, run


def readings(workload: str, *, seed: int, seconds: float, devices,
             bench, config, traffic, plant=None) -> dict:
    """One run of the cell, with ``plant(mp)`` patched in when given:
    each compared number, the verdict and the run's counts."""
    with pytest.MonkeyPatch.context() as mp:
        if plant is not None:
            plant(mp)
        out = harness.execute(workload, seed=seed, seconds=seconds,
                              trace=False, devices=devices, bench=bench,
                              config=config, traffic=traffic)
    return {**{k: c["value"] for k, c in out["checks"].items()},
            "correct": out["correct"], "attempted": out["attempted"],
            "failed": out["failed"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control_seeds = [int(s) for s in args.control_seeds.split(",")]
    fault_seeds = [int(s) for s in args.fault_seeds.split(",") if s]
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    config = harness.load_named("configs", wl["config"])
    traffic = harness.load_named("traffic", wl["traffic"])
    jax = run.setup_jax()
    devices = run.chips(jax, int(wl["chips"]))
    lower_prec = faults.LOWER[config["precision"]]
    sides = [("program", None, seeds),
             ("control", functools.partial(faults.control,
                                           precision=lower_prec),
              control_seeds)]
    sides += [(f.__name__, f, fault_seeds)
              for f in faults.FAULTS[traffic["driver"]]]
    summary: dict[str, dict[str, list]] = {}
    with jax.default_matmul_precision(config["precision"]):
        for side, plant, side_seeds in sides:
            for s in side_seeds:
                try:
                    line = readings(args.workload, seed=s,
                                    seconds=args.seconds, devices=devices,
                                    bench=bench, config=config,
                                    traffic=traffic, plant=plant)
                except Exception as e:  # a broken run that gives no number
                    print(json.dumps({"side": side, "seed": s,
                                      "crashed": repr(e)[:500]}), flush=True)
                    continue
                print(json.dumps({"side": side, "seed": s, **line}),
                      flush=True)
                for k in harness.load_named("checks",
                                            args.workload)["limits"]:
                    summary.setdefault(side, {}).setdefault(k, []).append(
                        line[k])
    print(json.dumps({
        "workload": args.workload, "device": devices[0].device_kind,
        "lower": {k: max(v) for k, v in summary.get("program", {}).items()},
        "upper": {side: {k: min(v) for k, v in vals.items()}
                  for side, vals in summary.items() if side != "program"}}),
        flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
