"""Breakages of the virtual-node tier, for the upper readings of a cell
that runs it.

:func:`no_cross_exchange` drops the exchange between chips: every
gossip round keeps the on-chip segment sum and the self weight, and no
block crosses to another chip.  ``chipbench.faults.no_exchange`` patches
``AltgdminEngine.make_mixer``, which the virtual tier never calls (it
mixes through ``make_virtual_mixer``), so on this tier that fault leaves
the run as it was.

    python3 -m chipbench.faults_virtual --workload exp1-virtual4.train \\
        --seeds 1,2,3 [--sound-seeds 4] [--seconds 1]

runs the cell on its chips once per seed with :func:`no_cross_exchange`
planted, and once per ``--sound-seeds`` seed with
``chipbench.faults.no_exchange`` planted, through the path
``chipbench.calibrate`` takes; one JSON line per run and a summary line
last.
"""
from __future__ import annotations

import argparse
import dataclasses
import json

import numpy as np

from chipbench import calibrate, faults, harness, run


def no_cross_exchange(mp):
    """The virtual tier's combine with every cross-chip shift class left
    out: each round is the chip's own segment sum and self weight."""
    from repro.core.engine import AltgdminEngine
    real = AltgdminEngine.make_virtual_mixer

    def local_only(self, vt, axis_name, T_con, **kw):
        none = np.zeros((0, vt.n_dev, 1))
        vt = dataclasses.replace(
            vt, dev_shifts=(), cross_rows=none.astype(np.int32),
            cross_cols=none.astype(np.int32), cross_vals=none)
        return real(self, vt, axis_name, T_con, **kw)
    mp.setattr(AltgdminEngine, "make_virtual_mixer", local_only)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sound-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    config = harness.load_named("configs", wl["config"])
    traffic = harness.load_named("traffic", wl["traffic"])
    jax = run.setup_jax()
    devices = run.chips(jax, int(wl["chips"]))
    sides = [(no_cross_exchange, args.seeds),
             (faults.no_exchange, args.sound_seeds)]
    summary: dict[str, dict[str, list]] = {}
    with jax.default_matmul_precision(config["precision"]):
        for plant, seeds in sides:
            for s in (int(x) for x in seeds.split(",") if x):
                line = calibrate.readings(
                    args.workload, seed=s, seconds=args.seconds,
                    devices=devices, bench=bench, config=config,
                    traffic=traffic, plant=plant)
                print(json.dumps({"side": plant.__name__, "seed": s,
                                  **line}), flush=True)
                for k in ("u_gap", "theta_gap"):
                    summary.setdefault(plant.__name__, {}).setdefault(
                        k, []).append(line[k])
    print(json.dumps({"workload": args.workload,
                      "device": devices[0].device_kind,
                      "smallest": {side: {k: min(v) for k, v in vals.items()}
                                   for side, vals in summary.items()}}),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
