"""The one-time sweep behind ``serve-poisson``'s fixed rate.

    python3 -m chipbench.sweep_knee --workload exp1.serve --seed 3 \
        --rates 1000,2000,4000,8000 [--seconds 5]

Runs the serving cell in one process at each offered rate (the traffic
file's other parameters unchanged) and prints, per rate, what was
offered and completed, the completed rate, the latency percentiles and
the mean batch.  The knee is the highest rate whose completed rate
keeps up with the offered one before the tail turns up; the cell's
rate is set once at 4/5 of it.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

from chipbench import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    config = harness.load_named("configs", wl["config"])
    traffic = harness.load_named("traffic", wl["traffic"])
    jax = run.setup_jax()
    devices = run.chips(jax, int(wl["chips"]))
    with jax.default_matmul_precision(config["precision"]):
        for rate in (float(r) for r in args.rates.split(",")):
            out = harness.execute(
                args.workload, seed=args.seed, seconds=args.seconds,
                trace=False, devices=devices, bench=bench, config=config,
                traffic={**traffic, "rate_hz": rate})
            c, m = out["counters"], out["metrics"]
            print(json.dumps({
                "rate_hz": rate, "offered": c["offered"],
                "completed": c["completed"],
                "req_per_s": m["serve_req_per_s"]["value"],
                "p50_ms": c["p50_ms"], "p95_ms": c["p95_ms"],
                "p99_ms": c["p99_ms"], "mean_batch": c["mean_batch"],
                "generator_late_p99_ms": c["generator_late_p99_ms"],
                "correct": out["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
