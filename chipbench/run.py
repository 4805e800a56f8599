"""Run one cell of BENCHMARK.json on the chips of this machine.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process drives the cell's chips and starts no other.  It puts the
repository's ``src/`` on the path, keeps x64 off, computes at the
matmul precision the configuration states, turns on JAX's persistent
compilation cache (every compile cached, however short), warms up the
cell's own shapes, measures for ``--seconds`` and prints the result as
the last line of standard output, with each number the correctness
check compared, beside its limit, as the last lines of standard error.
Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from chipbench import harness  # noqa: E402


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    if args.seconds <= 0:
        ap.error("--seconds must be > 0")
    return args


def setup_jax():
    """Import the system under test and configure JAX for a chip run."""
    sys.path.insert(0, str(harness.ROOT / "src"))
    from repro.utils.compile_cache import use_persistent_cache
    import jax
    jax.config.update("jax_enable_x64", False)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    use_persistent_cache()
    return jax


def chips(jax, wanted: int) -> list:
    """The TPU devices, or SystemExit(2) when there are too few."""
    try:
        devices = jax.devices("tpu")
    except RuntimeError as e:
        print(f"chipbench: no TPU: {e}", file=sys.stderr)
        raise SystemExit(2) from None
    if len(devices) < wanted:
        print(f"chipbench: the cell needs {wanted} TPU chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        raise SystemExit(2)
    return devices


def print_checks(result: dict) -> None:
    for name in result["counters"].get("metrics_not_read", []):
        print(f"chipbench: metric {name} found nothing to read in this run",
              file=sys.stderr)
    for name, c in result["checks"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {name} {c['value']!r} <= {c['limit']!r} {verdict}",
              file=sys.stderr)


def main(argv=None) -> int:
    args = parse(argv)
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, args.workload)
    config = harness.load_named("configs", wl["config"])
    jax = setup_jax()
    devices = chips(jax, int(wl["chips"]))
    with jax.default_matmul_precision(config["precision"]):
        result = harness.execute(args.workload, seed=args.seed,
                                 seconds=args.seconds, trace=bool(args.trace),
                                 devices=devices, bench=bench, config=config,
                                 t_process=T_PROCESS)
    print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
