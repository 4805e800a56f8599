"""A cell of BENCHMARK.json cut to a size the CPU runs in a second:
L=4 nodes × 10 tasks, d=64, the xla-ref engine, a short window.
Everything else is the cell's own files."""
import copy

from chipbench import harness


def cell(workload: str):
    bench_ = harness.load_benchmark()
    wl = harness.find_workload(bench_, workload)
    config = copy.deepcopy(harness.load_named("configs", wl["config"]))
    config["spec"]["problem"].update(d=64, T=40, L=4)
    config["spec"]["engine"]["backend"] = "xla-ref"
    traffic = dict(harness.load_named("traffic", wl["traffic"]))
    if "rate_hz" in traffic:
        traffic.update(rate_hz=200, pool=32, check_requests=16)
    return bench_, config, traffic


def execute(workload: str, *, seed: int, seconds: float = 0.5, devices=None,
            trace: bool = False, **traffic_overrides):
    import jax
    bench, config, traffic = cell(workload)
    traffic.update(traffic_overrides)
    with jax.default_matmul_precision(config["precision"]):
        checks = harness.load_named("checks", workload)
        return harness.execute(workload, seed=seed, seconds=seconds,
                               trace=trace, bench=bench, config=config,
                               traffic=traffic, checks=checks,
                               devices=devices or jax.devices())
