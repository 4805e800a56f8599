"""The control — the plain reference put in the program's place at the
precision below the configuration's (``high``: three bf16 passes for
float32 at ``highest``) — comes out not correct through the harness's
own comparison, at the cell's own Experiment-1 size on the CPU, on
three seeds each."""
import functools

import jax
import pytest

from chipbench import calibrate, faults, harness
from chipbench.tests import tiny

SEEDS = (11, 12, 2**32 + 13)


def control_readings(workload, seed):
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, workload)
    config = harness.load_named("configs", wl["config"])
    traffic = harness.load_named("traffic", wl["traffic"])
    with jax.default_matmul_precision(config["precision"]):
        line = calibrate.readings(
            workload, seed=seed, seconds=0.2, devices=jax.devices(),
            bench=bench, config=config, traffic=traffic,
            plant=functools.partial(faults.control, precision="high"))
    assert line["attempted"] > 0
    return line


@pytest.mark.parametrize("seed", SEEDS)
def test_training_control_fails(seed):
    line = control_readings("exp1.train", seed)
    assert line["correct"] is False, line


@pytest.mark.parametrize("seed", SEEDS)
def test_serving_control_fails(seed):
    line = control_readings("exp1.serve", seed)
    assert line["correct"] is False, line


@pytest.mark.parametrize("workload", ["exp1.train", "exp1.serve"])
def test_reference_in_the_programs_place_at_the_stated_precision_is_correct(
        workload, monkeypatch):
    """The control's plant is sound: at the configuration's own
    precision it passes, so the control fails by its precision alone."""
    faults.control(monkeypatch, precision="highest")
    out = tiny.execute(workload, seed=17, seconds=0.3)
    assert out["attempted"] > 0
    assert out["correct"] is True, out["checks"]
