"""``exp1-virtual4.train`` end to end on four fake CPU devices (a
subprocess, as the mesh tests run): ``harness.execute`` drives the
``train_jobs`` driver over the cell's own configuration, cut to L=8
nodes, d=T=32, r=2, n=30 and 40 iterations, on the xla-ref engine and,
sound, also on pallas-interpret (the cell's Pallas kernels, whose
outputs turn the shard_map's varying-axes check off).  Sound, the
virtual tier's answers match the plain reference at ``highest`` within
the cell's limits; with the cross-chip exchange dropped
(``chipbench.faults_virtual.no_cross_exchange``) they do not.
``chipbench.faults.no_exchange`` patches a mixer this tier never calls,
so it reads as sound here."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

SCRIPT = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import copy, json
    import jax
    jax.config.update("jax_enable_x64", False)
    sys.path[:0] = [".", "src"]
    import pytest
    from chipbench import faults, faults_virtual, harness

    plant = {"sound": None, "sound-pallas-interpret": None,
             "drop": faults_virtual.no_cross_exchange,
             "no_exchange": faults.no_exchange}[sys.argv[1]]
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, "exp1-virtual4.train")
    config = copy.deepcopy(harness.load_named("configs", wl["config"]))
    config["spec"]["problem"].update(d=32, T=32, r=2, n=30, L=8)
    config["spec"]["init"].update(T_pm=20)
    config["spec"]["engine"]["backend"] = (
        "pallas-interpret" if sys.argv[1].endswith("pallas-interpret")
        else "xla-ref")
    traffic = dict(harness.load_named("traffic", wl["traffic"]), T_GD=40)
    with pytest.MonkeyPatch.context() as mp:
        if plant is not None:
            plant(mp)
        with jax.default_matmul_precision(config["precision"]):
            out = harness.execute(
                "exp1-virtual4.train", seed=2**31 + 11, seconds=0.5,
                trace=False, devices=jax.devices(), bench=bench,
                config=config, traffic=traffic)
    print("RESULT " + json.dumps(out))
""")


def run_cell(case: str) -> dict:
    r = subprocess.run([sys.executable, "-c", SCRIPT, case],
                       capture_output=True, text=True, cwd=ROOT,
                       timeout=900, env={**os.environ,
                                         "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    line, = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("case", ["sound", "drop", "no_exchange",
                                  "sound-pallas-interpret"])
def test_virtual_tier_without_its_exchange_is_not_correct(case):
    out = run_cell(case)
    assert out["device"]["count"] == 4
    u_gap = out["checks"]["u_gap"]
    if case == "drop":
        assert out["correct"] is False
        # fails by far more than ten times its limit
        assert u_gap["value"] > 10 * u_gap["limit"]
    else:
        assert out["correct"] is True, out["checks"]
        assert out["counters"]["jobs_on_target"] == out["attempted"] > 0
        assert set(out["metrics"]) == {"train_time_to_target_s", "setup_s"}
