"""Each driver end to end at a tiny size on the CPU (xla-ref engine),
past the harness's look for a chip, and the inputs they draw."""
import numpy as np
import pytest

from chipbench import harness
from chipbench.drivers import serve_open_loop
from chipbench.reference import mtrl
from chipbench.tests import tiny

E2E = {"exp1.train": {"train_time_to_target_s", "setup_s"},
       "exp1.serve": {"serve_req_per_s", "setup_s"}}


@pytest.mark.parametrize("workload", ["exp1.train", "exp1.serve"])
def test_cell_runs_and_is_correct(workload):
    out = tiny.execute(workload, seed=2**31 + 12345)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    limits = harness.load_named("checks", workload)["limits"]
    assert set(out["checks"]) == set(limits)
    assert set(out["metrics"]) == E2E[workload]
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["counters"]["window_compiles"] == 0


def test_serving_inputs_follow_the_seed():
    _, _, traffic = tiny.cell("exp1.serve")
    U = np.linalg.qr(np.random.default_rng(0).standard_normal((64, 4)))[0]
    a = serve_open_loop.request_pool(U, traffic, np.random.default_rng(5))
    b = serve_open_loop.request_pool(U, traffic, np.random.default_rng(5))
    c = serve_open_loop.request_pool(U, traffic, np.random.default_rng(6))
    assert all(np.array_equal(x, y) for x, y in zip(a[0] + a[1], b[0] + b[1]))
    # every seed draws the same request sizes, in its own order
    sizes = [[x.shape[0] for x in pool[0]] for pool in (a, c)]
    assert sorted(sizes[0]) == sorted(sizes[1]) and sizes[0] != sizes[1]
    assert min(sizes[0]) == traffic["t_new_min"]
    assert max(sizes[0]) <= traffic["t_new_max"]
    due = serve_open_loop.arrivals(traffic, 2.0, np.random.default_rng(1))
    assert due.size == 400 and np.all(np.diff(due) >= 0)


def test_job_keys_differ_by_seed_and_job():
    keys = {tuple(np.asarray(mtrl.job_key(s, j)).tolist())
            for s in (0, 1, 2**31 + 7, 2**40 + 1) for j in (0, 1, 2)}
    assert len(keys) == 12
