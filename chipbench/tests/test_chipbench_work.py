"""Required-work counters against hand counts at Experiment-1 shapes
(L=20 nodes × 30 tasks, n=30, d=600, r=4)."""
import pytest

from chipbench import harness, work

EXP1 = dict(L=20, tpn=30, n=30, d=600, r=4)


def test_fused_iter_by_hand():
    w = work.fused_iter(**EXP1)
    # per task: XU 144000, AᵀA 960, Aᵀy 240, solve 64, Ab−y 240,
    # Xᵀres 36000, outer 2400, node sum 2400 = 186304; × 600 tasks
    assert w["flops"] == 600 * 186304 == 111_782_400
    # X 10.8M + y 18000 + U 48000 + B 2400 + G 48000 floats, 4 bytes
    assert w["bytes"] == 4 * 10_916_400 == 43_665_600


def test_task_gram_by_hand():
    # 32 requests holding 800 real rows: XU 4800, AᵀA 32, Aᵀy 8 per row
    w = work.task_gram(rows=800, requests=32, d=600, r=4)
    assert w["flops"] == 800 * 4840
    assert w["bytes"] == 4 * (800 * 600 + 800 + 2400 + 32 * 20)


def test_served_by_hand():
    # the Gram pass, then per request a 4×4 solve (64) and θ = U b
    # (2·600·4 = 4800), with θ's 600 floats written out
    w = work.served(rows=800, requests=32, d=600, r=4)
    assert w["flops"] == 800 * 4840 + 32 * (64 + 4800)
    assert w["bytes"] == 4 * (800 * 600 + 800 + 2400 + 32 * 20 + 32 * 600)


def test_roofline_bound_of_the_fused_pass_is_bytes():
    peaks = harness.load_peaks("TPU v5 lite")
    t, bound = work.roofline_s(work.fused_iter(**EXP1), peaks)
    assert bound == "bytes"
    assert t == pytest.approx(43_665_600 / 819e9)        # 53.3 µs


def test_training_job_adds_its_parts():
    kw = dict(T_pm=30, T_con_init=10, T_con=10)
    one = work.training_job(**EXP1, T_GD=1, **kw)
    two = work.training_job(**EXP1, T_GD=2, **kw)
    it = work.fused_iter(**EXP1)
    mx = work.mix(L=20, d=600, r=4)
    qr = work.qr(L=20, d=600, r=4)
    for k in ("flops", "bytes"):
        assert two[k] - one[k] == pytest.approx(it[k] + mx[k] + qr[k])
    # a job of 100 iterations moves about 110 X's worth of bytes: X read
    # by the init, 100 passes and the refit (102), plus gossip rounds
    # and QRs of the (20, 600, 4) stack (~0.8 MB per iteration, ~6 MB
    # per power iteration)
    job = work.training_job(**EXP1, T_GD=100, **kw)
    assert 108 * 43.2e6 < job["bytes"] < 112 * 43.2e6
