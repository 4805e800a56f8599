"""The per-layer readers of ``exp1-virtual4.train``, each worked out by
hand on a hand-built record: two chips' ops with known collective
intervals, the harness's spans and the program's spans."""
import collections

import pytest

from chipbench import harness, trace, work
from repro.utils import spans as spans_mod

W0 = 3000.0          # the harness's window starts here on perf_counter
JOBS, T_GD = 1, 2


def at(ns):
    return W0 + ns * 1e-9


def sp(name, start, end, **stats):
    return (name, at(start), (end - start) * 1e-9,
            {**dict.fromkeys(spans_mod.STATS, 0), **stats})


def chips():
    """Window [0, 1000) ns.  Chip 0: a loop over [0, 900) (no op of its
    own), compute [100,200) and [370,400), an async permute [200,260),
    the all-gather as a TPU runs it: a fused continuation [300,340)
    and the async pair [340,350) [350,380).  Chip 1: a permute and an
    all-gather as sync ops [500,650), compute [620,700)."""
    return trace.Reduced(
        devices={"/device:TPU:0": [
            ["%while.1 = (s32[]) while(%tuple.1), body=%region_0", 0, 900,
             ""],
            ["%fusion.1 = f32[5] fusion(%a), kind=kLoop", 100, 100, ""],
            ["%collective-permute-start.1 = (f32[5], f32[5]) "
             "collective-permute-start(%a)", 200, 10, ""],
            ["%collective-permute-done.1 = f32[5] "
             "collective-permute-done(%b)", 210, 50, ""],
            ["%fusion.9 = f32[20] fusion(%a), kind=kCustom, "
             "calls=%async_collective_fusion.9", 300, 40, ""],
            ["%async-collective-start = (f32[20]) fusion(%a), "
             "kind=kCustom, calls=%fused_computation.238", 340, 10, ""],
            ["%async-collective-done = f32[20] fusion(%c), kind=kCustom, "
             "calls=%fused_computation.250", 350, 30, ""],
            ["%fusion.2 = f32[5] fusion(%d), kind=kLoop", 370, 30, ""],
            ["%collective-permute-start.0 = (f32[5]) "
             "collective-permute-start(%z)", -50, 40, ""]],
                 "/device:TPU:1": [
            ["%collective-permute.3 = f32[5] collective-permute(%a)", 500,
             100, ""],
            ["%all-gather.2 = f32[20] all-gather(%a)", 600, 50, ""],
            ["%fusion.5 = f32[5] fusion(%e), kind=kLoop", 620, 80, ""]]},
        spans=[["window", 0, 1000], ["materialize", 0, 300],
               ["run_experiment", 300, 600]])


PROGRAM = [
    sp("repro.run_experiment", 300, 900),
    sp("repro.virtual.topology", 310, 320),
    sp("repro.virtual.shard", 330, 360),
    sp("repro.solve.scan", 360, 880, trace_s=0.01, traces=20, lower_s=0.02,
       lowerings=24, compile_s=0.03, compiles=24, devices=4,
       ppermutes_per_iter=30, gathers_per_iter=1,
       wire_bytes_per_iter=1440000),
    sp("repro.materialize", 0, 300),
    sp("repro.materialize.spectral_init", 50, 250, lower_s=0.004,
       lowerings=3),
]


def record(red, n_chips=4):
    spans = harness.Spans()
    spans.records = [("window", W0, at(1000)),
                     ("materialize", at(0), at(300)),
                     ("run_experiment", at(300), at(900))]
    shapes = dict(L=20, tpn=30, n=30, d=600, r=4)
    return harness.RunRecord(
        workload="exp1-virtual4.train", config={}, traffic={},
        n_chips=n_chips, peaks=harness.load_peaks("TPU v5 lite"),
        spans=spans, window_s=1000e-9, trace=red,
        work={"jobs": JOBS, "T_GD": T_GD, "shapes": shapes,
              "job": work.training_job(**shapes, T_pm=30, T_con_init=10,
                                       T_GD=T_GD, T_con=10)})


@pytest.fixture
def log(monkeypatch):
    recorded = collections.deque(maxlen=spans_mod.RECORDED_MAX)
    monkeypatch.setattr(spans_mod, "_recorded", recorded)
    recorded.extend(PROGRAM)
    return recorded


def read(metric, rec):
    return harness.load_metric_reader(metric)(rec)


def test_gossip_by_hand(log):
    # chip 0: collectives [200,260) [300,380) = 140 ns, [370,380) under
    # compute, so 130 exposed; two started (the permute, the async
    # gather).  Chip 1: [500,650) = 150, [620,650) under compute, 120
    # exposed; two started.  Over 2 iterations, mean over the chips.
    got = read("gossip_ms_per_iter.virtual4", record(chips()))
    assert got == pytest.approx({
        "value": (140 + 150) / 2 / 2 * 1e-6,
        "exposed_ms_per_iter": (130 + 120) / 2 / 2 * 1e-6,
        "collectives_per_iter": 2 / 2,
        "program_collectives_per_iter": 31})


def test_idle_share_by_hand(log):
    # chip 0 busy [0,900) (the loop holds its ops), chip 1 [500,700)
    got = read("idle_share.virtual4", record(chips()))
    assert got == pytest.approx({"value": (10 + 80) / 2, "min": 10.0,
                                 "max": 80.0})


def test_compile_path_by_hand(log):
    got = read("compile_path_ms.virtual4", record(chips()))
    assert got == pytest.approx({
        "value": (0.01 + 0.02 + 0.03 + 0.004) * 1e3 / JOBS,
        "lowerings_per_job": 27 / JOBS, "traces_per_job": 20 / JOBS,
        "topology_ms": 10e-6, "shard_ms": 30e-6})


def test_gd_iter_by_hand(log):
    assert read("gd_iter_ms.virtual4", record(chips())) == pytest.approx(
        600e-9 / T_GD * 1e3)


def test_step_mfu_by_hand(log):
    w = record(chips()).work["job"]
    peaks = harness.load_peaks("TPU v5 lite")
    span = 1000e-9 * 4
    flops = JOBS * w["flops"] / (span * peaks["flops_per_s"])
    nbytes = JOBS * w["bytes"] / (span * peaks["hbm_bytes_per_s"])
    got = read("step_mfu.virtual4", record(chips()))
    assert got == pytest.approx({
        "value": 100 * max(flops, nbytes),
        "bound": "flops" if flops >= nbytes else "bytes"})


@pytest.mark.parametrize("metric", ["gossip_ms_per_iter.virtual4",
                                    "idle_share.virtual4",
                                    "compile_path_ms.virtual4"])
def test_reader_finds_nothing_without_a_trace(metric, log):
    assert read(metric, record(None)) is None


def test_gossip_finds_nothing_without_collectives(log):
    red = chips()
    red.devices = {dev: [op for op in ops if "fusion.1 " in op[0]
                         or "fusion.5 " in op[0]]
                   for dev, ops in red.devices.items()}
    assert read("gossip_ms_per_iter.virtual4", record(red)) is None


def test_gossip_without_program_spans_reads_the_trace_alone(log):
    log.clear()
    got = read("gossip_ms_per_iter.virtual4", record(chips()))
    assert "program_collectives_per_iter" not in got
    assert got["collectives_per_iter"] == 1.0


def with_fused_kernel(red):
    """The fused kernel inside chip 0's loop, [100,200) and [400,450),
    and once as a bare op on chip 1, [700,760)."""
    red.devices["/device:TPU:0"] += [
        ["%_altgdmin_fused_step.7 = (f32[5,30,4]) custom-call(%a), "
         "custom_call_target=\"tpu_custom_call\"", 100, 100, ""],
        ["%_altgdmin_fused_step.7 = (f32[5,30,4]) custom-call(%a), "
         "custom_call_target=\"tpu_custom_call\"", 400, 50, ""]]
    red.devices["/device:TPU:1"] += [
        ["_altgdmin_fused_step[tpu_custom_call]", 700, 60, ""]]
    return red


def test_fused_kernel_roofline_by_hand(log):
    # per chip: 150 and 60 ns of kernel for 2 and 1 calls, so a mean of
    # 105 ns for 1.5 calls; each call's least time is that of one chip's
    # block, L / chips = 5 nodes
    peaks = harness.load_peaks("TPU v5 lite")
    t_min, bound = work.roofline_s(
        work.fused_iter(L=5, tpn=30, n=30, d=600, r=4), peaks)
    got = read("node_fused_iter_roofline.virtual4",
               record(with_fused_kernel(chips())))
    assert got == pytest.approx({"value": 100 * 1.5 * t_min / 105e-9,
                                 "bound": bound})


def test_init_ms_by_hand(log):
    assert read("init_ms.virtual4", record(chips())) == pytest.approx(
        300e-9 * 1e3)


def test_init_idle_by_hand(log):
    # materialize [0,300): chip 0 busy throughout (its loop), chip 1
    # idle throughout; the spectral init [50,250) likewise
    got = read("init_idle_ms.virtual4", record(chips()))
    assert got == pytest.approx({
        "value": 300 / 2 * 1e-6 / JOBS, "problem": 0.0, "topology": 0.0,
        "spectral_init": 200 / 2 * 1e-6 / JOBS, "eta": 0.0})


@pytest.mark.parametrize("metric", ["node_fused_iter_roofline.virtual4",
                                    "init_idle_ms.virtual4"])
def test_new_device_reader_finds_nothing_without_a_trace(metric, log):
    assert read(metric, record(None)) is None
