"""The trace reduction, on a hand-built device trace and on a small
trace recorded through the harness's tracer."""
import re
from pathlib import Path

import pytest

from chipbench import trace

CPU_FIXTURE = Path(__file__).parent / "fixtures" / "cpu_two_solves.xplane.pb"
TPU_FIXTURE = Path(__file__).parent / "fixtures" / "tpu_three_iterations.json"


def hand_built():
    # window [0, 100); device A busy [10,30) [20,40) [35,45) [60,70),
    # device B busy [0,50)
    return trace.Reduced(
        devices={"/device:TPU:0": [
            ["fusion.1", 10, 20, "jit(run)/pad"],
            ["_altgdmin_fused_step.1", 20, 20,
             "jit(run)/jit(_altgdmin_fused_step)/pallas_call"],
            ["collective-permute-done.3", 35, 10, "jit(run)/ppermute"],
            ["fusion.2", 60, 10, ""]],
                 "/device:TPU:1": [["_altgdmin_fused_step.2", 0, 50, ""]]},
        spans=[["window", 0, 100], ["materialize", 0, 50],
               ["run_experiment", 50, 50], ["solve", 55, 10]])


def test_busy_idle_and_op_time_by_hand():
    red = hand_built()
    assert trace.window_len_s(red) == pytest.approx(100e-9)
    # A: [10,45) ∪ [60,70) = 45; B: 50 → mean 47.5 ns
    assert trace.busy_s(red) == pytest.approx(47.5e-9)
    def fused(text):
        return text.startswith("_altgdmin_fused_step")
    assert trace.op_seconds(red, fused) == pytest.approx((20 + 50) / 2 * 1e-9)
    assert trace.op_count(red, fused) == 1.0
    # the JAX op name is part of what an op is matched by
    assert trace.op_count(red, lambda t: t.endswith("pallas_call")) == 0.5
    # solve span [55,65): A busy [60,65) = 5, B 0 → mean 2.5
    assert trace.busy_inside(red, "solve") == pytest.approx(2.5e-9)


def test_idle_goes_to_the_innermost_span():
    red = hand_built()
    idle = trace.idle_by_span(red)
    # A idle: [0,10) materialize, [45,50) materialize, [50,55) run,
    # [55,60) solve, [70,100) run;  B idle: [50,55) run, [55,65) solve,
    # [65,100) run
    assert idle["materialize"] == pytest.approx(15 / 2 * 1e-9)
    assert idle["solve"] == pytest.approx(15 / 2 * 1e-9)
    assert idle["run_experiment"] == pytest.approx((5 + 30 + 5 + 35) / 2 * 1e-9)
    assert sum(idle.values()) == pytest.approx(100e-9 - trace.busy_s(red))
    b = trace.breakdown(red)
    assert b["device_ops"][0] == ["_altgdmin_fused_step", pytest.approx(35e-9)]
    assert ["fusion", pytest.approx(15e-9)] in b["device_ops"]


# op names as a TPU v5e trace of an exp1.train job gives them (cut short)
@pytest.mark.parametrize("text, name, family, op", [
    ("%_altgdmin_fused_step.10 = (f32[600,1,4]{2,1,0:T(1,128)}, "
     "f32[600,768,4]{2,1,0:T(8,128)}) custom-call(f32[600,30,768]{2,1,0:"
     "T(8,128)S(1)} %bitcast.202, f32[20,768,4]{2,1,0:T(8,128)S(1)} %pad.66,"
     " f32[600,1,30]{2,1,0:T(1,128)S(1)} %copy.276), custom_call_target="
     '"tpu_custom_call", operand_layout_constraints={f32[600,30,768]{2,1,0}}',
     "_altgdmin_fused_step.10", "_altgdmin_fused_step[tpu_custom_call]",
     "custom-call"),
    ("%custom-call.94 = (f32[20,8,4]{2,1,0:T(8,128)S(1)}, f32[20,4]{1,0:"
     "T(8,128)S(1)}) custom-call(f32[20,8,4]{2,1,0:T(8,128)S(1)} "
     "%dynamic-update-slice.34), custom_call_target=\"QrDecompositionBlock\"",
     "custom-call.94", "custom-call[QrDecompositionBlock]", "custom-call"),
    ("%while.191 = (s32[]{:T(128)}, f32[20,600,4]{1,2,0:T(4,128)S(1)}, "
     "/*index=5*/f32[20,30,30,600]{3,2,1,0:T(8,128)}) while((s32[]{:T(128)},"
     " f32[20,600,4]{1,2,0:T(4,128)S(1)}) %tuple.224), condition=%region_8",
     "while.191", "while", "while"),
    ("%pad.65 = f32[20,30,30,768]{3,2,1,0:T(8,128)S(1)} pad(f32[20,30,30,"
     "600]{3,2,1,0:T(8,128)} %get-tuple-element.928, f32[]{:T(128)} "
     "%constant.141..sunk.50..sunk), padding=0_0x0_0x0_0x0_168",
     "pad.65", "pad", "pad"),
    ("fusion.12", "fusion.12", "fusion", ""),
])
def test_instruction_names_from_hlo_text(text, name, family, op):
    """A TPU trace names an op by its HLO text; an op is matched, and
    added up, by the instruction's own name, never by an operand's."""
    assert trace.instruction(text) == name
    assert trace.op_family(text) == family
    assert trace.opcode(text) == op
    red = trace.Reduced(devices={"/device:TPU:0": [[text, 0, 10, ""]]},
                        spans=[["window", 0, 10]])
    fused = re.compile(r"^_altgdmin_fused_step\b").search
    assert trace.op_count(red, fused) == (name.startswith("_altgdmin"))
    # a loop's event spans its body's ops: busy, but not an op of its own
    assert trace.busy_s(red) == pytest.approx(10e-9)
    assert [f for f, _ in trace.breakdown(red)["device_ops"]] == (
        [] if op == "while" else [family])


def test_recorded_trace_parses():
    """A trace the profiler wrote through the harness's tracer (CPU: two
    annotated solves in the window).  The CPU holds no device plane, so
    only the spans are there, nested and on one clock, and every device
    query refuses rather than reading an idle device."""
    red = trace.from_xplane(str(CPU_FIXTURE))
    assert red.devices == {}
    names = [n for n, _, _ in red.spans]
    assert names.count("window") == 1 and names.count("solve") == 2
    t0, t1 = trace.window(red)
    for _, s, d in red.spans:
        assert t0 <= s and s + d <= t1
    assert trace.span_intervals(red, "solve").shape == (2, 2)
    with pytest.raises(ValueError, match="no device plane"):
        trace.busy_s(red)


def test_recorded_tpu_trace_feeds_the_kernel_reader():
    """Three iterations of an exp1.train job as a TPU v5e trace gives
    them: the fused kernel is found by its instruction's name, once per
    iteration, and leads the breakdown; the scan's loop is no op."""
    from chipbench import harness, work
    fix = harness.load_json(TPU_FIXTURE)
    red = trace.Reduced(devices=fix["devices"], spans=fix["spans"])
    shapes = dict(L=20, tpn=30, n=30, d=600, r=4)
    rec = harness.RunRecord(
        workload="exp1.train", config={}, traffic={}, n_chips=1,
        peaks=harness.load_peaks("TPU v5 lite"), spans=None,
        window_s=trace.window_len_s(red), trace=red,
        work={"shapes": shapes, "fused_iter": work.fused_iter(**shapes)})
    got = harness.load_metric_reader("node_fused_iter_roofline")(rec)
    assert got["bound"] == "bytes"
    # 3 calls of 2.787 ms against 53.3 µs of required bytes each
    assert got["value"] == pytest.approx(1.9128, abs=1e-3)
    ops = trace.breakdown(red)["device_ops"]
    assert ops[0][0] == "_altgdmin_fused_step[tpu_custom_call]"
    assert ops[0][1] == pytest.approx(3 * 2.787e-3, rel=1e-3)
    assert not any(name == "while" for name, _ in ops)
    assert 0 < trace.busy_s(red) <= trace.window_len_s(red)


@pytest.mark.parametrize("workload", ["exp1.train", "exp1.serve"])
def test_traced_run_reports_its_per_layer_metrics(workload, monkeypatch):
    """A ``--trace 1`` run on the CPU, with the profiler's output
    swapped for the hand-built device trace (the CPU has no device
    plane): every per-layer metric the cell lists that this trace can
    feed is in the line, the others are named as not read, and the line
    has the device's busy and window seconds and the breakdown."""
    from chipbench import harness
    from chipbench.tests import tiny
    monkeypatch.setattr(trace, "from_xplane", lambda path: hand_built())
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: harness.load_json(
                            harness.BENCH_DIR / "peaks.json")["devices"][
                                "TPU v5 lite"])
    out = tiny.execute(workload, seed=5, seconds=0.3, trace=True)
    assert out["correct"] is True
    listed = {m["name"] for m in harness.load_benchmark()["per_layer"]
              if workload in m["workloads"]}
    assert set(out["metrics"]) <= listed
    want = {"exp1.train": {"init_ms.train", "gd_iter_ms.train",
                           "node_fused_iter_roofline", "step_mfu.train",
                           "idle_share.train"},
            "exp1.serve": {"host_ms_per_batch.serve",
                           "idle_share.serve", "tail_p95_ms.serve",
                           "step_mfu.serve"}}[workload]
    assert want <= set(out["metrics"])
    # what a reader found nothing of is named, not dropped in silence
    assert set(out["counters"]["metrics_not_read"]) == listed - set(
        out["metrics"])
    for mfu in ("step_mfu.train", "step_mfu.serve"):
        assert out["metrics"].get(mfu, {"bound": "bytes"})["bound"] in (
            "flops", "bytes")
    assert out["device"]["window_s"] == pytest.approx(100e-9)
    assert out["device"]["busy_s"] == pytest.approx(47.5e-9)
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
