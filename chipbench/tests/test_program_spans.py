"""The per-layer readers of the program's own spans
(``chipbench.program_spans``): on spans laid by hand over the hand-built
device trace, on a program without spans, against the profiler's own
events, and in traced runs of both cells on the CPU."""
import collections
import glob
import os
import sys
import time

import jax
import pytest

from chipbench import harness, program_spans, trace, work
from chipbench.tests.test_chipbench_trace import TPU_FIXTURE, hand_built
from repro.utils import spans as spans_mod

NEW = ("compile_path_ms.train", "init_idle_ms.train", "row_fill.serve",
       "pack_ms_per_batch.serve", "put_ms_per_batch.serve")
W0 = 2000.0          # the harness's window starts here on perf_counter


def at(ns):
    """A hand-built trace's nanosecond on the perf_counter clock."""
    return W0 + ns * 1e-9


def sp(name, start, end, **stats):
    return (name, at(start), (end - start) * 1e-9,
            {**dict.fromkeys(spans_mod.STATS, 0), **stats})


# the program's spans of a job and of one served batch, laid over
# hand_built's window [0, 100): device A idle [0,10) [45,60) [70,100),
# device B idle [50,100)
HAND_SPANS = [
    sp("repro.materialize", 2, 58),
    sp("repro.materialize.problem", 2, 12),
    sp("repro.materialize.topology", 12, 14),
    sp("repro.materialize.spectral_init", 14, 40, trace_s=0.001, traces=5,
       lower_s=0.01, lowerings=3, compile_s=0.02, compiles=3),
    sp("repro.materialize.eta", 40, 58),
    sp("repro.run_experiment", 60, 99),
    sp("repro.solve.scan", 61, 90, trace_s=0.002, traces=7, lower_s=0.03,
       lowerings=1, compile_s=0.04, compiles=1),
    sp("repro.serve.solve", 56, 64),
    sp("repro.serve.pack", 56, 58),
    sp("repro.serve.put", 58, 61),
    sp("repro.serve.dispatch", 61, 62, requests=20, slots=32, rows=500,
       rows_padded=2048),
    sp("repro.serve.put", 120, 125),         # starts after the window
    sp("repro.serve.dispatch", -10, -5, requests=1, slots=32, rows=9,
       rows_padded=2048),                    # ends before it starts
]
JOBS = 2
# each reader worked out by hand on HAND_SPANS
BY_HAND = {
    "compile_path_ms.train": {
        "value": (0.001 + 0.01 + 0.02 + 0.002 + 0.03 + 0.04) * 1e3 / JOBS,
        "lowerings_per_job": 4 / JOBS, "traces_per_job": 12 / JOBS},
    # materialize [2,58): A idle [2,10) [45,58) = 21, B [50,58) = 8
    "init_idle_ms.train": {
        "value": (21 + 8) / 2 * 1e-6 / JOBS,
        "problem": 8 / 2 * 1e-6 / JOBS, "topology": 0.0,
        "spectral_init": 0.0, "eta": (13 + 8) / 2 * 1e-6 / JOBS},
    "row_fill.serve": {"value": 100 * 500 / 2048, "slot_fill": 62.5},
    "pack_ms_per_batch.serve": 2e-6,
    "put_ms_per_batch.serve": 3e-6,
}


def record_for(red, recorded_spans, **work_):
    """A RunRecord over ``red`` whose harness window starts at ``W0``,
    with ``recorded_spans`` as what the program recorded."""
    spans = harness.Spans()
    spans.records = [("window", W0, at(trace.window_len_s(red) * 1e9))]
    shapes = dict(L=20, tpn=30, n=30, d=600, r=4)
    return harness.RunRecord(
        workload="", config={}, traffic={}, n_chips=1,
        peaks=harness.load_peaks("TPU v5 lite"), spans=spans,
        window_s=trace.window_len_s(red), trace=red,
        work={"shapes": shapes, "fused_iter": work.fused_iter(**shapes),
              "batches": 1, "jobs": JOBS,
              "gram": work.task_gram(rows=500, requests=20, d=600, r=4),
              **work_})


@pytest.fixture
def log(monkeypatch):
    """The program's record of spans, emptied; the test fills it."""
    recorded = collections.deque(maxlen=spans_mod.RECORDED_MAX)
    monkeypatch.setattr(spans_mod, "_recorded", recorded)
    return recorded


@pytest.mark.parametrize("metric", NEW)
def test_new_reader_by_hand(metric, log):
    log.extend(HAND_SPANS)
    got = harness.load_metric_reader(metric)(record_for(hand_built(), log))
    assert got == pytest.approx(BY_HAND[metric])


@pytest.mark.parametrize("metric", NEW)
@pytest.mark.parametrize("program", ["without_spans", "spans_not_recorded"])
def test_new_reader_finds_nothing_without_program_spans(metric, program,
                                                        log, monkeypatch):
    """A program that predates the spans (no ``repro.utils.spans``),
    or one that recorded none in the window: the reader reports nothing
    and raises nothing."""
    if program == "without_spans":
        log.extend(HAND_SPANS)
        monkeypatch.setitem(sys.modules, "repro.utils.spans", None)
    else:
        log.extend(s for s in HAND_SPANS if s[1] > at(100) or s[1] < W0)
    rec = record_for(hand_built(), log)
    assert program_spans.in_window(rec) == []
    assert harness.load_metric_reader(metric)(rec) is None


# the accepted readers of the device trace, on the two older fixtures,
# as they read before the program had spans
OLD_READINGS = {
    "hand_built": {
        "node_fused_iter_roofline": {"value": 152330.71690214545,
                                     "bound": "bytes"},
        "idle_share.train": 52.5, "idle_share.serve": 52.5,
        "host_ms_per_batch.serve": 7.500000000000001e-06,
        "node_task_gram_roofline": None},
    "tpu_three_iterations": {
        "node_fused_iter_roofline": {"value": 1.912779039399533,
                                     "bound": "bytes"},
        "idle_share.train": 0.0, "idle_share.serve": 0.0,
        "host_ms_per_batch.serve": None, "node_task_gram_roofline": None},
}


@pytest.mark.parametrize("fixture", sorted(OLD_READINGS))
def test_existing_readers_read_as_before(fixture, log):
    """The program's spans change no reading of the accepted readers
    and nothing of the breakdown."""
    if fixture == "hand_built":
        red = hand_built()
    else:
        fix = harness.load_json(TPU_FIXTURE)
        red = trace.Reduced(devices=fix["devices"], spans=fix["spans"])
    before = trace.breakdown(red)
    for with_program in (False, True):
        if with_program:
            log.extend(HAND_SPANS)
        rec = record_for(red, log)
        for metric, want in OLD_READINGS[fixture].items():
            got = harness.load_metric_reader(metric)(rec)
            assert got == want, (metric, with_program)
        assert trace.breakdown(red) == before


def test_recorded_spans_line_up_with_the_profilers_events(tmp_path, log):
    """Each span the program recorded, laid on the trace's clock by the
    harness window's offset, starts and ends where the profiler's own
    ``repro.*`` event does."""
    from jax.profiler import ProfileData
    hspans = harness.Spans(annotate=True)
    with jax.profiler.trace(str(tmp_path)):
        with hspans("window"):
            for k in range(4):
                with spans_mod.span(f"probe{k % 2}", rows=k):
                    time.sleep(0.004)
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = sorted((e.start_ns, e.duration_ns, e.name)
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name.startswith((trace.SPAN_PREFIX,
                                          spans_mod.PREFIX)))
    (w_ns, _, _), = [e for e in events if e[2] == "chipbench.window"]
    program = [e for e in events if e[2].startswith(spans_mod.PREFIX)]
    rec = harness.RunRecord(workload="", config={}, traffic={}, n_chips=1,
                            peaks={}, spans=hspans, window_s=0.0,
                            trace=trace.Reduced(devices={}, spans=[]),
                            work={})
    got = program_spans.in_window(rec)
    assert [n for n, _, _, _ in got] == [n for _, _, n in program] == [
        "repro.probe0", "repro.probe1"] * 2
    assert [s[3]["rows"] for s in got] == [0, 1, 2, 3]
    offset = w_ns - hspans.records[-1][1] * 1e9
    for (_, s, d, _), (ev_s, ev_d, _) in zip(got, program):
        assert abs(offset + s * 1e9 - ev_s) < 1e6          # within 1 ms
        assert abs(offset + (s + d) * 1e9 - (ev_s + ev_d)) < 1e6


def test_the_record_holds_only_spans_under_a_profiler(tmp_path, log):
    with spans_mod.span("off"):
        pass
    with jax.profiler.trace(str(tmp_path)):
        with spans_mod.span("on", rows=4):
            pass
    assert [(n, st["rows"], st["lowerings"])
            for n, _, _, st in spans_mod.recorded()] == [("repro.on", 4, 0)]


@pytest.mark.parametrize("workload", ["exp1.train", "exp1.serve"])
def test_traced_run_reads_the_program_spans_it_records(workload, log,
                                                       monkeypatch):
    """A ``--trace 1`` run on the CPU, with an empty device plane laid
    into its trace (the CPU has none): the line reads the cell's new
    metrics from the spans the program recorded in the run."""
    from chipbench.tests import tiny
    read_trace, read_layers = trace.from_xplane, harness.read_per_layer
    records = []

    def with_device(path):
        red = read_trace(path)
        red.devices = {"/device:TPU:0": []}
        return red

    def keep_record(bench, workload_, reported, rec):
        records.append(rec)
        return read_layers(bench, workload_, reported, rec)
    monkeypatch.setattr(trace, "from_xplane", with_device)
    monkeypatch.setattr(harness, "read_per_layer", keep_record)
    monkeypatch.setattr(harness, "load_peaks",
                        lambda kind: harness.load_json(
                            harness.BENCH_DIR / "peaks.json")["devices"][
                                "TPU v5 lite"])
    out = tiny.execute(workload, seed=7, seconds=0.3, trace=True)
    assert out["correct"] is True
    rec, = records
    m = out["metrics"]

    def durations(name):
        return sum(d for _, _, d, _ in program_spans.in_window(rec, name))
    if workload == "exp1.train":
        jobs = out["counters"]["jobs"]
        assert len(program_spans.in_window(rec, "repro.materialize")) == jobs
        assert len(program_spans.in_window(rec, "repro.solve.scan")) == jobs
        # every job lowers its scans anew (three in the init, one solve)
        assert m["compile_path_ms.train"]["lowerings_per_job"] == 4
        assert m["compile_path_ms.train"]["value"] > 0
        # the device does nothing here, so all of materialize is idle
        # (to the microseconds by which the two clocks' window starts
        # differ)
        assert m["init_idle_ms.train"]["value"] == pytest.approx(
            durations("repro.materialize") * 1e3 / jobs, rel=0.05)
    else:
        batches = out["counters"]["batches"]
        spans = program_spans.in_window(rec, "repro.serve.dispatch")
        assert len(spans) == batches
        assert sum(st["requests"] for _, _, _, st in spans) == out[
            "counters"]["completed"]
        assert 0 < m["row_fill.serve"]["value"] < 100
        assert m["row_fill.serve"]["slot_fill"] == pytest.approx(
            100 * out["counters"]["mean_batch"] / tiny.cell(workload)[2][
                "max_batch"])
        for name in ("pack", "put"):
            assert m[f"{name}_ms_per_batch.serve"]["value"] == pytest.approx(
                durations(f"repro.serve.{name}") * 1e3 / batches)
    assert not set(NEW) & set(out["counters"]["metrics_not_read"])
