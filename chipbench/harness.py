"""Name lookup, spans, tracing and the result line, shared by the drivers.

A driver (``chipbench/drivers/<name>.py``) exposes ``run(ctx) ->
DriverResult``.  It builds what the cell's traffic asks for during
set-up, runs the measured window inside ``ctx.tracer.window()`` and its
layer calls inside ``ctx.spans(<name>)``, then reads the device's peak
memory, frees the program's state and compares what the window produced
with the plain reference.  :func:`execute` turns that into the
result object the benchmark prints.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any, Callable

import numpy as np

from chipbench import trace as _trace

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def find_workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    names = [w["name"] for w in bench["workloads"]]
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {names}")


def load_named(kind: str, name: str) -> dict:
    """``chipbench/<kind>/<name>.json``: a configuration, a traffic mix
    or a cell's check limits."""
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file {path}")
    return load_json(path)


def load_metric_reader(name: str) -> Callable:
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name.replace('.', '__')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_driver(name: str):
    return importlib.import_module(f"chipbench.drivers.{name}")


def load_peaks(device_kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no entry in "
                       f"chipbench/peaks.json (have {sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------- spans

class Spans:
    """Host spans of the harness's own calls into each layer, on
    ``time.perf_counter``.  With ``annotate`` each span is also a
    ``jax.profiler.TraceAnnotation`` named ``chipbench.<name>``, so the
    device trace can attribute idle gaps to it."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: list[tuple[str, float, float]] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(_trace.SPAN_PREFIX + name)
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> np.ndarray:
        return np.array([t1 - t0 for n, t0, t1 in self.records if n == name])


class Tracer:
    """Runs the measured window under the JAX profiler when ``enabled``;
    the trace goes to a temporary directory and is reduced (then deleted)
    by :meth:`reduce`."""

    def __init__(self, enabled: bool, spans: Spans):
        self.enabled = enabled
        self.spans = spans
        self._dir: str | None = None

    @contextlib.contextmanager
    def window(self):
        if not self.enabled:
            with self.spans(_trace.WINDOW):
                yield
            return
        import jax
        self._dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        try:
            with self.spans(_trace.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self):
        """The reduced trace (:class:`chipbench.trace.Reduced`) or None."""
        if not self.enabled or self._dir is None:
            return None
        try:
            paths = glob.glob(os.path.join(self._dir, "**", "*.xplane.pb"),
                              recursive=True)
            if not paths:
                raise FileNotFoundError(f"the profiler wrote no xplane.pb "
                                        f"under {self._dir}")
            return _trace.from_xplane(paths[0])
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


class CompileEvents:
    """Counts JAX compilations while :meth:`counting`: XLA compiles that
    missed every cache (``cache_misses``, with the persistent cache on)
    and executables fetched from the persistent cache (``cache_hits``).
    JAX's listener registry is per process, so one listener is shared."""

    _shared: "CompileEvents | None" = None

    def __init__(self):
        self.armed = False
        self.counts = {"cache_misses": 0, "cache_hits": 0}

    @classmethod
    def get(cls) -> "CompileEvents":
        if cls._shared is None:
            import jax
            cls._shared = cls()
            jax.monitoring.register_event_listener(cls._shared._on_event)
        return cls._shared

    def _on_event(self, event: str, **kwargs):
        if self.armed:
            key = event.rsplit("/", 1)[-1]
            if key in self.counts:
                self.counts[key] += 1

    @contextlib.contextmanager
    def counting(self):
        self.counts = dict.fromkeys(self.counts, 0)
        self.armed = True
        try:
            yield self.counts
        finally:
            self.armed = False


# ------------------------------------------------------------- records

@dataclasses.dataclass
class Context:
    """What a driver gets: the cell, its files and the run's knobs."""
    workload: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    devices: list
    spans: Spans
    tracer: Tracer

    @property
    def n_chips(self) -> int:
        return int(self.workload["chips"])


@dataclasses.dataclass
class DriverResult:
    attempted: int
    failed: int
    end_to_end: dict[str, float]     # metric name -> value
    checks: dict[str, float]         # compared number -> reading
    window_s: float                  # host clock, first start to last end
    memory_peak_bytes: int | None
    work: dict[str, Any]             # required work and shapes, for readers
    counters: dict[str, Any]         # what the run counted, printed as is
    check_ok: bool = True            # False when nothing could be compared


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader reads."""
    workload: str
    config: dict
    traffic: dict
    n_chips: int
    peaks: dict
    spans: Spans
    window_s: float
    trace: Any                       # chipbench.trace.Reduced or None
    work: dict


def spec_from_config(config: dict, **solver_overrides):
    """The ``ExperimentSpec`` a configuration file states, with the
    traffic's solver fields (the job's T_GD) laid over it."""
    from repro.api import ExperimentSpec
    d = json.loads(json.dumps(config["spec"]))
    d.setdefault("solver", {}).update(solver_overrides)
    return ExperimentSpec.from_dict(d)


def memory_peak(devices) -> int | None:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def compare(readings: dict[str, float], limits: dict) -> tuple[bool, dict]:
    """Each reading against its limit (``value <= limit``; a NaN fails)."""
    out, ok = {}, True
    for name, value in readings.items():
        limit = float(limits[name]["limit"])
        good = bool(np.isfinite(value) and value <= limit)
        ok &= good
        out[name] = {"value": float(value), "limit": limit}
    missing = sorted(set(limits) - set(readings))
    if missing:
        raise KeyError(f"limits without a reading: {missing}")
    return ok, out


def applies(entry: dict, workload: str, reported: set[str]) -> bool:
    """Whether a BENCHMARK.json metric belongs in this cell's line."""
    if "workloads" in entry:
        return workload in entry["workloads"]
    return entry.get("moves", entry["name"]) in reported


def read_per_layer(bench: dict, workload: str, reported: set[str],
                   rec: RunRecord) -> tuple[dict, list[str]]:
    """The cell's per-layer metrics as its readers find them in ``rec``,
    and the names of those whose reader found nothing to read."""
    per_layer, not_read = {}, []
    for m in bench["per_layer"]:
        if not applies(m, workload, reported):
            continue
        got = load_metric_reader(m["name"])(rec)
        extra = {}
        if isinstance(got, dict):
            extra = {k: v for k, v in got.items() if k != "value"}
            got = got["value"]
        if got is not None and math.isfinite(got):
            per_layer[m["name"]] = {"value": float(got), "unit": m["unit"],
                                    **extra}
        else:
            not_read.append(m["name"])
    return per_layer, not_read


def execute(workload_name: str, *, seed: int, seconds: float, trace: bool,
            devices: list, bench: dict | None = None,
            config: dict | None = None, traffic: dict | None = None,
            checks: dict | None = None, t_process: float | None = None
            ) -> dict:
    """Run one cell and return the result object it prints.  The
    configuration, traffic and limits default to the cell's files;
    tests pass smaller ones."""
    t_begin = time.perf_counter() if t_process is None else t_process
    bench = load_benchmark() if bench is None else bench
    wl = find_workload(bench, workload_name)
    config = load_named("configs", wl["config"]) if config is None else config
    traffic = (load_named("traffic", wl["traffic"]) if traffic is None
               else traffic)
    checks = load_named("checks", workload_name) if checks is None else checks
    spans = Spans(annotate=trace)
    ctx = Context(workload=wl, config=config, traffic=traffic,
                  seed=int(seed), seconds=float(seconds),
                  devices=list(devices)[:int(wl["chips"])], spans=spans,
                  tracer=Tracer(trace, spans))
    driver = load_driver(traffic["driver"])
    res: DriverResult = driver.run(ctx, t_begin=t_begin)
    reduced = ctx.tracer.reduce()
    check_ok, check_out = compare(res.checks, checks["limits"])

    e2e = {}
    for m in bench["end_to_end"]:
        if m["name"] in res.end_to_end and applies(m, workload_name,
                                                   set(res.end_to_end)):
            e2e[m["name"]] = {"value": float(res.end_to_end[m["name"]]),
                              "unit": m["unit"]}
    dev0 = ctx.devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res.memory_peak_bytes}
    out = {"correct": bool(check_ok and res.check_ok),
           "attempted": int(res.attempted), "failed": int(res.failed)}
    if trace:
        rec = RunRecord(workload=workload_name, config=config,
                        traffic=traffic, n_chips=ctx.n_chips,
                        peaks=load_peaks(dev0.device_kind), spans=spans,
                        window_s=res.window_s, trace=reduced,
                        work=res.work)
        out["metrics"], not_read = read_per_layer(bench, workload_name,
                                                  set(e2e), rec)
        # a declared metric whose reader found nothing stays out of the
        # line, and the run names it
        res.counters["metrics_not_read"] = not_read
        if reduced is not None:
            device["busy_s"] = _trace.busy_s(reduced)
            device["window_s"] = _trace.window_len_s(reduced)
            out["breakdown"] = _trace.breakdown(reduced)
    else:
        out["metrics"] = e2e
    out["device"] = device
    out["counters"] = res.counters
    out["checks"] = check_out
    return out
