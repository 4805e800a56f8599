"""The program's own spans of a traced run, for the per-layer readers.

``repro.utils.spans`` annotates each phase of the program as a
``TraceAnnotation`` named ``repro.<phase>`` and, while a profiler is
active, keeps the span in the process (:func:`repro.utils.spans.recorded`)
with its counts and compile-path stats, on ``time.perf_counter``.  The
readers run in that process, after the window.  They take the spans
that start in the harness's window, on the clock the harness's own
spans share with them, and lay a span on the device trace's clock by
the offset between the two starts of the harness's ``window`` span.

Where the program has no such spans (a program that predates them),
every function here finds none, and the readers report nothing.
"""
import numpy as np

from chipbench import trace


def _window(rec) -> tuple[float, float] | None:
    """The harness's window on ``time.perf_counter``, or None."""
    wins = [(t0, t1) for n, t0, t1 in rec.spans.records if n == trace.WINDOW]
    return wins[-1] if wins else None


def in_window(rec, name: str | None = None) -> list[tuple]:
    """The program's spans that start in the window, all or those
    called ``name`` (``repro.`` included), as ``(name, start_s,
    duration_s, stats)`` on ``time.perf_counter``."""
    win = _window(rec)
    if rec.trace is None or win is None:
        return []
    try:
        from repro.utils.spans import recorded
    except ImportError:
        return []
    return [sp for sp in recorded()
            if win[0] <= sp[1] < win[1] and (name is None or sp[0] == name)]


def idle_inside(rec, name: str) -> float:
    """Device-idle seconds of the window inside the program's spans
    called ``name`` that start in it, mean over the trace's devices."""
    red = rec.trace
    t0, t1 = trace.window(red)
    offset = t0 - _window(rec)[0] * 1e9
    spans = trace.merge((offset + s * 1e9, offset + (s + d) * 1e9)
                        for _, s, d, _ in in_window(rec, name))
    return float(np.mean([
        trace.length(trace.intersect(
            trace.complement(trace.busy(red, dev), t0, t1), spans))
        for dev in sorted(red.devices)])) * 1e-9
