"""Host time per served batch: each ``ServingEngine.solve`` span (pack,
transfer, solve, θ to the host) minus the device-busy time inside it,
averaged over the window's batches."""
from chipbench import trace


def read(rec):
    if rec.trace is None or not rec.work["batches"]:
        return None
    spans = trace.span_intervals(rec.trace, "solve")
    if not spans.size:
        return None
    host = trace.length(spans) * 1e-9 - trace.busy_inside(rec.trace, "solve")
    return host / len(spans) * 1e3
