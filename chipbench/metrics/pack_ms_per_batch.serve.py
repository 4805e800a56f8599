"""Host time per served batch spent filling the padded request buffer:
the mean duration of the program's ``repro.serve.pack`` spans that
start in the window (``serving.engine.pack_requests``)."""
from chipbench import program_spans


def read(rec):
    spans = program_spans.in_window(rec, "repro.serve.pack")
    if not spans:
        return None
    return sum(sp[2] for sp in spans) / len(spans) * 1e3
