"""Share of the rows each served batch dispatches that hold a request's
samples: Σ ``rows`` (real) ÷ Σ ``rows_padded`` (slots × padded T_new)
over the window's ``repro.serve.dispatch`` spans, in %.  Extra field:
``slot_fill``, Σ ``requests`` ÷ Σ ``slots``, in %."""
from chipbench import program_spans

COUNTS = ("rows", "rows_padded", "requests", "slots")


def read(rec):
    spans = program_spans.in_window(rec, "repro.serve.dispatch")
    n = {k: sum(float(sp[3].get(k, 0)) for sp in spans) for k in COUNTS}
    if not n["rows_padded"] or not n["slots"]:
        return None
    return {"value": 100.0 * n["rows"] / n["rows_padded"],
            "slot_fill": 100.0 * n["requests"] / n["slots"]}
