"""Host time per served batch spent handing the packed buffers to the
device: the mean duration of the program's ``repro.serve.put`` spans
that start in the window (``pack_requests``' two ``jnp.asarray`` calls,
a (32, n_pad, 600) float32 X and its y)."""
from chipbench import program_spans


def read(rec):
    spans = program_spans.in_window(rec, "repro.serve.put")
    if not spans:
        return None
    return sum(sp[2] for sp in spans) / len(spans) * 1e3
