"""Device-idle time per job under the program's ``repro.materialize``
spans that start in the window (problem generation, graph and weights,
spectral init, η), in ms.  Extra fields: the same under each phase's
span (``problem``, ``topology``, ``spectral_init``, ``eta``)."""
from chipbench import program_spans

PHASE = "repro.materialize"
PHASES = ("problem", "topology", "spectral_init", "eta")


def read(rec):
    if (not rec.work["jobs"] or not program_spans.in_window(rec, PHASE)
            or not rec.trace.devices):
        return None
    jobs = rec.work["jobs"]
    out = {"value": program_spans.idle_inside(rec, PHASE) / jobs * 1e3}
    for phase in PHASES:
        out[phase] = (program_spans.idle_inside(rec, f"{PHASE}.{phase}")
                      / jobs * 1e3)
    return out
