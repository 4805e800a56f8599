"""Host time on JAX's compile path per job: the seconds that tracing,
lowering to MLIR and backend compiling (or fetching an executable from
the persistent cache) took, as the program's ``repro.*`` spans that
start in the window record them (``trace_s``, ``lower_s``,
``compile_s``; ``repro.utils.spans`` gives each event to the innermost
open span), over the window's jobs.  Extra fields: lowerings and traces
per job."""
from chipbench import program_spans


def read(rec):
    stats = [sp[3] for sp in program_spans.in_window(rec)]
    if not stats or not rec.work["jobs"]:
        return None
    jobs = rec.work["jobs"]

    def per_job(key):
        return sum(float(s.get(key, 0)) for s in stats) / jobs
    seconds = per_job("trace_s") + per_job("lower_s") + per_job("compile_s")
    return {"value": seconds * 1e3,
            "lowerings_per_job": per_job("lowerings"),
            "traces_per_job": per_job("traces")}
