"""Share of its roofline that the serving Gram kernel
(``kernels/altgdmin_ls.node_task_gram``) reaches: the least time the
window's real (unpadded) rows need (``work.task_gram``) over the
kernel's measured device time."""
import re

from chipbench import trace, work

# named after its jitted wrapper, as node_fused_iter_roofline says
KERNEL = re.compile(r"^_altgdmin_node_minimize_B\b"
                    r"|jit\(_altgdmin_node_minimize_B\)/pallas_call$")


def read(rec):
    if rec.trace is None:
        return None
    t = trace.op_seconds(rec.trace, KERNEL.search)
    if t <= 0:
        return None
    t_min, bound = work.roofline_s(rec.work["gram"], rec.peaks)
    return {"value": 100.0 * t_min / t, "bound": bound}
