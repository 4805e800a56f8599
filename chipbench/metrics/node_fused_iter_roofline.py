"""Share of its roofline that the fused min-B + gradient kernel
(``kernels/altgdmin_ls.node_fused_iter``) reaches: the least time its
required work takes on this chip (``work.fused_iter`` at unpadded d,
the per-node gradient as output) over its measured device time.  Each
device runs the kernel on its own L / chips nodes."""
import re

from chipbench import trace, work

# the kernel's HLO instruction is named after its jitted wrapper in
# ``kernels/ops.py`` (``_altgdmin_fused_step.7``, whether the wrapper is
# called alone or inlined into a scan), and its JAX op name, where the
# trace gives one, ends ``jit(_altgdmin_fused_step)/pallas_call``
KERNEL = re.compile(r"^_altgdmin_fused_step\b"
                    r"|jit\(_altgdmin_fused_step\)/pallas_call$")


def read(rec):
    if rec.trace is None:
        return None
    t = trace.op_seconds(rec.trace, KERNEL.search)
    calls = trace.op_count(rec.trace, KERNEL.search)
    if t <= 0 or calls == 0:
        return None
    s = dict(rec.work["shapes"])
    s["L"] //= rec.n_chips
    t_min, bound = work.roofline_s(work.fused_iter(**s), rec.peaks)
    return {"value": 100.0 * calls * t_min / t, "bound": bound}
