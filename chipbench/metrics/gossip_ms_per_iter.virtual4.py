"""Device time of the collectives between chips per outer iteration,
mean over the cell's chips: the gossip's collective-permutes and the
per-iteration metrics' all-gather.  An async collective is its
``-start`` and ``-done`` ops; a TPU lowers the all-gather to an
``async-collective-start``/``-done`` pair and fusions that call an
``async_collective_fusion``, which this counts whole.  Extra fields:
``exposed_ms_per_iter``, the part of it in which no other op runs on the
same chip (a loop's span is no op); ``collectives_per_iter``, the
collectives the trace shows started; and
``program_collectives_per_iter``, the ``ppermutes_per_iter`` plus
``gathers_per_iter`` that the program's ``repro.solve.scan`` counts."""
import re

import numpy as np

from chipbench import program_spans, trace

STARTS = ("collective-permute", "collective-permute-start", "all-gather",
          "all-gather-start", "async-collective-start")
ENDS = ("collective-permute-done", "all-gather-done", "async-collective-done")
FUSED = "calls=%async_collective_fusion"
_SUFFIX = re.compile(r"[._]\d+$")


def family(text: str) -> str:
    """The op's instruction without XLA's numeric suffix."""
    return _SUFFIX.sub("", trace.instruction(text))


def is_collective(text: str) -> bool:
    return family(text) in STARTS + ENDS or FUSED in text


def _per_chip(ops, t0, t1):
    """(collective ns, exposed ns) of one chip's ops in the window."""
    coll = trace.clip(trace.merge(
        (s, s + d) for n, s, d, _ in ops if is_collective(n)), t0, t1)
    other = trace.clip(trace.merge(
        (s, s + d) for n, s, d, _ in ops if not is_collective(n)
        and trace.opcode(n) not in trace.CONTAINERS), t0, t1)
    return (trace.length(coll),
            trace.length(coll) - trace.length(trace.intersect(coll, other)))


def read(rec):
    red = rec.trace
    iters = rec.work["jobs"] * rec.work["T_GD"]
    if red is None or not red.devices or not iters:
        return None
    t0, t1 = trace.window(red)
    started = np.mean([sum(1 for n, s, _, _ in red.devices[dev]
                           if family(n) in STARTS and t0 <= s < t1)
                       for dev in sorted(red.devices)])
    if not started:
        return None
    per = np.array([_per_chip(red.devices[dev], t0, t1)
                    for dev in sorted(red.devices)]) * 1e-6 / iters
    got = {"value": float(per[:, 0].mean()),
           "exposed_ms_per_iter": float(per[:, 1].mean()),
           "collectives_per_iter": float(started) / iters}
    scans = program_spans.in_window(rec, "repro.solve.scan")
    if scans:
        st = scans[0][3]
        got["program_collectives_per_iter"] = (
            st.get("ppermutes_per_iter", 0) + st.get("gathers_per_iter", 0))
    return got
