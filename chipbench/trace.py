"""Reduction of a JAX profiler trace to the events the metrics read.

:func:`from_xplane` keeps, from the ``.xplane.pb`` that
``jax.profiler`` writes, the device operations (the ``XLA Ops`` line of
every ``/device:*`` plane, each with the JAX op name it came from where
the trace gives one) and the harness's own host spans (the
``TraceAnnotation`` events named ``chipbench.<span>``).  Both sit on one
clock, in nanoseconds.  A TPU trace names each op by its HLO text
(``%_altgdmin_fused_step.7 = (f32[...]) custom-call(...), ...``);
:func:`instruction` takes the instruction's own name from it.  An op is
matched by ``"<instruction> <jax op name>"``: a Pallas kernel inlined
into a jitted program is named after the jitted function that wraps it
(``_altgdmin_fused_step.7``) and its JAX op name, where the trace gives
one, ends in ``pallas_call``.  The result is plain data
(:class:`Reduced`), so a small recorded trace can be kept as a test
fixture.

Everything below measures inside the harness's ``window`` span and
averages over the devices the trace holds.
"""
from __future__ import annotations

import dataclasses
import heapq
import re
from typing import Callable, Iterable

import numpy as np

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
SPAN_PREFIX = "chipbench."
WINDOW = "window"
_SUFFIX = re.compile(r"[._]\d+$")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
# ops whose trace event spans the ops of their body
CONTAINERS = ("while", "conditional", "call")
OP_NAME_STATS = ("tf_op", "hlo_op")


@dataclasses.dataclass
class Reduced:
    """Device ops per device plane as ``[name, start_ns, duration_ns,
    jax_op_name]`` and host spans as ``[name, start_ns, duration_ns]``."""
    devices: dict[str, list[list]]
    spans: list[list]


def from_xplane(path: str) -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: dict[str, list[list]] = {}
    spans: list[list] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend([e.name, float(e.start_ns),
                                float(e.duration_ns), _op_name(e)]
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name[len(SPAN_PREFIX):],
                                      float(e.start_ns),
                                      float(e.duration_ns)])
    return Reduced(devices=devices, spans=spans)


def instruction(text: str) -> str:
    """The HLO instruction's name in an op's trace name: ``fusion.12``
    from ``%fusion.12 = f32[...] fusion(...), ...``; a bare name is
    returned as it is."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def opcode(text: str) -> str:
    """The HLO opcode in an op's trace name (``while`` from ``%while.3 =
    (s32[], f32[8]) while(...)``), or "" for a bare name."""
    _, eq, rest = text.partition(" = ")
    m = _OPCODE.search(" " + rest) if eq else None
    return m.group(1) if m else ""


def _op_name(event) -> str:
    stats = dict(event.stats)
    for key in OP_NAME_STATS:
        if key in stats:
            return str(stats[key])
    return ""


# ------------------------------------------------------------- intervals

def merge(intervals: Iterable[tuple[float, float]]) -> np.ndarray:
    """Union of [start, end) intervals as a sorted (k, 2) array."""
    iv = sorted((s, e) for s, e in intervals if e > s)
    out: list[list[float]] = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out, dtype=np.float64).reshape(-1, 2)


def clip(iv: np.ndarray, t0: float, t1: float) -> np.ndarray:
    if iv.size == 0:
        return iv
    s = np.clip(iv[:, 0], t0, t1)
    e = np.clip(iv[:, 1], t0, t1)
    keep = e > s
    return np.stack([s[keep], e[keep]], axis=1)


def length(iv: np.ndarray) -> float:
    return float(np.sum(iv[:, 1] - iv[:, 0])) if iv.size else 0.0


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two merged interval sets."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if e > s:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, dtype=np.float64).reshape(-1, 2)


def complement(iv: np.ndarray, t0: float, t1: float) -> np.ndarray:
    """[t0, t1) minus a merged interval set."""
    out, cur = [], t0
    for s, e in clip(iv, t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return np.array(out, dtype=np.float64).reshape(-1, 2)


# --------------------------------------------------------------- queries

def window(red: Reduced) -> tuple[float, float]:
    """The harness's measured window, in trace nanoseconds."""
    for name, start, dur in red.spans:
        if name == WINDOW:
            return start, start + dur
    raise ValueError("the trace holds no chipbench.window span")


def window_len_s(red: Reduced) -> float:
    t0, t1 = window(red)
    return (t1 - t0) * 1e-9


def _ops(red: Reduced, dev: str) -> list[tuple[str, float, float]]:
    """(match text, start, end) of each op: its instruction's name and
    its JAX op name."""
    return [(f"{instruction(n)} {op}", s, s + d)
            for n, s, d, op in red.devices[dev]]


def _per_device_mean(red: Reduced, f: Callable[[str], float]) -> float:
    if not red.devices:
        raise ValueError("the trace holds no device plane")
    return float(np.mean([f(dev) for dev in sorted(red.devices)]))


def busy(red: Reduced, dev: str, pred=None) -> np.ndarray:
    """Merged intervals in the window in which an op (matching ``pred``
    if given) runs on ``dev``."""
    t0, t1 = window(red)
    return clip(merge((s, e) for n, s, e in _ops(red, dev)
                      if pred is None or pred(n)), t0, t1)


def busy_s(red: Reduced) -> float:
    """Seconds of the window in which some op runs, mean over devices."""
    return _per_device_mean(red, lambda dev: length(busy(red, dev))) * 1e-9


def op_seconds(red: Reduced, pred: Callable[[str], bool]) -> float:
    """Summed duration of the window's ops matching ``pred``, mean over
    devices."""
    t0, t1 = window(red)

    def one(dev):
        return sum(min(e, t1) - max(s, t0) for n, s, e in _ops(red, dev)
                   if pred(n) and e > t0 and s < t1)
    return _per_device_mean(red, one) * 1e-9


def op_count(red: Reduced, pred: Callable[[str], bool]) -> float:
    """Ops matching ``pred`` that start in the window, mean over devices."""
    t0, t1 = window(red)
    return _per_device_mean(red, lambda dev: sum(
        1 for n, s, e in _ops(red, dev) if pred(n) and t0 <= s < t1))


def span_intervals(red: Reduced, name: str) -> np.ndarray:
    return np.array([(s, s + d) for n, s, d in red.spans if n == name],
                    dtype=np.float64).reshape(-1, 2)


def busy_inside(red: Reduced, name: str) -> float:
    """Device-busy seconds inside the host spans called ``name``, mean
    over devices."""
    spans = merge(map(tuple, span_intervals(red, name)))
    return _per_device_mean(
        red, lambda dev: length(intersect(busy(red, dev), spans))) * 1e-9


def idle_by_span(red: Reduced) -> dict[str, float]:
    """Idle device seconds in the window, each piece of idle time given
    to the innermost (shortest) harness span that covers it; idle time
    that no span but the window covers goes to ``window``.  Mean over
    devices.  One sweep over the boundaries of spans and idle
    intervals, so a window of thousands of spans reduces in well under
    a second."""
    t0, t1 = window(red)
    inner = [(s, s + d, n) for n, s, d in red.spans if n != WINDOW]
    totals: dict[str, float] = {}
    n_dev = len(red.devices)
    for dev in sorted(red.devices):
        idle = complement(busy(red, dev), t0, t1)
        for name, ns in _idle_by_innermost(idle, inner).items():
            totals[name] = totals.get(name, 0.0) + ns / n_dev
    return {k: v * 1e-9 for k, v in totals.items() if v > 0}


def _idle_by_innermost(idle: np.ndarray, spans: list) -> dict[str, float]:
    """Nanoseconds of the merged ``idle`` intervals under the shortest
    of ``spans`` ((start, end, name)) that covers each, the rest under
    ``window``; ties in length go to the span listed first."""
    if idle.size == 0:
        return {}
    cuts = np.unique(np.concatenate(
        [idle.ravel(), [x for s, e, _ in spans for x in (s, e)]]))
    cuts = cuts[(cuts >= idle[0, 0]) & (cuts <= idle[-1, 1])]
    by_start = sorted(range(len(spans)), key=lambda k: spans[k][0])
    active: list[tuple[float, int]] = []
    out: dict[str, float] = {}
    nxt = 0
    for a, b in zip(cuts[:-1], cuts[1:]):
        i = int(np.searchsorted(idle[:, 0], a, side="right")) - 1
        if i < 0 or idle[i, 1] <= a:
            continue
        while nxt < len(by_start) and spans[by_start[nxt]][0] <= a:
            k = by_start[nxt]
            heapq.heappush(active, (spans[k][1] - spans[k][0], k))
            nxt += 1
        while active and spans[active[0][1]][1] <= a:
            heapq.heappop(active)
        # an ended span deeper in the heap is popped once it surfaces
        name = spans[active[0][1]][2] if active else WINDOW
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def op_family(name: str) -> str:
    """An op's instruction name without XLA's numeric suffix
    (``fusion.12`` -> ``fusion``), to add up repeats of one op; a custom
    call keeps its target (``custom-call[QrDecompositionBlock]``)."""
    family = _SUFFIX.sub("", instruction(name))
    target = _TARGET.search(name)
    return f"{family}[{target.group(1)}]" if target else family


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took most time and the idle time by harness
    span, each as at most ``top`` ``[name, seconds]`` pairs.  A loop or
    call is left out of the ops: its body's ops are counted."""
    t0, t1 = window(red)
    per: dict[str, float] = {}
    n_dev = len(red.devices)
    for dev in red.devices:
        for n, s, d, _ in red.devices[dev]:
            e = s + d
            if e > t0 and s < t1 and opcode(n) not in CONTAINERS:
                k = op_family(n)
                per[k] = per.get(k, 0.0) + (min(e, t1) - max(s, t0)) / n_dev
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(idle_by_span(red).items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
