"""Program spans on the profiler's own clock.

``with span("materialize.problem"): ...`` marks a phase of host code as
a ``jax.profiler.TraceAnnotation`` named ``repro.materialize.problem``.
Under an active profiler (``jax.profiler.trace``, or a benchmark run
with ``--trace 1``) the span lands in the trace beside the device's ops,
so device idle time can be given to the phase the host was in.  Keyword
``counts`` become the event's stats (``serve.dispatch`` carries its
request, slot and row counts); :meth:`span.count` adds those known only
inside the span.

One ``jax.monitoring`` listener, registered on first use, adds JAX's
compile-path events to the innermost open span of the thread that
raised them:

  * ``/jax/core/compile/jaxpr_trace_duration``: ``trace_s``, ``traces``
  * ``/jax/core/compile/jaxpr_to_mlir_module_duration``: ``lower_s``,
    ``lowerings``
  * ``/jax/core/compile/backend_compile_duration`` (which also covers a
    fetch from the persistent compilation cache): ``compile_s``,
    ``compiles``

On exit under an active profiler the span writes those six stats on
its event (zeros included), and :func:`recorded` keeps it, with its
counts and stats, on ``time.perf_counter``: a reader in the same
process (the benchmark's per-layer metrics) takes the spans from there
and lines them up with the trace through a span of its own that both
clocks hold.  JAX reports a trace nested in another trace as an event
of its own, so ``trace_s`` counts such time twice;
the nested traces of cached functions take microseconds.

Spans belong in host code.  Inside a jitted or scanned function a span
would time the tracing, not the execution.  With no profiler active a
span costs one annotation object, one check of the profiler and a push
and pop on a thread-local stack.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

PREFIX = "repro."
# JAX's compile-path event -> (seconds stat, count stat)
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", "traces"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration": ("lower_s",
                                                        "lowerings"),
    "/jax/core/compile/backend_compile_duration": ("compile_s", "compiles"),
}
STATS = tuple(k for pair in COMPILE_EVENTS.values() for k in pair)

# spans that ended under an active profiler, oldest first; bounded, so
# a long profile keeps its newest spans (the trace keeps them all)
RECORDED_MAX = 1 << 16
_recorded: collections.deque = collections.deque(maxlen=RECORDED_MAX)
_local = threading.local()
_listening = False
_listen_lock = threading.Lock()


def _open() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _on_duration(event: str, duration_secs: float, **_):
    keys = COMPILE_EVENTS.get(event)
    stack = getattr(_local, "stack", None)
    if keys is None or not stack:
        return
    top = stack[-1]
    if top.compile_path is None:
        top.compile_path = dict.fromkeys(STATS, 0)
    totals = top.compile_path
    totals[keys[0]] += duration_secs
    totals[keys[1]] += 1


def _listen():
    global _listening
    with _listen_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            _listening = True


def recorded() -> list[tuple[str, float, float, dict]]:
    """The spans that ended under an active profiler, oldest first, as
    ``(name, start_s, duration_s, stats)``: ``repro.`` kept, start and
    duration on ``time.perf_counter``, stats the span's counts and its
    six compile-path stats."""
    return list(_recorded)


class span:
    """``with span(name, **counts):`` a program phase, annotated as
    ``repro.<name>``.  ``compile_path`` holds the seconds and counts of
    the compile-path events raised while it was the innermost open span
    of its thread, or None where there were none."""

    __slots__ = ("compile_path", "_name", "_counts", "_late", "_ann",
                 "_t0")

    def __init__(self, name: str, **counts):
        if not _listening:
            _listen()
        self.compile_path = None
        self._name = PREFIX + name
        self._counts = counts
        self._late: dict = {}
        self._ann = jax.profiler.TraceAnnotation(self._name, **counts)

    def count(self, **counts) -> None:
        """Add counts that the phase learns while it runs; the span
        writes them on its event as it ends."""
        self._counts = {**self._counts, **counts}
        self._late = {**self._late, **counts}

    def __enter__(self) -> "span":
        self._ann.__enter__()
        self._t0 = time.perf_counter() if self._ann.is_enabled() else None
        _open().append(self)
        return self

    def __exit__(self, *exc) -> None:
        _open().pop()
        if self._t0 is not None:
            stats = self.compile_path or dict.fromkeys(STATS, 0)
            self._ann.set_metadata(**self._late, **stats)
            _recorded.append((self._name, self._t0,
                              time.perf_counter() - self._t0,
                              {**self._counts, **stats}))
        self._ann.__exit__(*exc)
