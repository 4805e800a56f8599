"""Program spans (``repro.utils.spans``): their names and nesting in a
profiler trace of a tiny job and one served batch, the counts the
serving dispatch carries, and the compile-path stats."""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, materialize, run_experiment
from repro.serving import ServingEngine
from repro.serving.engine import pack_requests
from repro.utils import spans as spans_mod
from repro.utils.spans import STATS, span

SPEC = {"problem": {"d": 16, "T": 8, "r": 2, "n": 10, "L": 4, "kappa": 1.0,
                    "noise_std": 0.0, "n_folds": 0},
        "topology": {"family": "ring", "seed": 0, "weights": "metropolis"},
        "init": {"T_pm": 2, "T_con": 2},
        "solver": {"name": "dif_altgdmin", "T_GD": 3, "T_con": 2},
        "engine": {"backend": "xla-ref"}, "substrate": "simulator"}

# child -> parent, as the program opens them
NESTING = {
    "repro.materialize.problem": "repro.materialize",
    "repro.materialize.topology": "repro.materialize",
    "repro.materialize.spectral_init": "repro.materialize",
    "repro.materialize.eta": "repro.materialize",
    "repro.materialize": "repro.run_experiment",
    "repro.run_experiment.solve": "repro.run_experiment",
    "repro.run_experiment.time_axis": "repro.run_experiment",
    "repro.solve.build": "repro.run_experiment.solve",
    "repro.solve.scan": "repro.run_experiment.solve",
    "repro.solve.refit": "repro.run_experiment.solve",
    "repro.serve.pack": "repro.serve.solve",
    "repro.serve.put": "repro.serve.solve",
    "repro.serve.dispatch": "repro.serve.solve",
    "repro.serve.theta": "repro.serve.solve",
}


def requests(sizes, d=16, seed=0):
    rng = np.random.default_rng(seed)
    return ([rng.standard_normal((t, d)) for t in sizes],
            [rng.standard_normal(t) for t in sizes])


def traced_events(tmp_path, fn):
    """``fn()`` under the profiler; the ``repro.*`` host events as
    {name: [(start_ns, end_ns, stats), ...]}."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        fn()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(spans_mod.PREFIX):
                    out.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    return out


def test_job_and_batch_spans_nest_in_a_profiler_trace(tmp_path):
    spec = ExperimentSpec.from_dict(SPEC)
    U = np.linalg.qr(np.random.default_rng(1).standard_normal((16, 2)))[0]
    engine = ServingEngine(U, max_batch=4, pad_n_to=8, backend="xla-ref")
    X_list, y_list = requests([5, 9, 3])

    def work():
        out = run_experiment(spec, jax.random.PRNGKey(0))
        jax.block_until_ready(out.U_nodes)
        np.asarray(engine.solve(X_list, y_list)[1])
    ev = traced_events(tmp_path, work)

    assert set(ev) == set(NESTING) | {"repro.run_experiment",
                                      "repro.serve.solve"}
    for name, times in ev.items():
        assert len(times) == 1, name
        stats = times[0][2]
        assert set(STATS) <= set(stats), name
    for child, parent in NESTING.items():
        (c0, c1, _), = ev[child]
        (p0, p1, _), = ev[parent]
        assert p0 <= c0 and c1 <= p1, (child, parent)
    # the phases of one parent follow each other in the program's order
    order = ["repro.materialize.problem", "repro.materialize.topology",
             "repro.materialize.spectral_init", "repro.materialize.eta",
             "repro.run_experiment.solve", "repro.run_experiment.time_axis"]
    starts = [ev[n][0][0] for n in order]
    assert starts == sorted(starts)

    # the dispatch carries the packed shapes' counts
    X, _, R = pack_requests(X_list, y_list, max_batch=4, pad_n_to=8)
    stats = ev["repro.serve.dispatch"][0][2]
    assert (stats["requests"], stats["slots"]) == (R, X.shape[0]) == (3, 4)
    assert stats["rows"] == 5 + 9 + 3
    assert stats["rows_padded"] == X.shape[0] * X.shape[1] == 4 * 16


def test_a_second_job_lowers_only_its_spectral_init(tmp_path):
    """Jobs that differ only in their data, each with the step size its
    own spectral init estimates, share the solver's jitted loop: the
    second job's ``repro.solve.scan`` counts ``cached=1`` and traces and
    lowers nothing, and the job's lowerings are the spectral init's
    three scans."""
    spec = ExperimentSpec.from_dict(SPEC)
    assert spec.solver.eta is None
    jax.block_until_ready(run_experiment(spec, jax.random.PRNGKey(0)).U_nodes)
    ev = traced_events(tmp_path, lambda: jax.block_until_ready(
        run_experiment(spec, jax.random.PRNGKey(1)).U_nodes))
    (_, _, scan), = ev["repro.solve.scan"]
    assert (scan["cached"], scan["traces"], scan["lowerings"]) == (1, 0, 0)
    (_, _, init), = ev["repro.materialize.spectral_init"]
    assert init["lowerings"] == 3
    assert sum(st["lowerings"] for times in ev.values()
               for _, _, st in times) == 3


def test_a_fresh_closure_lowers_and_a_cached_function_does_not():
    f = jax.jit(lambda x: x * 3.0 + 1.0)
    with span("probe") as first:
        f(jnp.ones(7)).block_until_ready()
    assert first.compile_path["lowerings"] > 0
    assert first.compile_path["compiles"] > 0
    assert first.compile_path["lower_s"] > 0
    with span("probe") as second:
        f(jnp.ones(7)).block_until_ready()
    assert second.compile_path is None or second.compile_path[
        "lowerings"] == 0


def test_events_go_to_the_innermost_open_span():
    with span("outer") as outer:
        with span("inner") as inner:
            jax.jit(lambda x: x - 2.0)(jnp.ones(5)).block_until_ready()
        assert spans_mod._open() == [outer]
    assert inner.compile_path["lowerings"] > 0
    assert outer.compile_path is None
    assert spans_mod._open() == []


class Recording(jax.profiler.TraceAnnotation):
    """A TraceAnnotation that records what is written on it."""
    written: list = []

    def set_metadata(self, **kw):
        Recording.written.append(kw)
        super().set_metadata(**kw)


@pytest.mark.parametrize("profiling", [False, True])
def test_stats_are_written_only_under_a_profiler(profiling, tmp_path,
                                                 monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recording)
    monkeypatch.setattr(Recording, "written", [])
    g = jax.jit(lambda x: x / 5.0)

    def call():
        with span("probe", rows=3) as s:
            g(jnp.ones(9)).block_until_ready()
        return s
    if profiling:
        with jax.profiler.trace(str(tmp_path)):
            s = call()
        assert Recording.written == [s.compile_path]
    else:
        s = call()
        assert Recording.written == []
    assert s.compile_path["lowerings"] > 0


def test_an_exception_leaves_no_span_open():
    with pytest.raises(ValueError, match="request 0 has T_new=1"):
        ServingEngine(np.eye(16, 2), max_batch=2, backend="xla-ref").solve(
            *requests([1]))
    assert spans_mod._open() == []
