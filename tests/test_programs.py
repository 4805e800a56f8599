"""The solver-program IR and its three lowerings (PR-9 tentpole).

Every registered solver is a :class:`repro.core.program.SolverProgram`;
the registry derives its simulator / mesh / virtual-mesh entry points
from the program's lowerings.  These tests pin the refactor's
contract:

  * the simulator lowering is BITWISE identical to the legacy
    hand-written drivers in :mod:`repro.core.altgdmin`, for all 12
    solvers, on both the ``xla-ref`` and ``pallas-interpret`` backends
    (the legacy drivers stay in-tree as the oracle);
  * the mesh lowering (one node per device) and the virtual-node mesh
    lowering (L = devices × block) agree with the simulator ≤ 1e-8 for
    all 12 solvers — run in a subprocess with 8 fake host devices,
    like tests/test_runtime_mesh.py;
  * the simulator lowering jits its loop once per static key and keeps
    it: a job on fresh data of the same shapes reuses it without tracing
    or lowering, and any changed static builds a new one;
  * the registry metadata round-trips the program (topology / combine /
    spec_kwargs / takes_avail), and repro.core.runtime holds only the
    two substrate skeletons (tools/check_runtime_clean.py's invariant).
"""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from repro.api.registry import get_solver, solver_names
from repro.core import altgdmin as alg
from repro.core import (decentralized_spectral_init, generate_problem,
                        node_view)
from repro.core import program as program_mod
from repro.core.program import get_program, program_names
from repro.distributed import graphs, mixing
from repro.utils.spans import STATS, span

ALL_SOLVERS = ("dif_altgdmin", "dec_altgdmin", "centralized_altgdmin",
               "dgd_altgdmin", "exact_diffusion", "beyond_central",
               "dif_topk", "dif_quantized", "dif_event",
               "dif_partial", "dif_stale", "dif_pushsum")

# the extra SolverSpec knobs each program consumes, with the values the
# parity runs use (chosen to exercise the non-default paths)
SPEC_KW = {
    "beyond_central": dict(local_steps=2),
    "dif_topk": dict(compression_k=3),
    "dif_quantized": dict(compression="int8_stochastic"),
    "dif_event": dict(event_threshold=0.05),
}


def test_every_solver_is_a_program():
    assert program_names() == tuple(sorted(ALL_SOLVERS))
    # subset, not equality: other test modules may register ad-hoc
    # solver defs into the shared registry within the same process
    assert set(program_names()) <= set(solver_names())
    assert set(ALL_SOLVERS) <= set(solver_names())
    for name in ALL_SOLVERS:
        s = get_solver(name)
        p = get_program(name)
        assert s.program is p
        assert s.mesh_fn is not None and s.virtual_mesh_fn is not None
        assert (s.topology, s.combine) == (p.topology, p.combine)
        assert s.spec_kwargs == p.spec_kwargs
        assert s.takes_avail == p.takes_avail
        assert set(SPEC_KW.get(name, {})) <= set(p.spec_kwargs)


def test_runtime_module_is_solver_free():
    """The historical per-solver *_mesh closures must not grow back in
    repro.core.runtime (same check tools/check_runtime_clean.py runs in
    CI): only the two substrate skeletons live there."""
    r = subprocess.run(
        [sys.executable, "tools/check_runtime_clean.py"],
        capture_output=True, text=True, cwd=REPO_ROOT, timeout=60)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr}"


# ------------------------------------------------ shared tiny problem

@pytest.fixture(scope="module")
def prob8():
    L, d, r, T, n = 8, 16, 2, 24, 20
    prob = generate_problem(jax.random.PRNGKey(0), d=d, T=T, r=r, n=n,
                            L=L, kappa=1.2)
    Xg, yg = node_view(prob)
    g = graphs.erdos_renyi(L, 0.6, seed=2)
    adj = jnp.asarray(np.asarray(g.adj, dtype=float))
    W = jnp.asarray(mixing.metropolis_weights(g))
    init = decentralized_spectral_init(
        jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa, mu=prob.mu,
        r=r, T_pm=8, T_con=4)
    eta = alg.resolve_eta(None, prob.n, R_diag=init.R_diag, L=L)
    avail = jnp.asarray(np.random.default_rng(0).random((3, L)) > 0.3)
    return dict(prob=prob, Xg=Xg, yg=yg, adj=adj, W=W, U0=init.U0,
                eta=eta, T_GD=3, avail=avail)


def _legacy(name, pb, backend):
    """The hand-written driver in repro.core.altgdmin — the oracle."""
    kw = dict(eta=pb["eta"], T_GD=pb["T_GD"], U_star=pb["prob"].U_star,
              backend=backend)
    U0, Xg, yg, W = pb["U0"], pb["Xg"], pb["yg"], pb["W"]
    fns = {
        "dif_altgdmin": lambda: alg.dif_altgdmin(U0, Xg, yg, W, T_con=2,
                                                 **kw),
        "dec_altgdmin": lambda: alg.dec_altgdmin(U0, Xg, yg, W, T_con=2,
                                                 **kw),
        "centralized_altgdmin": lambda: alg.centralized_altgdmin(
            U0[0], Xg, yg, **kw),
        "dgd_altgdmin": lambda: alg.dgd_altgdmin(U0, Xg, yg, pb["adj"],
                                                 **kw),
        "exact_diffusion": lambda: alg.exact_diffusion_altgdmin(
            U0, Xg, yg, W, T_con=2, **kw),
        "beyond_central": lambda: alg.beyond_central_altgdmin(
            U0, Xg, yg, W, T_con=2, local_steps=2, **kw),
        "dif_topk": lambda: alg.dif_topk_altgdmin(
            U0, Xg, yg, W, T_con=2, compression_k=3, **kw),
        "dif_quantized": lambda: alg.dif_quantized_altgdmin(
            U0, Xg, yg, W, T_con=2, compression="int8_stochastic", **kw),
        "dif_event": lambda: alg.dif_event_altgdmin(
            U0, Xg, yg, W, T_con=2, event_threshold=0.05, **kw),
        "dif_partial": lambda: alg.dif_partial_altgdmin(
            U0, Xg, yg, W, T_con=2, avail=pb["avail"], **kw),
        "dif_stale": lambda: alg.dif_stale_altgdmin(
            U0, Xg, yg, W, T_con=2, avail=pb["avail"], **kw),
        "dif_pushsum": lambda: alg.dif_pushsum_altgdmin(
            U0, Xg, yg, W, T_con=2, avail=pb["avail"], **kw),
    }
    return fns[name]()


def _lowered(name, pb, backend):
    """The same run through the program's simulator lowering."""
    s = get_solver(name)
    kw = dict(eta=pb["eta"], T_GD=pb["T_GD"], U_star=pb["prob"].U_star,
              backend=backend, **SPEC_KW.get(name, {}))
    if s.takes_avail:
        kw["avail"] = pb["avail"]
    if s.topology == "none":
        return s.fn(pb["U0"][0], pb["Xg"], pb["yg"], **kw)
    if s.topology == "adj":
        return s.fn(pb["U0"], pb["Xg"], pb["yg"], pb["adj"], **kw)
    return s.fn(pb["U0"], pb["Xg"], pb["yg"], pb["W"], T_con=2, **kw)


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
@pytest.mark.parametrize("name", ALL_SOLVERS)
def test_simulator_lowering_bitwise_vs_legacy(name, backend, prob8):
    """The simulator lowering is the SAME program as the legacy driver —
    bit-for-bit, metrics included, on both the reference and the
    interpreted-kernel backends."""
    ref = _legacy(name, prob8, backend)
    new = _lowered(name, prob8, backend)
    for field in ("U_nodes", "B_nodes", "sd_max", "sd_mean", "spread"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, field)),
                                      np.asarray(getattr(new, field)),
                                      err_msg=f"{name}/{backend}: {field}")
    if ref.send_frac is None:
        assert new.send_frac is None
    else:
        np.testing.assert_array_equal(np.asarray(ref.send_frac),
                                      np.asarray(new.send_frac))


# --------------------------------------- the simulator's kept loops
# lower_simulator jits its scan once per static key and keeps it; a job
# on fresh data of the same shapes and statics reuses it.

# one program per mixer family: plain, neighbor, central, state,
# masked, masked_state
CACHE_SOLVERS = ("dif_altgdmin", "dgd_altgdmin", "centralized_altgdmin",
                 "dif_topk", "dif_partial", "dif_stale")


@pytest.fixture(scope="module")
def prob8_fresh(prob8):
    """prob8's shapes, graph and step size on fresh data: its own
    problem, init and availability mask, and W and the adjacency as new
    buffers of the same values (the key reads content, not identity)."""
    L = prob8["W"].shape[0]
    prob = generate_problem(jax.random.PRNGKey(5), d=16, T=24, r=2, n=20,
                            L=L, kappa=1.2)
    Xg, yg = node_view(prob)
    init = decentralized_spectral_init(
        jax.random.PRNGKey(6), Xg, yg, prob8["W"], kappa=prob.kappa,
        mu=prob.mu, r=2, T_pm=8, T_con=4)
    avail = jnp.asarray(np.random.default_rng(1).random((3, L)) > 0.3)
    return dict(prob8, prob=prob, Xg=Xg, yg=yg, U0=init.U0, avail=avail,
                W=jnp.array(prob8["W"]), adj=jnp.array(prob8["adj"]))


def _solve_scan_span(monkeypatch, fn):
    """``fn()`` with the program's spans recorded: its result, and the
    ``cached`` count and compile-path stats of the one ``solve.scan``
    span it opened."""
    opened = []

    class Spy(span):
        __slots__ = ()

        def __init__(self, name, **counts):
            super().__init__(name, **counts)
            opened.append((name, counts, self))

    monkeypatch.setattr(program_mod, "span", Spy)
    out = fn()
    jax.block_until_ready(out.U_nodes)
    (counts, s), = [(c, s) for n, c, s in opened if n == "solve.scan"]
    return out, counts["cached"], s.compile_path or dict.fromkeys(STATS, 0)


def _assert_bitwise(ref, new, tag):
    for field in ("U_nodes", "B_nodes", "sd_max", "sd_mean", "spread",
                  "send_frac"):
        a, b = getattr(ref, field), getattr(new, field)
        assert (a is None) == (b is None), f"{tag}: {field}"
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{tag}: {field}")


@pytest.mark.parametrize("backend", ["xla-ref", "pallas-interpret"])
@pytest.mark.parametrize("name", CACHE_SOLVERS)
def test_simulator_scan_is_reused_on_fresh_data(name, backend, prob8,
                                                prob8_fresh, monkeypatch):
    """A second solve on a fresh problem of the same shapes takes the
    kept loop (``cached=1``, nothing traced or lowered in
    ``solve.scan``) and still equals the legacy driver bit for bit."""
    _lowered(name, prob8, backend)
    new, cached, stats = _solve_scan_span(
        monkeypatch, lambda: _lowered(name, prob8_fresh, backend))
    assert cached == 1
    assert (stats["traces"], stats["lowerings"]) == (0, 0), stats
    _assert_bitwise(_legacy(name, prob8_fresh, backend), new,
                    f"{name}/{backend}")


def _topk(pb, W, **kw):
    """dif_topk through the program's lowering and the legacy driver:
    a state mixer with a rule knob, whose set-up reads W on the host."""
    common = dict(U_star=pb["prob"].U_star, backend="xla-ref", **kw)
    return (lambda: get_solver("dif_topk").fn(pb["U0"], pb["Xg"], pb["yg"],
                                              W, **common),
            lambda: alg.dif_topk_altgdmin(pb["U0"], pb["Xg"], pb["yg"], W,
                                          **common))


def _changed(pb, change):
    """dif_topk's call with one static or argument changed from the
    kept call's, and its W."""
    kw = dict(eta=pb["eta"], T_GD=pb["T_GD"], T_con=2, compression_k=3)
    W = pb["W"]
    if change == "W":                   # same graph, other weights
        W = 0.5 * (W + jnp.eye(W.shape[0], dtype=W.dtype))
    elif change is not None:
        kw[change] = {"eta": 1.1 * kw["eta"], "T_GD": 2, "T_con": 3,
                      "compression_k": 2}[change]
    return _topk(pb, W, **kw)


@pytest.mark.parametrize("change", ["T_con", "compression_k", "W"])
def test_simulator_scan_cache_key_sees_every_static(change, prob8,
                                                    monkeypatch):
    """Changing T_con, a rule knob or W's values builds a new loop
    (``cached=0``) whose answer is the legacy driver's: a kept loop is
    never reused for a call it does not fit."""
    _changed(prob8, None)[0]()          # the unchanged call's loop is kept
    lowered, legacy = _changed(prob8, change)
    new, cached, _ = _solve_scan_span(monkeypatch, lowered)
    assert cached == 0
    _assert_bitwise(legacy(), new, change)


@pytest.mark.parametrize("change", ["eta", "T_GD"])
def test_simulator_scan_takes_eta_and_T_GD_as_arguments(change, prob8,
                                                        monkeypatch):
    """η and the iteration count are arguments of the kept loop: a new
    step size (as each job's spectral init estimates its own) reuses it
    with nothing traced or lowered, a new T_GD reuses it and lowers the
    new length; both give the legacy answer bit for bit."""
    _changed(prob8, None)[0]()
    lowered, legacy = _changed(prob8, change)
    new, cached, stats = _solve_scan_span(monkeypatch, lowered)
    assert cached == 1
    assert (stats["lowerings"] == 0) == (change == "eta"), stats
    _assert_bitwise(legacy(), new, change)


@pytest.mark.parametrize("name", ["dif_altgdmin", "dgd_altgdmin"])
def test_simulator_scan_built_under_a_trace_holds_no_tracer(name, prob8,
                                                            monkeypatch):
    """A loop first built while an outer trace runs (``make_jaxpr``, as
    the dispatch-budget linter traces) is reused by a plain call after
    it, with the legacy answer.  In float32 the fused backends hoist the
    combine onto W^{T_con} (or the neighbour average), computed when the
    mixer is built: that set-up must not be staged into the outer
    trace."""
    f32 = {k: prob8[k].astype(jnp.float32)
           for k in ("Xg", "yg", "U0", "W", "adj")}
    prob = dataclasses.replace(
        prob8["prob"], U_star=prob8["prob"].U_star.astype(jnp.float32))
    pb = dict(prob8, prob=prob, **f32)
    jax.make_jaxpr(lambda Xg: _lowered(name, dict(pb, Xg=Xg),
                                       "pallas-interpret").U_nodes)(pb["Xg"])
    new, cached, _ = _solve_scan_span(
        monkeypatch, lambda: _lowered(name, pb, "pallas-interpret"))
    assert cached == 1
    _assert_bitwise(_legacy(name, pb, "pallas-interpret"), new, name)


def test_clear_scan_cache_lets_a_replaced_engine_method_be_traced(
        prob8, monkeypatch):
    """The key reads statics, not code: a loop kept before an engine
    method is replaced (as a planted fault replaces one) is still
    taken, and after :func:`clear_scan_cache` the next call traces the
    replacement and gives its legacy answer."""
    from repro.core.engine import AltgdminEngine
    kw = dict(eta=prob8["eta"], T_GD=prob8["T_GD"], T_con=2,
              U_star=prob8["prob"].U_star, backend="xla-ref")
    args = (prob8["U0"], prob8["Xg"], prob8["yg"], prob8["W"])
    s = get_solver("dif_altgdmin")
    s.fn(*args, **kw)                   # the unpatched loop is kept
    real = AltgdminEngine.min_grad

    def doubled(self, *a, **k):
        B, G = real(self, *a, **k)
        return B, 2.0 * G
    monkeypatch.setattr(AltgdminEngine, "min_grad", doubled)
    _, cached, _ = _solve_scan_span(monkeypatch, lambda: s.fn(*args, **kw))
    assert cached == 1
    program_mod.clear_scan_cache()
    new, cached, _ = _solve_scan_span(monkeypatch, lambda: s.fn(*args, **kw))
    assert cached == 0
    _assert_bitwise(alg.dif_altgdmin(*args, **kw), new, "patched")


def test_simulator_scan_with_a_traced_topology_is_not_kept(prob8,
                                                          monkeypatch):
    """A topology that is itself traced has no content to key on: each
    call builds its loop (``cached=0``), as the legacy driver does, and
    gives the legacy answer."""
    s = get_solver("dif_altgdmin")
    kw = dict(eta=prob8["eta"], T_GD=prob8["T_GD"], T_con=2,
              U_star=prob8["prob"].U_star, backend="xla-ref")
    ref = alg.dif_altgdmin(prob8["U0"], prob8["Xg"], prob8["yg"],
                           prob8["W"], **kw)
    for _ in range(2):
        new, cached, _ = _solve_scan_span(monkeypatch, lambda: jax.jit(
            lambda W: s.fn(prob8["U0"], prob8["Xg"], prob8["yg"], W, **kw)
        )(prob8["W"]))
        assert cached == 0
        np.testing.assert_array_equal(np.asarray(ref.U_nodes),
                                      np.asarray(new.U_nodes))


def test_scan_cache_keeps_the_newest_entries(prob8, monkeypatch):
    """The kept loops are an LRU of ``SCAN_CACHE_SIZE`` entries: with
    room for two, a third key drops the one used least recently."""
    assert program_mod.SCAN_CACHE_SIZE == 16
    monkeypatch.setattr(program_mod, "SCAN_CACHE_SIZE", 2)
    s = get_solver("dif_altgdmin")
    kw = dict(eta=prob8["eta"], T_GD=2, U_star=prob8["prob"].U_star,
              backend="xla-ref")

    def solve(T_con):
        return _solve_scan_span(monkeypatch, lambda: s.fn(
            prob8["U0"], prob8["Xg"], prob8["yg"], prob8["W"], T_con=T_con,
            **kw))[1]
    assert [solve(1), solve(2), solve(1)] == [0, 0, 1]   # 1 is now newest
    assert solve(3) == 0                                  # 2 is dropped
    assert len(program_mod._SCANS) == 2
    assert [solve(1), solve(3), solve(2)] == [1, 1, 0]


# --------------------------------------- mesh / virtual-mesh parity
# Subprocess with 8 fake host devices (device count is fixed at process
# start).  One process per substrate covers all 12 solvers to amortize
# the spectral init; the scripts print per-solver deltas on failure.

_PRELUDE = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    jax.config.update("jax_enable_x64", True)
    import sys
    sys.path.insert(0, "src")
    import numpy as np, jax.numpy as jnp
    from repro.core import (generate_problem, node_view,
                            decentralized_spectral_init)
    from repro.core import altgdmin as alg
    from repro.api.registry import get_solver
    from repro.distributed import graphs, mixing
    from repro.distributed import consensus as cons

    SPEC_KW = {
        "beyond_central": dict(local_steps=2),
        "dif_topk": dict(compression_k=3),
        "dif_quantized": dict(compression="int8_stochastic"),
        "dif_event": dict(event_threshold=0.05),
    }
    NAMES = %r

    def setup(L, p, seed):
        prob = generate_problem(jax.random.PRNGKey(0), d=16, T=3 * L,
                                r=2, n=20, L=L, kappa=1.2)
        Xg, yg = node_view(prob)
        g = graphs.erdos_renyi(L, p, seed=seed)
        adj = jnp.asarray(np.asarray(g.adj, dtype=float))
        W = jnp.asarray(mixing.metropolis_weights(g))
        init = decentralized_spectral_init(
            jax.random.PRNGKey(1), Xg, yg, W, kappa=prob.kappa,
            mu=prob.mu, r=2, T_pm=8, T_con=4)
        eta = alg.resolve_eta(None, prob.n, R_diag=init.R_diag, L=L)
        avail = jnp.asarray(np.random.default_rng(0).random((3, L)) > 0.3)
        return prob, Xg, yg, adj, W, init.U0, eta, avail

    def simulate(s, name, U0, Xg, yg, adj, W, eta, U_star, avail):
        kw = dict(eta=eta, T_GD=3, U_star=U_star, backend="xla-ref",
                  **SPEC_KW.get(name, {}))
        if s.takes_avail:
            kw["avail"] = avail
        if s.topology == "none":
            return s.fn(U0[0], Xg, yg, **kw)
        if s.topology == "adj":
            return s.fn(U0, Xg, yg, adj, **kw)
        return s.fn(U0, Xg, yg, W, T_con=2, **kw)
""" % (ALL_SOLVERS,)

MESH_SCRIPT = textwrap.dedent(_PRELUDE + """
    prob, Xg, yg, adj, W, U0, eta, avail = setup(8, 0.6, 2)
    mesh = jax.make_mesh((8,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    Madj = np.asarray(cons.neighbor_average_matrix(adj))
    fails = []
    for name in NAMES:
        s = get_solver(name)
        sim = simulate(s, name, U0, Xg, yg, adj, W, eta, prob.U_star,
                       avail)
        kw = dict(eta=eta, T_GD=3, T_con=2, backend="xla-ref",
                  U_star=prob.U_star, **SPEC_KW.get(name, {}))
        kw["W"] = Madj if s.topology == "adj" else np.asarray(W)
        if s.takes_avail:
            kw["avail"] = avail
        hw = s.mesh_fn(U0, Xg, yg, mesh, "nodes", **kw)
        dU = float(np.max(np.abs(np.asarray(hw.U_nodes)
                                 - np.asarray(sim.U_nodes))))
        dsd = float(np.max(np.abs(np.asarray(hw.sd_max)
                                  - np.asarray(sim.sd_max))))
        print(f"mesh {name:22s} dU={dU:.2e} dsd={dsd:.2e}")
        if not (dU <= 1e-8 and dsd <= 1e-8):
            fails.append((name, dU, dsd))
    assert not fails, fails
    print("OK")
""")

VIRTUAL_SCRIPT = textwrap.dedent(_PRELUDE + """
    from repro.distributed.mixing import SparseWeights
    prob, Xg, yg, adj, W, U0, eta, avail = setup(16, 0.4, 3)
    mesh = jax.make_mesh((8,), ("nodes",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    vtW = cons.VirtualTopology.from_weights(
        SparseWeights.from_dense(np.asarray(W)), 8)
    Madj = np.asarray(cons.neighbor_average_matrix(adj))
    vtA = cons.VirtualTopology.from_weights(
        SparseWeights.from_dense(Madj), 8)
    fails = []
    for name in NAMES:
        s = get_solver(name)
        sim = simulate(s, name, U0, Xg, yg, adj, W, eta, prob.U_star,
                       avail)
        kw = dict(eta=eta, T_GD=3, T_con=2, backend="xla-ref",
                  U_star=prob.U_star, **SPEC_KW.get(name, {}))
        kw["vt"] = vtA if s.topology == "adj" else vtW
        if s.takes_avail:
            kw["avail"] = avail
        hw = s.virtual_mesh_fn(U0, Xg, yg, mesh, "nodes", **kw)
        U_sim = np.asarray(sim.U_nodes)
        if s.topology == "none":
            U_sim = np.broadcast_to(U_sim[0],
                                    np.asarray(hw.U_nodes).shape)
        dU = float(np.max(np.abs(np.asarray(hw.U_nodes) - U_sim)))
        dsd = float(np.max(np.abs(np.asarray(hw.sd_max)
                                  - np.asarray(sim.sd_max))))
        print(f"virt {name:22s} dU={dU:.2e} dsd={dsd:.2e}")
        if not (dU <= 1e-8 and dsd <= 1e-8):
            fails.append((name, dU, dsd))
    assert not fails, fails
    print("OK")
""")


def _run_sub(script):
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, cwd=REPO_ROOT,
                       timeout=1800)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-4000:]}"
    assert "OK" in r.stdout


def test_mesh_lowering_matches_simulator_subprocess():
    """All 12 programs, mesh-lowered (one node per device, the weighted
    W path), agree with the simulator lowering ≤ 1e-8."""
    _run_sub(MESH_SCRIPT)


def test_virtual_mesh_lowering_matches_simulator_subprocess():
    """All 12 programs, virtual-mesh-lowered (L=16 on 8 devices, block
    of 2), agree with the simulator lowering ≤ 1e-8."""
    _run_sub(VIRTUAL_SCRIPT)
