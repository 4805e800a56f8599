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
  * the registry metadata round-trips the program (topology / combine /
    spec_kwargs / takes_avail), and repro.core.runtime holds only the
    two substrate skeletons (tools/check_runtime_clean.py's invariant).
"""
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
from repro.core.program import get_program, program_names
from repro.distributed import graphs, mixing

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
