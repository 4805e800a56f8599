"""Fused node-batched AltGDmin engine: backend registry semantics, parity
of every backend against the pure-jnp oracles (dtypes, padding, tpn=1),
identical sd_max trajectories across backends for all four algorithms
(driven through the declarative API), and the structural FLOP guarantee —
the fused kernel streams A = X_t U exactly once per task (the unfused
pair builds it twice)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (EngineSpec, ExperimentSpec, InitSpec, ProblemSpec,
                       SolverSpec, TopologySpec, run_experiment,
                       solver_names)
from repro.core import dif_altgdmin
from repro.core.engine import (AltgdminEngine, default_engine_backend,
                               resolve_engine)
from repro.distributed import circulant_weights
from repro.kernels import altgdmin_ls as ls
from repro.kernels import ops, ref


def _instance(L=3, tpn=4, n=20, d=100, r=4, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    X = jax.random.normal(ks[0], (L, tpn, n, d), dtype)
    U = jnp.stack([
        jnp.linalg.qr(jax.random.normal(jax.random.fold_in(ks[1], g),
                                        (d, r), jnp.float32))[0]
        for g in range(L)]).astype(dtype)
    y = jax.random.normal(ks[2], (L, tpn, n), dtype)
    return X, U, y


# ---------------------------------------------------------------- registry

def test_backend_registry_rejects_unknown():
    with pytest.raises(ValueError):
        ops.resolve_backend("vulkan")
    with pytest.raises(ValueError):
        AltgdminEngine("vulkan")


def test_backend_default_and_scope():
    base = ops.default_backend()
    assert base in ops.BACKENDS
    with ops.backend_scope("xla-ref"):
        assert ops.default_backend() == "xla-ref"
        with ops.backend_scope("pallas-interpret"):
            assert ops.default_backend() == "pallas-interpret"
        assert ops.default_backend() == "xla-ref"
    assert ops.default_backend() == base


def test_engine_honors_backend_scope_and_rejects_conflicts():
    with ops.backend_scope("pallas-interpret"):
        assert AltgdminEngine().backend == "pallas-interpret"
    eng = AltgdminEngine("xla-ref")
    assert resolve_engine(eng, "xla-ref") is eng
    assert resolve_engine(eng) is eng
    with pytest.raises(ValueError):
        resolve_engine(eng, "pallas-interpret")


def test_engine_backend_env(monkeypatch):
    monkeypatch.setenv("REPRO_ENGINE_BACKEND", "pallas-interpret")
    assert default_engine_backend() == "pallas-interpret"
    monkeypatch.delenv("REPRO_ENGINE_BACKEND")
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "xla-ref")
    assert default_engine_backend() == "xla-ref"
    monkeypatch.delenv("REPRO_KERNEL_BACKEND")
    assert default_engine_backend() in ("pallas", "xla-ref")


# ---------------------------------------------------------------- parity

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("L,tpn,n,d,r,blk_d", [
    (3, 4, 20, 100, 4, 32),      # d not a multiple of blk_d → padding
    (2, 1, 25, 64, 3, 64),       # tpn = 1
    (4, 5, 16, 256, 6, 256),     # single d tile
])
def test_fused_step_matches_ref(L, tpn, n, d, r, blk_d, dtype):
    X, U, y = _instance(L, tpn, n, d, r, dtype)
    B_ref, G_ref = ops.altgdmin_fused_step(X, U, y, blk_d=blk_d,
                                           backend="xla-ref")
    B, G = ops.altgdmin_fused_step(X, U, y, blk_d=blk_d,
                                   backend="pallas-interpret")
    tol = dict(rtol=1e-4, atol=1e-4) if dtype == jnp.float32 else \
        dict(rtol=5e-2, atol=5e-2)
    np.testing.assert_allclose(np.asarray(B, np.float32),
                               np.asarray(B_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(G, np.float32),
                               np.asarray(G_ref, np.float32), **tol)


def test_fused_step_matches_per_task_oracles():
    """Cross-check against kernels/ref.py directly (not just the xla-ref
    dispatch route): per-node lstsq + gradient oracle."""
    L, tpn, n, d, r = 3, 4, 20, 96, 4
    X, U, y = _instance(L, tpn, n, d, r)
    B, G = ops.altgdmin_fused_step(X, U, y, blk_d=32,
                                   backend="pallas-interpret")
    for g in range(L):
        A = jnp.einsum("tnd,dr->tnr", X[g], U[g])
        B_or = jnp.stack([jnp.linalg.lstsq(A[t], y[g, t])[0]
                          for t in range(tpn)])
        np.testing.assert_allclose(np.asarray(B[g]), np.asarray(B_or),
                                   rtol=1e-3, atol=1e-4)
        G_or = ref.ref_altgdmin_grad(X[g], U[g], B_or, y[g])
        np.testing.assert_allclose(np.asarray(G[g]), np.asarray(G_or),
                                   rtol=1e-3, atol=1e-3)


def test_node_minimize_and_gradient_match_ref():
    L, tpn, n, d, r = 2, 3, 18, 80, 5
    X, U, y = _instance(L, tpn, n, d, r)
    B_ref = ops.altgdmin_node_minimize_B(X, U, y, blk_d=32,
                                         backend="xla-ref")
    B = ops.altgdmin_node_minimize_B(X, U, y, blk_d=32,
                                     backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(B), np.asarray(B_ref),
                               rtol=1e-4, atol=1e-5)
    G_ref = ops.altgdmin_node_gradient(X, U, B_ref, y, blk_d=32,
                                       backend="xla-ref")
    G = ops.altgdmin_node_gradient(X, U, B_ref, y, blk_d=32,
                                   backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref),
                               rtol=1e-4, atol=1e-4)


def test_mix_nodes_matches_agree_power():
    from repro.core.agree import agree_power
    L = 8
    W = jnp.asarray(circulant_weights(L, (-1, 1)), jnp.float32)
    Wp = jnp.linalg.matrix_power(W, 5)
    Z = jax.random.normal(jax.random.PRNGKey(2), (L, 7, 3), jnp.float32)
    out = ops.mix_nodes(Z, Wp, backend="pallas-interpret")
    want = agree_power(Z, W, 5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


# --------------------------------------------------- FLOP structure

def _count_a_builds(fn, *args, n, blk_d, r):
    """Count dot_general eqns inside the pallas_call body that build the
    streamed A accumulator: an (n, blk_d) X tile contracted with a
    (blk_d, r) U tile."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    def walk(jx):
        total = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "dot_general":
                shapes = sorted(v.aval.shape for v in eqn.invars)
                if shapes == sorted([(n, blk_d), (blk_d, r)]):
                    total += 1
            for v in eqn.params.values():
                vals = v if isinstance(v, (list, tuple)) else [v]
                for item in vals:
                    inner = getattr(item, "jaxpr", item)
                    if hasattr(inner, "eqns"):
                        total += walk(inner)
        return total

    return walk(jaxpr.jaxpr)


def test_fused_kernel_builds_A_exactly_once():
    """Acceptance: the fused kernel performs exactly ONE streamed
    accumulation of A = X_t U per task per iteration, while the unfused
    gram+grad pair performs two (the gradient's pass-0 recompute)."""
    L, tpn, n, d, r, blk = 2, 3, 20, 64, 4, 32
    X, U, y = _instance(L, tpn, n, d, r)
    B = ops.altgdmin_node_minimize_B(X, U, y, blk_d=blk,
                                     backend="xla-ref")

    fused = _count_a_builds(
        lambda X, U, y: ls.node_fused_iter(X, U, y, blk_d=blk,
                                           interpret=True),
        X, U, y, n=n, blk_d=blk, r=r)
    gram = _count_a_builds(
        lambda X, U, y: ls.node_task_gram(X, U, y, blk_d=blk,
                                          interpret=True),
        X, U, y, n=n, blk_d=blk, r=r)
    grad = _count_a_builds(
        lambda X, U, B, y: ls.node_task_grad_tiles(X, U, B, y, blk_d=blk,
                                                   interpret=True),
        X, U, B, y, n=n, blk_d=blk, r=r)

    assert fused == 1, f"fused kernel builds A {fused}× per task"
    assert gram + grad == 2, (gram, grad)


# ------------------------------------------------- trajectory parity

API_SPEC = ExperimentSpec(
    problem=ProblemSpec(d=60, T=24, r=3, n=25, L=6, kappa=1.5),
    topology=TopologySpec(family="ring", weights="circulant"),
    init=InitSpec(T_pm=20, T_con=8),
    solver=SolverSpec(name="dif_altgdmin", T_GD=50, T_con=3))


def _with(spec, *, solver=None, backend=None, **solver_kw):
    if solver is not None or solver_kw:
        spec = dataclasses.replace(
            spec, solver=dataclasses.replace(
                spec.solver, **({"name": solver} if solver else {}),
                **solver_kw))
    if backend is not None:
        spec = dataclasses.replace(spec, engine=EngineSpec(backend=backend))
    return spec


@pytest.mark.parametrize("algo", sorted(solver_names()))
def test_all_algorithms_trajectory_parity(algo):
    """Acceptance: identical sd_max trajectories on xla-ref vs fused
    backends (rtol=1e-4) for every registered solver, driven through
    the declarative API."""
    a = run_experiment(_with(API_SPEC, solver=algo, backend="xla-ref"),
                       key=0)
    b = run_experiment(_with(API_SPEC, solver=algo,
                             backend="pallas-interpret"), key=0)
    np.testing.assert_allclose(a.sd_max, b.sd_max, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(a.B_nodes, np.float32),
                               np.asarray(b.B_nodes, np.float32),
                               rtol=1e-3, atol=1e-4)


def test_engine_xla_ref_is_bit_identical_to_seed_path():
    """The xla-ref engine IS the seed code path — same arrays out, no
    tolerance — whether selected via the spec or injected pre-built."""
    spec = _with(API_SPEC, T_GD=10, T_con=2, backend="xla-ref")
    res = run_experiment(spec, key=0)
    res2 = run_experiment(spec, key=0, engine=AltgdminEngine("xla-ref"))
    np.testing.assert_array_equal(np.asarray(res.U_nodes),
                                  np.asarray(res2.U_nodes))
    # and the legacy driver with the same materialized pieces agrees
    m = res.materialized
    legacy = dif_altgdmin(m.init.U0, m.Xg, m.yg, m.W, T_con=2, eta=m.eta,
                          T_GD=10, U_star=m.problem.U_star,
                          backend="xla-ref")
    np.testing.assert_array_equal(np.asarray(res.U_nodes),
                                  np.asarray(legacy.U_nodes))


def test_sample_split_fold_path_runs_fused():
    """With a fold axis the min and gradient halves see different data, so
    the engine must take the two-dispatch path — and still match xla-ref."""
    L, tpn, n, d, r, F = 3, 2, 15, 48, 3, 2
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    Xg = jax.random.normal(ks[0], (F, L, tpn, n, d), jnp.float32)
    yg = jax.random.normal(ks[1], (F, L, tpn, n), jnp.float32)
    U0 = jnp.stack([
        jnp.linalg.qr(jax.random.normal(jax.random.fold_in(ks[2], g),
                                        (d, r), jnp.float32))[0]
        for g in range(L)])
    W = jnp.asarray(circulant_weights(L, (-1, 1)))
    a = dif_altgdmin(U0, Xg, yg, W, eta=1e-3, T_GD=5, T_con=2,
                     backend="xla-ref")
    b = dif_altgdmin(U0, Xg, yg, W, eta=1e-3, T_GD=5, T_con=2,
                     backend="pallas-interpret")
    np.testing.assert_allclose(np.asarray(a.sd_max), np.asarray(b.sd_max),
                               rtol=1e-4, atol=1e-5)
