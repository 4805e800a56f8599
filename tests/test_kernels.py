"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU): shape/dtype
sweeps per kernel + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


# ---------------------------------------------------------------- flash

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,Sq,Skv,H,Hkv,D", [
    (1, 64, 64, 2, 2, 32),       # MHA square
    (2, 32, 32, 4, 1, 16),       # MQA
    (1, 64, 64, 4, 2, 32),       # GQA group 2
    (1, 16, 48, 2, 2, 32),       # cross lengths (decode-ish, aligned ends)
    (1, 40, 40, 2, 2, 32),       # non-multiple of block → padding path
])
def test_flash_attention_matches_ref(B, Sq, Skv, H, Hkv, D, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    out = ops.flash_attention(q, k, v, blk_q=16, blk_k=16)
    want = ref.ref_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2))
    want = jnp.swapaxes(want, 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("window", [8, 16, 33])
def test_flash_attention_sliding_window(window):
    B, S, H, D = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    out = ops.flash_attention(q, k, v, window=window, blk_q=16, blk_k=16)
    want = ref.ref_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                             jnp.swapaxes(v, 1, 2), window=window)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(jnp.swapaxes(want, 1, 2)),
                               rtol=2e-5, atol=2e-5)


def test_flash_matches_model_chunked_attention():
    """The Pallas kernel, the chunked-jnp production path, and the naive
    core must all agree (same math, three implementations)."""
    from repro.models.attention import chunked_attention, attention_core
    B, S, H, D = 2, 64, 4, 32
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    a = attention_core(q, k, v, pos, pos)
    b = chunked_attention(q, k, v, pos, pos, chunk=16)
    c = ops.flash_attention(q, k, v, blk_q=16, blk_k=16)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------- SSD

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 32, 2, 16, 8, 8),
    (2, 48, 3, 8, 16, 16),
    (1, 20, 2, 16, 8, 8),        # padding path (20 % 8 ≠ 0)
    (1, 64, 1, 32, 32, 64),      # single chunk
])
def test_ssd_scan_matches_sequential_ref(B, S, H, P, N, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H),
                                           jnp.float32)) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,), jnp.float32) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(jax.random.fold_in(ks[3], 1), (B, S, N), dtype)
    D = jnp.ones((H,), jnp.float32) * 0.5
    y, h = ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk)
    y_ref, h_ref = ref.ref_ssd(x, dt, A, Bm, Cm, D)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               rtol=tol, atol=tol)


def test_ssd_chunked_model_path_matches_ref():
    """models.ssm.ssd_chunked (the jnp production path) vs sequential."""
    from repro.models.ssm import ssd_chunked
    B, S, H, P, N = 2, 40, 3, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(jax.random.fold_in(ks[3], 7), (B, S, N),
                           jnp.float32)
    D = jnp.full((H,), 0.5, jnp.float32)
    y, h = ssd_chunked(x, dt, A, Bm, Cm, D, chunk=16)
    y_ref, h_ref = ref.ref_ssd(x, dt, A, Bm, Cm, D)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------- MTRL LS

@pytest.mark.parametrize("T,n,d,r,blk_d", [
    (6, 30, 64, 4, 16),
    (3, 20, 100, 8, 32),         # d not a multiple of blk_d → padding
    (1, 50, 256, 2, 256),        # single tile
])
def test_task_gram_and_minimize_B(T, n, d, r, blk_d):
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    X = jax.random.normal(ks[0], (T, n, d), jnp.float32)
    U = jnp.linalg.qr(jax.random.normal(ks[1], (d, r), jnp.float32))[0]
    y = jax.random.normal(ks[2], (T, n), jnp.float32)
    B = ops.altgdmin_minimize_B(X, U, y, blk_d=blk_d)
    # oracle: direct lstsq per task
    A = jnp.einsum("tnd,dr->tnr", X, U)
    B_ref = jnp.stack([jnp.linalg.lstsq(A[t], y[t])[0] for t in range(T)])
    np.testing.assert_allclose(np.asarray(B), np.asarray(B_ref), rtol=1e-3,
                               atol=1e-4)
    # Gram pieces vs oracle
    from repro.kernels.altgdmin_ls import node_task_gram
    dpad = (-d) % blk_d
    Xp = jnp.pad(X, ((0, 0), (0, 0), (0, dpad)))
    Up = jnp.pad(U, ((0, dpad), (0, 0)))
    G, c = node_task_gram(Xp[None], Up[None], y[None],
                          blk_d=min(blk_d, d + dpad), interpret=True)
    G, c = G[0], c[0]
    G_ref, c_ref = ref.ref_task_gram(X, U, y)
    np.testing.assert_allclose(np.asarray(G), np.asarray(G_ref), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(c), np.asarray(c_ref), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("T,n,d,r", [(5, 25, 64, 4), (2, 30, 80, 6)])
def test_altgdmin_gradient_kernel(T, n, d, r):
    ks = jax.random.split(jax.random.PRNGKey(6), 4)
    X = jax.random.normal(ks[0], (T, n, d), jnp.float32)
    U = jnp.linalg.qr(jax.random.normal(ks[1], (d, r), jnp.float32))[0]
    B = jax.random.normal(ks[2], (T, r), jnp.float32)
    y = jax.random.normal(ks[3], (T, n), jnp.float32)
    g = ops.altgdmin_gradient(X, U, B, y, blk_d=32)
    g_ref = ref.ref_altgdmin_grad(X, U, B, y)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-4,
                               atol=1e-4)


def test_kernel_LS_matches_simulator_minimize_B():
    """The Pallas LS path must agree with the simulator's minimize_B on a
    real MTRL instance (same Cholesky route)."""
    from repro.core import generate_problem, node_view
    from repro.core.altgdmin import minimize_B
    prob = generate_problem(jax.random.PRNGKey(7), d=60, T=24, r=3, n=20,
                            L=4, kappa=1.5, dtype=jnp.float32)
    Xg, yg = node_view(prob)
    B_sim = minimize_B(jnp.broadcast_to(prob.U_star, (4,) + prob.U_star.shape),
                       Xg, yg)
    B_ker = jnp.stack([
        ops.altgdmin_minimize_B(Xg[g], prob.U_star, yg[g], blk_d=32)
        for g in range(4)])
    np.testing.assert_allclose(np.asarray(B_ker), np.asarray(B_sim),
                               rtol=1e-3, atol=1e-4)


# ---------------------------------------------------------------- gossip

@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=3, max_value=5000),
       k=st.integers(min_value=1, max_value=4))
def test_gossip_combine_matches_ref(n, k):
    key = jax.random.PRNGKey(n)
    z = jax.random.normal(key, (n,), jnp.float32)
    nbrs = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    w_self = 1.0 / (k + 1)
    w_nbr = (1.0 - w_self) / k
    weights = (w_self,) + (w_nbr,) * k
    out = ops.gossip_combine(z, nbrs, weights)
    want = ref.ref_gossip_combine(z, nbrs, weights)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(min_value=3, max_value=2000),
       k=st.integers(min_value=1, max_value=5))
def test_gossip_combine_per_shift_weights(n, k):
    """Non-uniform per-shift weights (an irregular-graph W row) through
    the fused kernel match the weighted reference."""
    key = jax.random.PRNGKey(n + 7)
    z = jax.random.normal(key, (n,), jnp.float32)
    nbrs = jax.random.normal(jax.random.fold_in(key, 1), (k, n), jnp.float32)
    weights = jax.nn.softmax(jax.random.normal(
        jax.random.fold_in(key, 2), (k + 1,)))
    out = ops.gossip_combine(z, nbrs, weights)
    want = ref.ref_gossip_combine(z, nbrs, weights)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-6,
                               atol=1e-6)


def test_gossip_combine_kernel_odd_rows():
    """Regression (PR 4): the raw kernel pads row counts not divisible
    by blk_rows instead of tripping a bare assert — M=300 with the
    default blk_rows=256 crashed before."""
    from repro.kernels import gossip_axpy
    key = jax.random.PRNGKey(3)
    z = jax.random.normal(key, (300, 8), jnp.float32)
    nbrs = jax.random.normal(jax.random.fold_in(key, 1), (2, 300, 8),
                             jnp.float32)
    weights = jnp.asarray([0.5, 0.3, 0.2], jnp.float32)
    out = gossip_axpy.gossip_combine(z, nbrs, weights, blk_rows=256,
                                      interpret=True)
    want = ref.ref_gossip_combine(z, nbrs, weights)
    assert out.shape == z.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_mix_rows_preserves_dtype():
    """Regression (PR 4): mix_rows' out_shape followed a hard-coded f32,
    silently upcasting bf16 operands in the hoisted AGREE path; the
    output dtype must follow Z."""
    key = jax.random.PRNGKey(5)
    W = jax.nn.softmax(jax.random.normal(key, (4, 4)), axis=1)
    for dtype in (jnp.bfloat16, jnp.float32):
        Z = jax.random.normal(jax.random.fold_in(key, 1),
                              (4, 37, 3)).astype(dtype)
        out = ops.mix_nodes(Z, W.astype(jnp.float32),
                            backend="pallas-interpret")
        assert out.dtype == dtype, (dtype, out.dtype)
        assert out.shape == Z.shape
        want = jnp.einsum("gh,h...->g...", W.astype(jnp.float32),
                          Z.astype(jnp.float32))
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(want.astype(dtype), np.float32),
                                   rtol=1e-2 if dtype == jnp.bfloat16
                                   else 1e-6, atol=1e-2)
