"""jit'd public wrappers around the Pallas kernels, plus the backend
dispatch registry.

Every op routes through one of three named backends:

  * ``pallas``           — compiled Pallas (the TPU production path);
  * ``pallas-interpret`` — same kernel body executed in interpret mode
                           (CPU-exact validation of the TPU code path);
  * ``xla-ref``          — the pure-jnp oracle from :mod:`repro.kernels.ref`
                           (XLA decides the schedule; numerics fallback).

Selection order: explicit ``backend=`` argument → ``set_default_backend``
→ ``REPRO_KERNEL_BACKEND`` env var → ``pallas`` on TPU / ``pallas-interpret``
elsewhere.  Wrappers also handle padding to block multiples and layout
conversion from the model's (B, S, H, D) convention to the kernels'
(B, H, S, D).
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp

from repro.utils import env as env_registry
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import altgdmin_ls as _ls
from repro.kernels import compress as _cp
from repro.kernels import gossip_axpy as _ga
from repro.kernels import ref as _ref


# ------------------------------------------------------------ dispatch

BACKENDS = ("pallas", "pallas-interpret", "xla-ref")
_default_backend: str | None = None


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _validate(name: str) -> str:
    if name not in BACKENDS:
        raise ValueError(f"unknown kernel backend {name!r}; "
                         f"expected one of {BACKENDS}")
    return name


def default_backend(*, extra_env: str | None = None,
                    off_tpu_fallback: str = "pallas-interpret") -> str:
    """The backend used when an op gets ``backend=None``.  Resolution:
    programmatic override (set_default_backend / backend_scope) →
    ``extra_env`` (if given) → ``REPRO_KERNEL_BACKEND`` → ``pallas`` on
    TPU / ``off_tpu_fallback`` elsewhere.  The AltGDmin engine shares
    this chain with ``extra_env="REPRO_ENGINE_BACKEND"`` and an
    ``xla-ref`` fallback (seed-numerics default off-TPU).

    Env reads go through the :mod:`repro.utils.env` registry, which
    validates at resolve time: a bad value fails with a message naming
    the offending variable, and an undeclared variable name fails at
    the registry instead of silently reading nothing."""
    if _default_backend is not None:
        return _default_backend
    for var in (extra_env, "REPRO_KERNEL_BACKEND"):
        env = env_registry.read_choice(var, BACKENDS) if var else None
        if env:
            return env
    return "pallas" if _on_tpu() else _validate(off_tpu_fallback)


def set_default_backend(name: str | None) -> None:
    """Process-wide override (None restores env/auto selection)."""
    global _default_backend
    _default_backend = None if name is None else _validate(name)


@contextlib.contextmanager
def backend_scope(name: str):
    """Temporarily select a backend for every op in the ``with`` body."""
    global _default_backend
    prev = _default_backend
    set_default_backend(name)
    try:
        yield
    finally:
        _default_backend = prev


def resolve_backend(backend: str | None) -> str:
    return default_backend() if backend is None else _validate(backend)


def _interp(backend: str) -> bool:
    """interpret flag for the two Pallas backends (callers must have
    routed xla-ref elsewhere already).  The compiled kernels lower for
    the TPU only with x64 off: Mosaic takes int32 grid ids and index
    maps, and under x64 their constants are int64."""
    if backend == "pallas" and jax.config.jax_enable_x64:
        raise ValueError("backend 'pallas' needs jax_enable_x64 off; run "
                         "the compiled kernels in float32 (x64 is the "
                         "CPU oracle's mode: use 'pallas-interpret' or "
                         "'xla-ref' there)")
    return backend != "pallas"


# ------------------------------------------------------------ attention

def flash_attention(q, k, v, *, causal=True, window=None, blk_q=128,
                    blk_k=128, backend=None):
    """Model layout: q (B,S,H,D); k,v (B,Skv,Hkv,D) → (B,S,H,D)."""
    return _flash_attention(q, k, v, causal=causal, window=window,
                            blk_q=blk_q, blk_k=blk_k,
                            backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("causal", "window", "blk_q",
                                             "blk_k", "backend"))
def _flash_attention(q, k, v, *, causal, window, blk_q, blk_k, backend):
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    qT = jnp.swapaxes(q, 1, 2)
    kT = jnp.swapaxes(k, 1, 2)
    vT = jnp.swapaxes(v, 1, 2)
    if backend == "xla-ref":
        o = _ref.ref_attention(qT, kT, vT, causal=causal, window=window,
                               scale=D ** -0.5)
        return jnp.swapaxes(o, 1, 2)
    blk_q_ = min(blk_q, Sq)
    blk_k_ = min(blk_k, Skv)
    pq = (-Sq) % blk_q_
    pk = (-Skv) % blk_k_
    if pq:
        qT = jnp.pad(qT, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        # right-pad keys: padded slots sit above the causal diagonal of
        # every real query (offset uses REAL lengths), so causal masking
        # excludes them for free
        kT = jnp.pad(kT, ((0, 0), (0, 0), (0, pk), (0, 0)))
        vT = jnp.pad(vT, ((0, 0), (0, 0), (0, pk), (0, 0)))
    o = _fa.flash_attention(qT, kT, vT, causal=causal, window=window,
                            scale=D ** -0.5, blk_q=blk_q_, blk_k=blk_k_,
                            offset=Skv - Sq, interpret=_interp(backend))
    if pq:
        o = o[:, :, :Sq]
    return jnp.swapaxes(o, 1, 2)


# ------------------------------------------------------------ SSD

def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk=128, backend=None):
    """Model layout: x (B,S,H,P); dt (B,S,H); Bm/Cm (B,S,N) →
    (y (B,S,H,P), h_final (B,H,P,N))."""
    return _ssd_scan(x, dt, A, Bm, Cm, D, chunk=chunk,
                     backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("chunk", "backend"))
def _ssd_scan(x, dt, A, Bm, Cm, D, *, chunk, backend):
    if backend == "xla-ref":
        return _ref.ref_ssd(x, dt, A, Bm, Cm, D)
    B, S, H, P = x.shape
    chunk_ = min(chunk, S)
    pad = (-S) % chunk_
    xT = jnp.swapaxes(x, 1, 2)                       # (B,H,S,P)
    dtT = jnp.swapaxes(dt, 1, 2)                     # (B,H,S)
    if pad:
        xT = jnp.pad(xT, ((0, 0), (0, 0), (0, pad), (0, 0)))
        dtT = jnp.pad(dtT, ((0, 0), (0, 0), (0, pad)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    y, h = _ssd.ssd_scan(xT, dtT, A, Bm, Cm, D, chunk=chunk_,
                         interpret=_interp(backend))
    y = jnp.swapaxes(y[:, :, :S], 1, 2)
    return y, h


# ------------------------------------------------------------ MTRL LS

def _solve_spd(G, c):
    """b = G⁻¹ c for one tiny SPD system, by the fused kernel's own
    unrolled Cholesky in plain array ops.  Not ``jax.scipy.linalg.solve``:
    compiled for a multi-chip v5e mesh, its ``Cholesky`` custom call can be
    given a result layout with the two minor dims swapped, and the factor
    is then read transposed (wrong B on four chips, right on one)."""
    return _ls._chol_solve_unrolled(G, c, G.shape[-1])


def _pad_d(X, U, blk_d):
    """Pad the streamed d axis (last of X, second-to-last of U) to a
    block multiple.  Zero columns contribute nothing to A = X U, so the
    Gram/gradient results are exact after trimming."""
    d = X.shape[-1]
    blk = min(blk_d, d)
    pad = (-d) % blk
    if pad:
        X = jnp.pad(X, ((0, 0),) * (X.ndim - 1) + ((0, pad),))
        U = jnp.pad(U, ((0, 0),) * (U.ndim - 2) + ((0, pad), (0, 0)))
    return X, U, blk


def altgdmin_minimize_B(X, U, y, *, blk_d=256, backend=None):
    """b_t = (X_t U)† y_t for one node's tasks (the node-batched kernel
    with L = 1).  X: (T,n,d); U: (d,r); y: (T,n) → B (T,r)."""
    return altgdmin_node_minimize_B(X[None], U[None], y[None], blk_d=blk_d,
                                    backend=backend)[0]


def altgdmin_gradient(X, U, B, y, *, blk_d=256, backend=None):
    """∇_U f = Σ_t X_tᵀ(X_t U b_t − y_t) b_tᵀ for one node (the
    node-batched kernel with L = 1).  X: (T,n,d); U: (d,r); B: (T,r);
    y: (T,n) → (d,r)."""
    return altgdmin_node_gradient(X[None], U[None], B[None], y[None],
                                  blk_d=blk_d, backend=backend)[0]


# ---------------------------------------------- MTRL LS (node-batched)

def altgdmin_node_minimize_B(X, U, y, *, blk_d=256, backend=None):
    """Node-batched min step: all L·tpn task systems in one dispatch.
    X: (L,tpn,n,d); U: (L,d,r); y: (L,tpn,n) → B (L,tpn,r)."""
    return _altgdmin_node_minimize_B(X, U, y, blk_d=blk_d,
                                     backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("blk_d", "backend"))
def _altgdmin_node_minimize_B(X, U, y, *, blk_d, backend):
    if backend == "xla-ref":
        G, c = jax.vmap(_ref.ref_task_gram)(X, U, y)
    else:
        Xp, Up, blk = _pad_d(X, U, blk_d)
        G, c = _ls.node_task_gram(Xp, Up, y, blk_d=blk,
                                  interpret=_interp(backend))
    return jax.vmap(jax.vmap(_solve_spd))(G, c)


def altgdmin_node_gradient(X, U, B, y, *, blk_d=256, backend=None):
    """Node-batched gradients with a given B (sample-split path).
    X: (L,tpn,n,d); U: (L,d,r); B: (L,tpn,r); y: (L,tpn,n) → (L,d,r)."""
    return _altgdmin_node_gradient(X, U, B, y, blk_d=blk_d,
                                   backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("blk_d", "backend"))
def _altgdmin_node_gradient(X, U, B, y, *, blk_d, backend):
    if backend == "xla-ref":
        return jax.vmap(_ref.ref_altgdmin_grad)(X, U, B, y)
    d = X.shape[3]
    Xp, Up, blk = _pad_d(X, U, blk_d)
    tiles = _ls.node_task_grad_tiles(Xp, Up, B, y, blk_d=blk,
                                     interpret=_interp(backend))
    return jnp.sum(tiles, axis=1)[:, :d]


def altgdmin_fused_step(X, U, y, *, blk_d=256, backend=None):
    """The fused engine iteration (min-B + gradient, one A build, one
    dispatch).  X: (L,tpn,n,d); U: (L,d,r); y: (L,tpn,n) →
    (B (L,tpn,r), grad (L,d,r))."""
    return _altgdmin_fused_step(X, U, y, blk_d=blk_d,
                                backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("blk_d", "backend"))
def _altgdmin_fused_step(X, U, y, *, blk_d, backend):
    if backend == "xla-ref":
        G, c = jax.vmap(_ref.ref_task_gram)(X, U, y)
        B = jax.vmap(jax.vmap(_solve_spd))(G, c)
        return B, jax.vmap(_ref.ref_altgdmin_grad)(X, U, B, y)
    d = X.shape[3]
    Xp, Up, blk = _pad_d(X, U, blk_d)
    B, tiles = _ls.node_fused_iter(Xp, Up, y, blk_d=blk,
                                   interpret=_interp(backend))
    return B, jnp.sum(tiles, axis=1)[:, :d]


# ------------------------------------------------------------ gossip

def gossip_combine(z, neighbors, weights, *, backend=None):
    """Fused z ← w₀·z + Σ_k w_{k+1}·neighbors[k] over arbitrary-shape z.
    ``weights``: (K+1,) per-shift values — a uniform ring passes the same
    neighbour weight K times; arbitrary weighted topologies pass their
    own W-row slice.  ONE kernel dispatch either way."""
    return _gossip_combine(z, neighbors,
                           jnp.asarray(weights, jnp.float32),
                           backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("backend",))
def _gossip_combine(z, neighbors, weights, *, backend):
    if backend == "xla-ref":
        return _ref.ref_gossip_combine(z, neighbors, weights)
    shape = z.shape
    flat = z.reshape(-1)
    n = flat.shape[0]
    C, R = 256, 8                 # lane width × row tile
    pad = (-n) % (C * R)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    nbr = neighbors.reshape(neighbors.shape[0], -1)
    if pad:
        nbr = jnp.pad(nbr, ((0, 0), (0, pad)))
    M = flat.shape[0] // C
    out = _ga.gossip_combine(flat.reshape(M, C),
                             nbr.reshape(neighbors.shape[0], M, C),
                             weights, blk_rows=R,
                             interpret=_interp(backend))
    return out.reshape(-1)[:n].reshape(shape)


def compress_topk(M, k, *, backend=None):
    """Rank-preserving top-k ROW sparsification of node blocks: per
    (d, r) block the k rows with the largest squared row norms.
    M: (N, d, r) → (vals (N, k, r) in M.dtype, descending row-norm
    order; idx (N, k) int32).  The wire carries (vals, idx) — k·(r+1)
    entries instead of d·r."""
    return _compress_topk(M, k=int(k), backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("k", "backend"))
def _compress_topk(M, *, k, backend):
    if M.ndim != 3:
        raise ValueError(f"compress_topk wants node-batched (N, d, r) "
                         f"blocks, got shape {M.shape}")
    if not 1 <= k <= M.shape[1]:
        raise ValueError(f"compress_topk needs 1 <= k <= d, got k={k}, "
                         f"d={M.shape[1]}")
    if backend == "xla-ref":
        return _ref.ref_compress_topk(M, k)
    return _cp.compress_topk(M, k, interpret=_interp(backend))


def dequant(q, scale, *, backend=None):
    """Decode an int8 wire payload: q · scale per node block (f32
    accumulation on the kernel backends).  q: (N, d, r) int8;
    scale: (N, 1, 1) → (N, d, r) in scale.dtype."""
    return _dequant(q, scale, backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("backend",))
def _dequant(q, scale, *, backend):
    if backend == "xla-ref":
        return _ref.ref_dequant(q, scale)
    return _cp.dequant(q, scale, interpret=_interp(backend))


def mix_nodes(Z, W, *, blk_c=512, backend=None):
    """Consensus combine Z ← W Z over the leading node axis for a dense
    precomputed mixer (e.g. W^{T_con}): the whole AGREE phase in one
    fused sweep.  Z: (L, ...); W: (L, L) → same shape AND dtype as Z
    (accumulation is f32)."""
    return _mix_nodes(Z, W, blk_c=blk_c, backend=resolve_backend(backend))


@functools.partial(jax.jit, static_argnames=("blk_c", "backend"))
def _mix_nodes(Z, W, *, blk_c, backend):
    L = Z.shape[0]
    flat = Z.reshape(L, -1)
    if backend == "xla-ref":
        out = W.astype(jnp.float32) @ flat.astype(jnp.float32)
        return out.astype(Z.dtype).reshape(Z.shape)
    M = flat.shape[1]
    blk = min(blk_c, M)
    pad = (-M) % blk
    if pad:
        flat = jnp.pad(flat, ((0, 0), (0, pad)))
    out = _ga.mix_rows(W, flat, blk_c=blk, interpret=_interp(backend))
    return out[:, :M].reshape(Z.shape)
