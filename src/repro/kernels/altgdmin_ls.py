"""AltGDmin least-squares Pallas kernels — the paper's own compute hot
loop (Algorithm 3 lines 8 & 11), adapted for the MXU.

Per outer iteration every node evaluates, for each local task t:
    A_t = X_t U          (n×r tall-skinny),
    G_t = A_tᵀA_t,  c_t = A_tᵀ y_t      (the normal equations),
and, for the gradient, X_tᵀ(A_t b_t − y_t) b_tᵀ.  The d dimension (600 in
the paper's experiments, arbitrary in production) is the long streamed
axis: X_t tiles of (n, blk_d) and U tiles of (blk_d, r) stream through
VMEM while the (n, r) A-tile accumulates in scratch.

Public layout (node-batched): X (L, tpn, n, d), per-node U (L, d, r),
y (L, tpn, n).  All L·tpn task systems ride one grid axis, so a whole
outer iteration — Gram, r×r solve, residual and gradient tiles — is ONE
``pallas_call`` (``node_fused_iter``), and the streamed A = X_t U
accumulator is built exactly once per task (the standalone gradient
kernel rebuilds it in its pass 0; the fused kernel reuses the min-step
accumulator, saving one of the three HBM sweeps over X and ~43% of the
model FLOPs at the paper's r=4 shape).

Kernel layout: the wrappers flatten the task axis to N = L·tpn and give
each per-task vector a singleton axis — y (N, 1, n), B / c (N, 1, r) —
so every block's last two dims are either the array's own or (8, 128)
multiples, as the TPU lowering requires.  Grid cell t reads U block
t // tpn.  The reshapes are free (row-major contiguous).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _check_blk(d: int, blk_d: int) -> int:
    blk_d = min(blk_d, d)
    if d % blk_d:
        raise ValueError(f"d={d} must be a multiple of blk_d={blk_d} "
                         f"(ops.py pads)")
    return blk_d


def _x_spec(n, blk_d):
    return pl.BlockSpec((1, n, blk_d), lambda t, *g: (t, 0, g[-1]))


def _u_spec(blk_d, r, tpn):
    return pl.BlockSpec((1, blk_d, r), lambda t, *g: (t // tpn, g[-1], 0))


def _row_spec(m):
    """(1, 1, m) block of a per-task (N, 1, m) vector."""
    return pl.BlockSpec((1, 1, m), lambda t, *g: (t, 0, 0))


def _tile_spec(blk_d, r):
    return pl.BlockSpec((1, blk_d, r), lambda t, *g: (t, g[-1], 0))


def _chol_solve_unrolled(G, c, r: int):
    """Solve G b = c for SPD G: (r, r) via fully-unrolled Cholesky +
    forward/back substitution.  r is a static Python int (tiny: 4–10), so
    the O(r³) unroll is a handful of scalar ops — this is what lets the
    min-B solve live INSIDE the kernel instead of bouncing (G, c) to HBM
    and re-dispatching for the gradient.  ``ops`` solves the Gram kernel's
    systems with it too, outside any kernel."""
    Lc = [[None] * r for _ in range(r)]
    for i in range(r):
        for j in range(i + 1):
            s = G[i, j] - sum((Lc[i][k] * Lc[j][k] for k in range(j)),
                              jnp.float32(0))
            Lc[i][j] = jnp.sqrt(s) if i == j else s / Lc[j][j]
    z = [None] * r
    for i in range(r):
        z[i] = (c[i] - sum((Lc[i][k] * z[k] for k in range(i)),
                           jnp.float32(0))) / Lc[i][i]
    b = [None] * r
    for i in reversed(range(r)):
        b[i] = (z[i] - sum((Lc[k][i] * b[k] for k in range(i + 1, r)),
                           jnp.float32(0))) / Lc[i][i]
    return jnp.stack(b)


def _accum_a(x_ref, u_ref, a_scr):
    x = x_ref[0].astype(jnp.float32)                 # (n, blk_d)
    u = u_ref[0].astype(jnp.float32)                 # (blk_d, r)
    a_scr[...] += jax.lax.dot_general(x, u, (((1,), (0,)), ((), ())))


def _grad_tile(x_ref, r_scr, b_row, g_ref):
    """g_ref ← X_tileᵀ resid bᵀ for one (blk_d, r) tile."""
    x = x_ref[0].astype(jnp.float32)                 # (n, blk_d)
    xtres = jax.lax.dot_general(x, r_scr[...],
                                (((0,), (0,)), ((), ())))      # (blk_d, 1)
    g_ref[0] = jax.lax.dot_general(xtres, b_row,
                                   (((1,), (0,)), ((), ())))


def _fused_iter_kernel(x_ref, u_ref, y_ref, b_ref, gt_ref,
                       a_scr, b_scr, r_scr, *, r: int):
    """Grid (L·tpn, 2, d//blk_d).  Pass 0 streams X/U d-tiles and
    accumulates A = X_t U (the ONLY A build); at the last d-tile it forms
    the normal equations in-register, solves them (unrolled Cholesky),
    emits b_t and caches the residual A b − y.  Pass 1 re-streams X d-tiles
    once to emit the disjoint gradient tiles X_tileᵀ resid b_tᵀ."""
    pi, di = pl.program_id(1), pl.program_id(2)
    nd = pl.num_programs(2)

    @pl.when((pi == 0) & (di == 0))
    def _init():
        a_scr[...] = jnp.zeros_like(a_scr)

    @pl.when(pi == 0)
    def _pass0():
        _accum_a(x_ref, u_ref, a_scr)

    @pl.when((pi == 0) & (di == nd - 1))
    def _solve():
        a = a_scr[...]                               # (n, r)
        y = y_ref[0, 0].astype(jnp.float32)          # (n,)
        G = jax.lax.dot_general(a, a, (((0,), (0,)), ((), ())))
        c = jax.lax.dot_general(y[None, :], a, (((1,), (0,)), ((), ())))[0]
        b = _chol_solve_unrolled(G, c, r)            # (r,)
        b_ref[0, 0] = b
        b_scr[...] = b[None, :]
        r_scr[...] = (jax.lax.dot_general(
            a, b[:, None], (((1,), (0,)), ((), ())))[:, 0] - y)[:, None]

    @pl.when(pi == 1)
    def _pass1():
        _grad_tile(x_ref, r_scr, b_scr[...], gt_ref)


def node_fused_iter(X, U, y, *, blk_d: int, interpret: bool):
    """One fused AltGDmin iteration for all nodes/tasks in one dispatch.

    X: (L, tpn, n, d); U: (L, d, r); y: (L, tpn, n) →
      B     (L, tpn, r)     — min-B solutions b_t = (X_t U_g)† y_t,
      tiles (L, tpn, d, r)  — per-task gradient contributions
                              X_tᵀ(X_t U_g b_t − y_t) b_tᵀ
    (sum tiles over tpn in ops.py for ∇f_g).  d must be a multiple of
    blk_d (ops.py pads)."""
    L, tpn, n, d = X.shape
    r = U.shape[2]
    N = L * tpn
    blk_d = _check_blk(d, blk_d)
    B, tiles = pl.pallas_call(
        functools.partial(_fused_iter_kernel, r=r),
        grid=(N, 2, d // blk_d),
        in_specs=[_x_spec(n, blk_d), _u_spec(blk_d, r, tpn), _row_spec(n)],
        out_specs=[_row_spec(r), _tile_spec(blk_d, r)],
        out_shape=[
            jax.ShapeDtypeStruct((N, 1, r), jnp.float32),
            jax.ShapeDtypeStruct((N, d, r), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((n, r), jnp.float32),      # A accumulator
            pltpu.VMEM((1, r), jnp.float32),      # b_t
            pltpu.VMEM((n, 1), jnp.float32),      # residual
        ],
        interpret=interpret,
    )(X.reshape(N, n, d), U, y.reshape(N, 1, n))
    return B.reshape(L, tpn, r), tiles.reshape(L, tpn, d, r)


def _gram_kernel(x_ref, u_ref, y_ref, g_ref, c_ref, a_scr):
    di = pl.program_id(1)
    nd = pl.num_programs(1)

    @pl.when(di == 0)
    def _init():
        a_scr[...] = jnp.zeros_like(a_scr)

    _accum_a(x_ref, u_ref, a_scr)

    @pl.when(di == nd - 1)
    def _finalize():
        a = a_scr[...]                               # (n, r)
        y = y_ref[0, 0].astype(jnp.float32)          # (n,)
        g_ref[0] = jax.lax.dot_general(a, a, (((0,), (0,)), ((), ())))
        c_ref[0, 0] = jax.lax.dot_general(y[None, :], a,
                                          (((1,), (0,)), ((), ())))[0]


def node_task_gram(X, U, y, *, blk_d: int, interpret: bool):
    """Node-batched Gram systems (min-B half only — the sample-split path
    where min and gradient use different folds, and the serving solve).
    X: (L, tpn, n, d); U: (L, d, r); y: (L, tpn, n) →
    (G (L, tpn, r, r), c (L, tpn, r))."""
    L, tpn, n, d = X.shape
    r = U.shape[2]
    N = L * tpn
    blk_d = _check_blk(d, blk_d)
    G, c = pl.pallas_call(
        _gram_kernel,
        grid=(N, d // blk_d),
        in_specs=[_x_spec(n, blk_d), _u_spec(blk_d, r, tpn), _row_spec(n)],
        out_specs=[pl.BlockSpec((1, r, r), lambda t, i: (t, 0, 0)),
                   _row_spec(r)],
        out_shape=[
            jax.ShapeDtypeStruct((N, r, r), jnp.float32),
            jax.ShapeDtypeStruct((N, 1, r), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n, r), jnp.float32)],
        interpret=interpret,
    )(X.reshape(N, n, d), U, y.reshape(N, 1, n))
    return G.reshape(L, tpn, r, r), c.reshape(L, tpn, r)


def _grad_kernel(x_ref, u_ref, b_ref, y_ref, g_ref, a_scr, r_scr):
    """Two passes over d per task (grid dims: task, pass, d-tile):
    pass 0 accumulates A = X U; pass 1 computes resid = A b − y once, then
    writes the disjoint (blk_d, r) gradient tiles X_tileᵀ resid bᵀ."""
    pi, di = pl.program_id(1), pl.program_id(2)

    @pl.when((pi == 0) & (di == 0))
    def _init():
        a_scr[...] = jnp.zeros_like(a_scr)

    @pl.when(pi == 0)
    def _pass0():
        _accum_a(x_ref, u_ref, a_scr)

    @pl.when((pi == 1) & (di == 0))
    def _resid():
        b = b_ref[0, 0].astype(jnp.float32)          # (r,)
        y = y_ref[0, 0].astype(jnp.float32)          # (n,)
        r_scr[...] = (jax.lax.dot_general(
            a_scr[...], b[:, None], (((1,), (0,)), ((), ())))[:, 0]
            - y)[:, None]                            # (n, 1)

    @pl.when(pi == 1)
    def _pass1():
        _grad_tile(x_ref, r_scr, b_ref[0].astype(jnp.float32), g_ref)


def node_task_grad_tiles(X, U, B, y, *, blk_d: int, interpret: bool):
    """Node-batched gradient tiles with a given B (sample-split path —
    A must be rebuilt on the gradient fold's data, so this keeps the
    two-pass structure).  X: (L, tpn, n, d); U: (L, d, r); B: (L, tpn, r);
    y: (L, tpn, n) → (L, tpn, d, r)."""
    L, tpn, n, d = X.shape
    r = U.shape[2]
    N = L * tpn
    blk_d = _check_blk(d, blk_d)
    tiles = pl.pallas_call(
        _grad_kernel,
        grid=(N, 2, d // blk_d),
        in_specs=[_x_spec(n, blk_d), _u_spec(blk_d, r, tpn), _row_spec(r),
                  _row_spec(n)],
        out_specs=_tile_spec(blk_d, r),
        out_shape=jax.ShapeDtypeStruct((N, d, r), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((n, r), jnp.float32),      # A accumulator
            pltpu.VMEM((n, 1), jnp.float32),      # residual
        ],
        interpret=interpret,
    )(X.reshape(N, n, d), U, B.reshape(N, 1, r), y.reshape(N, 1, n))
    return tiles.reshape(L, tpn, d, r)
