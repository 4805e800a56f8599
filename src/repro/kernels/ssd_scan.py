"""Mamba2 SSD chunked-scan Pallas kernel (TPU target, validated
interpret=True).

Per (batch, head) grid cell the chunk dimension is innermost and
sequential; the (P, N) inter-chunk state lives in VMEM scratch and is
carried across chunk iterations — the HBM traffic is exactly one read of
(x, dt, B, C) and one write of y per token.  Within a chunk the
recurrence is unrolled into the masked quadratic form (state-space
duality): two (Q×Q)·(Q×P/N) MXU matmuls instead of Q sequential steps.

Layouts: x (B, H, nc·Q, P); dt (B, H, nc·Q); Bm/Cm (B, nc·Q, N);
out y (B, H, nc·Q, P) (+ optional final state via a second out).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, y_ref, h_ref,
                h_scr, *, chunk: int):
    h_idx = pl.program_id(1)
    c_idx = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt = dt_ref[0, 0].astype(jnp.float32)        # (Q,)
    a = A_ref[h_idx]                             # scalar A_h < 0
    Bm = B_ref[0].astype(jnp.float32)            # (Q, N)
    Cm = C_ref[0].astype(jnp.float32)            # (Q, N)

    dA = dt * a                                  # (Q,)
    cum = jnp.cumsum(dA)                         # (Q,)
    seg = cum[-1]

    # intra-chunk: masked quadratic form on the MXU
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(li >= lj, jnp.exp(cum[:, None] - cum[None, :]), 0.0)
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())))  # (Q,Q)
    M = scores * L * dt[None, :]
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())))        # (Q,P)

    # inter-chunk: contribution of the carried state
    h = h_scr[...]                               # (P, N)
    y = y + jnp.exp(cum)[:, None] * jax.lax.dot_general(
        Cm, h, (((1,), (1,)), ((), ())))         # (Q,N)·(P,N)ᵀ → (Q,P)

    # D skip connection
    y = y + x * D_ref[h_idx]
    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: h ← e^seg·h + Σ_q decay_q dt_q x_q B_qᵀ
    decay = jnp.exp(seg - cum) * dt              # (Q,)
    S_c = jax.lax.dot_general(x * decay[:, None], Bm,
                              (((0,), (0,)), ((), ())))             # (P,N)
    h_scr[...] = jnp.exp(seg) * h + S_c

    @pl.when(c_idx == nc - 1)
    def _emit_state():
        h_ref[0, 0] = h_scr[...]


def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk: int = 128,
             interpret: bool):
    """x: (B,H,S,P); dt: (B,H,S); A: (H,); Bm/Cm: (B,S,N); D: (H,) →
    (y (B,H,S,P), h_final (B,H,P,N)).  S must be a multiple of ``chunk``
    (ops.py pads)."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    if S % chunk:
        raise ValueError(f"S={S} must be a multiple of chunk={chunk} "
                         f"(ops.py pads)")
    nc = S // chunk
    grid = (B, H, nc)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, h, c: (b, h, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # A: (H,) scalars
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),     # D: (H,) scalars
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt, A.astype(jnp.float32), Bm, Cm, D.astype(jnp.float32))
    return y, h
