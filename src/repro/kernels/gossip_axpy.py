"""Fused gossip combine kernel: z ← w₀·z + Σ_k w_{k+1}·nbr_k.

After the collective-permutes of one diffusion round, each device holds
its own block plus K neighbour blocks; this VPU kernel fuses the weighted
K+1-way combine into a single pass over VMEM tiles (instead of K separate
axpy sweeps through HBM).  The weights arrive as a (K+1, 1) operand —
per-shift values rather than a uniform scalar pair — so arbitrary
weighted topologies (Metropolis rows, irregular graphs) lower to the
same ONE dispatch per round as the uniform ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(w_ref, z_ref, nbr_ref, o_ref, *, K: int):
    acc = w_ref[0] * z_ref[...].astype(jnp.float32)          # (blk, C)
    for k in range(K):                                       # static unroll
        acc = acc + w_ref[k + 1] * nbr_ref[k].astype(jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _mix_kernel(w_ref, z_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)               # (L, L)
    z = z_ref[...].astype(jnp.float32)               # (L, blk_c)
    o_ref[...] = jax.lax.dot_general(
        w, z, (((1,), (0,)), ((), ()))).astype(o_ref.dtype)


def mix_rows(W, Z, *, blk_c: int, interpret: bool):
    """Fused consensus combine Z ← W Z for a precomputed mixing matrix
    (typically W^{T_con} from ``agree_power`` — the whole AGREE phase in
    ONE weighted combine instead of T_con HBM sweeps).  The node count L
    is small (≤ ~100), so W stays resident while Z streams in column
    tiles.  W: (L, L); Z: (L, M), M a multiple of blk_c (ops.py pads).
    Output dtype follows Z (accumulation is f32 in-kernel)."""
    L, M = Z.shape
    blk_c = min(blk_c, M)
    if M % blk_c:
        raise ValueError(f"mix_rows needs M divisible by blk_c: "
                         f"M={M}, blk_c={blk_c} (ops.mix_nodes pads)")
    return pl.pallas_call(
        _mix_kernel,
        grid=(M // blk_c,),
        in_specs=[
            pl.BlockSpec((L, L), lambda i: (0, 0)),
            pl.BlockSpec((L, blk_c), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((L, blk_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((L, M), Z.dtype),
        interpret=interpret,
    )(W, Z)


def gossip_combine(z, neighbors, weights, *, blk_rows: int,
                   interpret: bool):
    """z: (M, C); neighbors: (K, M, C); weights: (K+1,) → (M, C).

    Row counts not divisible by ``blk_rows`` are zero-padded and trimmed
    (the combine is row-wise, so padded rows never touch real ones)."""
    M, C = z.shape
    K = neighbors.shape[0]
    blk_rows = min(blk_rows, M)
    pad = (-M) % blk_rows
    if pad:
        z = jnp.pad(z, ((0, pad), (0, 0)))
        neighbors = jnp.pad(neighbors, ((0, 0), (0, pad), (0, 0)))
    Mp = M + pad
    w = jnp.asarray(weights, jnp.float32).reshape(K + 1)
    out = pl.pallas_call(
        functools.partial(_combine_kernel, K=K),
        grid=(Mp // blk_rows,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((blk_rows, C), lambda i: (i, 0)),
            pl.BlockSpec((K, blk_rows, C), lambda i: (0, i, 0)),
        ],
        out_specs=pl.BlockSpec((blk_rows, C), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Mp, C), z.dtype),
        interpret=interpret,
    )(w, z, neighbors)
    return out[:M] if pad else out
