"""Wire-compression kernels for the compressed consensus rules.

The compressed combine rules (``repro.distributed.consensus``:
``topk_gossip`` / ``quantized_gossip``) shrink what one gossip round
puts on the wire; these kernels implement the per-node encode/decode hot
paths on the pallas backends:

  * :func:`compress_topk` — rank-preserving top-k ROW sparsification of
    a node-batched ``(N, d, r)`` iterate block: the k rows with the
    largest squared row norms are selected per block (keeping whole rows
    keeps the payload a valid rank-≤r factor slice, unlike entrywise
    masking).  Selection is an iterative masked argmax (k small, ≤ d)
    so no sort network is needed; norms accumulate in f32.
  * :func:`dequant` — int8 wire payload → ``scale.dtype`` blocks
    (``q · scale`` with f32 accumulation), the decode half of the
    quantized wire format.

Both are dispatched through ``ops.py`` (``compress_topk`` / ``dequant``)
with ``ref.py`` oracles; float64 operands never reach them — the
consensus layer's shared ``_fused_wanted`` gate routes x64 runs to the
exact reference path, the same policy the combine kernels follow.

The top-k selection loop loads and stores rows at dynamic offsets; Mosaic
compiles it for the TPU (``tests/test_tpu_compile.py``), so the selection
stays in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _topk_kernel(m_ref, vals_ref, idx_ref, *, k: int, d: int):
    m = m_ref[0].astype(jnp.float32)                    # (d, r)
    s = jnp.sum(m * m, axis=1, keepdims=True)           # (d, 1) row norms
    rows = jax.lax.broadcasted_iota(jnp.int32, (d, 1), 0)

    def select(j, s):
        top = jnp.max(s)
        # first max (stable); a NaN norm counts as the max, as in
        # lax.top_k, so a diverged row is selected, never row d
        hit = (s == top) | jnp.isnan(s)
        i0 = jnp.min(jnp.where(hit, rows, d))
        vals_ref[0, pl.ds(j, 1), :] = m_ref[0, pl.ds(i0, 1), :]
        idx_ref[0, pl.ds(j, 1), :] = jnp.full((1, 1), i0, jnp.int32)
        return jnp.where(rows == i0, -jnp.inf, s)

    jax.lax.fori_loop(0, k, select, s)


def compress_topk(M, k: int, *, interpret: bool):
    """Top-k row sparsification.  M: (N, d, r) → (vals (N, k, r) in
    M.dtype, descending row-norm order; idx (N, k) int32).  One grid
    cell per node block; d×r is small (the subspace iterate), so the
    whole block sits in VMEM.  Ties between equal row norms resolve to
    the lowest index (matching ``lax.top_k``'s stable order).  The
    kernel writes idx as (N, k, 1) so its block's last two dims are the
    array's own."""
    N, d, r = M.shape
    if not 1 <= k <= d:
        raise ValueError(f"compress_topk needs 1 <= k <= d, got k={k}, "
                         f"d={d}")
    vals, idx = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, d=d),
        grid=(N,),
        in_specs=[pl.BlockSpec((1, d, r), lambda i: (i, 0, 0))],
        out_specs=(pl.BlockSpec((1, k, r), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, k, 1), lambda i: (i, 0, 0))),
        out_shape=(jax.ShapeDtypeStruct((N, k, r), M.dtype),
                   jax.ShapeDtypeStruct((N, k, 1), jnp.int32)),
        interpret=interpret,
    )(M)
    return vals, idx.reshape(N, k)


def _dequant_kernel(scale_ref, q_ref, o_ref):
    s = scale_ref[0, 0, 0].astype(jnp.float32)
    o_ref[...] = (q_ref[...].astype(jnp.float32) * s).astype(o_ref.dtype)


def dequant(q, scale, *, interpret: bool):
    """Decode an int8 wire payload: ``q · scale`` per node block with f32
    accumulation.  q: (N, d, r) int8; scale: (N, 1, 1) → (N, d, r) in
    scale.dtype."""
    N, d, r = q.shape
    if scale.shape != (N, 1, 1):
        raise ValueError(f"dequant needs a per-node (N, 1, 1) scale, got "
                         f"{scale.shape} for q {q.shape}")
    return pl.pallas_call(
        _dequant_kernel,
        grid=(N,),
        in_specs=[pl.BlockSpec((1, 1, 1), lambda i: (i, 0, 0)),
                  pl.BlockSpec((1, d, r), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, d, r), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d, r), scale.dtype),
        interpret=interpret,
    )(scale, q)
