"""Flash attention Pallas kernel (TPU target, validated interpret=True).

Online-softmax block streaming: Q tiles stay resident in VMEM while KV
tiles stream from HBM; running (m, l, o) accumulators live in VMEM
scratch.  Causal + sliding-window masking and GQA head grouping are
handled inside the kernel, so the S² score matrix never exists.

Grid: (B, H, Sq/blk_q, Skv/blk_k) — the KV-block dimension is innermost
and sequential ("arbitrary"), the rest parallel.  MXU alignment: blk_q and
blk_k default to 128, head_dim padded to a lane multiple by the wrapper
(ops.py).

Layouts: q (B, H, Sq, D); k, v (B, Hkv, Skv, D); out (B, H, Sq, D).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  blk_q: int, blk_k: int, causal: bool, window, scale: float,
                  offset: int):
    """``offset`` aligns query and key coordinates: query block-row i sits
    at absolute position i·blk_q + offset (aligned ends ⇒ offset =
    Skv_real − Sq_real; right-padded keys fall above the causal diagonal
    and are masked for free)."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * blk_q + offset
    k_start = ki * blk_k

    # skip fully-masked KV blocks (strictly above the causal diagonal)
    run = True
    if causal:
        run = k_start <= q_start + blk_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (blk_q, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (blk_k, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (blk_q, blk_k), 1)
        mask = jnp.ones((blk_q, blk_k), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_cur = jnp.max(s, axis=1)[:, None]               # (blk_q, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)[:, None]
        v = v_ref[0, 0].astype(jnp.float32)               # (blk_k, D)
        acc_scr[...] = (acc_scr[...] * alpha
                        + jax.lax.dot_general(p, v,
                                              (((1,), (0,)), ((), ()))))
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True, window=None,
                    scale=None, blk_q: int = 128, blk_k: int = 128,
                    offset: int | None = None, interpret: bool):
    """q: (B,H,Sq,D); k,v: (B,Hkv,Skv,D) → (B,H,Sq,D).

    Sq and Skv must be multiples of the block sizes (ops.py pads).
    ``offset`` defaults to Skv − Sq (aligned ends)."""
    B, H, Sq, D = q.shape
    Hkv, Skv = k.shape[1], k.shape[2]
    group = H // Hkv
    blk_q = min(blk_q, Sq)
    blk_k = min(blk_k, Skv)
    if Sq % blk_q or Skv % blk_k:
        raise ValueError(f"Sq={Sq}/Skv={Skv} must be multiples of blk_q={blk_q}/"
                         f"blk_k={blk_k} (ops.py pads)")
    scale = scale if scale is not None else D ** -0.5
    offset = Skv - Sq if offset is None else offset
    grid = (B, H, Sq // blk_q, Skv // blk_k)

    kernel = functools.partial(
        _flash_kernel, blk_q=blk_q, blk_k=blk_k, causal=causal,
        window=window, scale=scale, offset=offset)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, blk_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, 1), jnp.float32),     # running max m
            pltpu.VMEM((blk_q, 1), jnp.float32),     # running sum l
            pltpu.VMEM((blk_q, D), jnp.float32),     # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)
