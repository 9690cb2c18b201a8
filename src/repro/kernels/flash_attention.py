"""Blocked flash attention for TPU (Pallas).

FlashAttention-2-style online softmax with explicit BlockSpec VMEM tiling:

  grid = (batch, q_heads, Sq/block_q, Sk/block_k)   last dim "arbitrary"

Q/O blocks are (block_q, head_dim), K/V blocks (block_k, head_dim); the
running max / denominator / accumulator live in VMEM scratch and persist
across the sequential KV-block dimension.  GQA is folded into the K/V index
maps (q head h reads kv head h // group).  Causal + local-window masking is
applied in-kernel; fully-masked KV blocks are skipped with pl.when so the
causal kernel does ~half the work of the full grid.

MXU alignment: block_q/block_k default to 128; head_dim should be a
multiple of 128 for peak MXU utilization (pad if smaller).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref, o_ref,  # blocks
    m_ref, l_ref, acc_ref,       # VMEM scratch
    *, sm_scale: float, causal: bool, window: Optional[int],
    block_q: int, block_k: int, seq_k: int, q_offset: int,
):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # absolute positions of this (q block, k block)
    q_lo = iq * block_q + q_offset
    k_lo = ik * block_k

    # skip KV blocks that are entirely masked out
    live = k_lo < seq_k
    if causal:
        live &= k_lo <= q_lo + block_q - 1
    if window is not None:
        live &= k_lo + block_k - 1 > q_lo - window

    @pl.when(live)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)          # [bk, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                  # [bq, bk]
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        logits = jnp.where(mask, logits, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(logits - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # [B, Hq, Sq, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,  # [B, Hkv, Sk, D]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    sm_scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, hq, sq, d = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    scale = float(sm_scale) if sm_scale is not None else float(1.0 / np.sqrt(d))

    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    nq = q.shape[2] // block_q
    nk = k.shape[2] // block_k

    grid = (b, hq, nq, nk)
    out = pl.pallas_call(
        functools.partial(
            _kernel,
            sm_scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, seq_k=sk, q_offset=q_offset,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, q.shape[2], d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :sq]
