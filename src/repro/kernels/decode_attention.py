"""Single-token decode attention (Pallas): one query against a long KV cache.

Memory-bound by design (arithmetic intensity ~= 1 FLOP/byte): the kernel
streams KV blocks HBM -> VMEM along the sequential grid dimension, keeping
the online-softmax carry (m, l, acc) in VMEM scratch.  Per-sequence valid
lengths live in SMEM so padded cache tails are masked without traffic.

  grid = (batch, q_heads, S/block_k)    last dim "arbitrary"
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
    len_ref,            # SMEM: [1] valid KV length for this sequence
    q_ref, k_ref, v_ref, o_ref,
    m_ref, l_ref, acc_ref,
    *, sm_scale: float, block_k: int,
):
    ik = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[0]
    k_lo = ik * block_k

    @pl.when(k_lo < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)               # [1, d]
        k = k_ref[0, 0].astype(jnp.float32)               # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)               # [bk, d]
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                       # [1, bk]
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        logits = jnp.where(kpos < length, logits, NEG_INF)
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


def decode_attention(
    q: jax.Array,       # [B, Hq, D]
    k: jax.Array,       # [B, Hkv, S, D]
    v: jax.Array,       # [B, Hkv, S, D]
    *,
    length: Optional[jax.Array] = None,  # [B] int32 valid lengths
    sm_scale: Optional[float] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    scale = float(sm_scale) if sm_scale is not None else float(1.0 / np.sqrt(d))
    if length is None:
        length = jnp.full((b,), s, jnp.int32)
    block_k = min(block_k, s)
    pad = (-s) % block_k
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nk = k.shape[2] // block_k
    q4 = q[:, :, None, :]  # [B, Hq, 1, D]

    out = pl.pallas_call(
        functools.partial(_kernel, sm_scale=scale, block_k=block_k),
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec((1,), lambda ib, ih, ik: (ib,), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, d), lambda ib, ih, ik: (ib, ih, 0, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, ik: (ib, ih // group, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, ik: (ib, ih // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, d), lambda ib, ih, ik: (ib, ih, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(length.astype(jnp.int32), q4, k, v)
    return out[:, :, 0, :]
