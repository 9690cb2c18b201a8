"""RG-LRU gated linear recurrence for TPU (Pallas) — RecurrentGemma's mixer.

Same chunked-scan pattern as the Mamba kernel but with a diagonal state
(one scalar per channel), so the carry is just [1, block_d] fp32:

  h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t),
  a_t = exp(c * r_t * log_a)   (log_a learned, negative)

  grid = (batch, D/block_d, S/chunk)   last dim "arbitrary"
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def _kernel(x_ref, r_ref, i_ref, la_ref, h0_ref, y_ref, hT_ref, h_ref, *, c: float):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)      # [chunk, bd]
    r = r_ref[0].astype(jnp.float32)
    gi = i_ref[0].astype(jnp.float32)
    log_a = la_ref[...].astype(jnp.float32)  # [1, bd]

    log_at = c * r * log_a                 # [chunk, bd]
    a = jnp.exp(log_at)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_at), 1e-12)) * (gi * x)
    A_in, B_in = jax.lax.associative_scan(_combine, (a, b), axis=0)
    states = A_in * h_ref[...] + B_in      # [chunk, bd]
    y_ref[0] = states.astype(y_ref.dtype)
    h_ref[...] = states[-1:]

    @pl.when(ic == nc - 1)
    def _final():
        hT_ref[0] = h_ref[...].astype(hT_ref.dtype)


def rglru_scan(
    x: jax.Array,       # [B, S, D]
    r: jax.Array,       # [B, S, D] recurrence gate
    i: jax.Array,       # [B, S, D] input gate
    log_a: jax.Array,   # [D]
    h0: Optional[jax.Array] = None,  # [B, D]
    *,
    c: float = 8.0,
    chunk: int = 256,
    block_d: int = 512,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, Dm = x.shape
    if h0 is None:
        h0 = jnp.zeros((B, Dm), jnp.float32)
    chunk = min(chunk, S)
    block_d = min(block_d, Dm)
    pad_s = (-S) % chunk
    if pad_s:
        zpad = ((0, 0), (0, pad_s), (0, 0))
        x, r, i = (jnp.pad(t, zpad) for t in (x, r, i))
    nc = x.shape[1] // chunk
    nd = Dm // block_d
    la2 = log_a[None, :]
    h02 = h0[:, None, :]  # [B, 1, D]

    y, hT = pl.pallas_call(
        functools.partial(_kernel, c=c),
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, block_d), lambda ib, idd, ic: (0, idd)),
            pl.BlockSpec((1, 1, block_d), lambda ib, idd, ic: (ib, 0, idd)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, 1, block_d), lambda ib, idd, ic: (ib, 0, idd)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, x.shape[1], Dm), x.dtype),
            jax.ShapeDtypeStruct((B, 1, Dm), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, r, i, la2, h02)
    return y[:, :S], hT[:, 0]
