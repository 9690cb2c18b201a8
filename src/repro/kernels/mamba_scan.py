"""Chunked Mamba-1 selective scan for TPU (Pallas).

The GPU reference implementation is a fused sequential scan per thread
block; the TPU-native reformulation is *chunked*: the sequence axis becomes
a sequential grid dimension of chunks, the recurrent state (block_d x
d_state, fp32) persists in VMEM scratch, and *within* a chunk the recurrence
h_t = a_t h_{t-1} + b_t is computed with an associative scan over the chunk
axis — log2(chunk) vectorized steps on the VPU instead of `chunk` dependent
steps.  Channels (d_inner) are tiled over a parallel grid dimension so the
working set (chunk x block_d x d_state fp32) fits VMEM.

  grid = (batch, d_inner/block_d, S/chunk)   last dim "arbitrary"
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


def _kernel(
    x_ref, dt_ref, A_ref, B_ref, C_ref, D_ref, h0_ref,
    y_ref, hT_ref,
    h_ref,  # VMEM scratch: [block_d, N] fp32 carry
    *, chunk: int,
):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)

    x = x_ref[0].astype(jnp.float32)        # [chunk, bd]
    dt = dt_ref[0].astype(jnp.float32)      # [chunk, bd]
    A = A_ref[...].astype(jnp.float32)      # [bd, N]
    Bm = B_ref[0].astype(jnp.float32)       # [chunk, N]
    Cm = C_ref[0].astype(jnp.float32)       # [chunk, N]
    D = D_ref[...].astype(jnp.float32)      # [1, bd]

    a = jnp.exp(dt[:, :, None] * A[None])               # [chunk, bd, N]
    b = (dt * x)[:, :, None] * Bm[:, None, :]           # [chunk, bd, N]
    A_in, B_in = jax.lax.associative_scan(_combine, (a, b), axis=0)
    h0 = h_ref[...]
    states = A_in * h0[None] + B_in                      # [chunk, bd, N]
    y = jnp.einsum("cdn,cn->cd", states, Cm) + x * D     # [chunk, bd]
    y_ref[0] = y.astype(y_ref.dtype)
    h_ref[...] = states[-1]

    @pl.when(ic == nc - 1)
    def _final():
        hT_ref[0] = h_ref[...].astype(hT_ref.dtype)


def mamba_scan(
    x: jax.Array,       # [B, S, Din]
    delta: jax.Array,   # [B, S, Din]  post-softplus
    A: jax.Array,       # [Din, N]
    Bm: jax.Array,      # [B, S, N]
    Cm: jax.Array,      # [B, S, N]
    D: jax.Array,       # [Din]
    h0: Optional[jax.Array] = None,  # [B, Din, N]
    *,
    chunk: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    B, S, Din = x.shape
    N = A.shape[1]
    if h0 is None:
        h0 = jnp.zeros((B, Din, N), jnp.float32)
    chunk = min(chunk, S)
    block_d = min(block_d, Din)
    pad_s = (-S) % chunk
    if pad_s:
        zpad = ((0, 0), (0, pad_s), (0, 0))
        x = jnp.pad(x, zpad)
        delta = jnp.pad(delta, zpad)
        Bm = jnp.pad(Bm, zpad)
        Cm = jnp.pad(Cm, zpad)
    nc = x.shape[1] // chunk
    nd = Din // block_d
    D2 = D[None, :]  # [1, Din]

    y, hT = pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((block_d, N), lambda ib, idd, ic: (idd, 0)),
            pl.BlockSpec((1, chunk, N), lambda ib, idd, ic: (ib, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda ib, idd, ic: (ib, ic, 0)),
            pl.BlockSpec((1, block_d), lambda ib, idd, ic: (0, idd)),
            pl.BlockSpec((1, block_d, N), lambda ib, idd, ic: (ib, idd, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, block_d, N), lambda ib, idd, ic: (ib, idd, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, x.shape[1], Din), x.dtype),
            jax.ShapeDtypeStruct((B, Din, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, delta, A, Bm, Cm, D2, h0)
    return y[:, :S], hT
