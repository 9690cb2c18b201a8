"""Per-block magnitude top-k compression for TPU (Pallas).

Compresses delta logs / gradients for the asymmetric state store: each
1024-element block keeps its k largest-|x| entries (values + indices) and
emits the residual (for error feedback).  TPU-native selection: k iterations
of argmax+clear on a VMEM-resident block — no sort network, no gather.

  grid = (n_blocks,)  fully parallel
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, vals_ref, idx_ref, res_ref, *, k: int, block: int):
    x = x_ref[...].astype(jnp.float32)  # [1, block] — kept 2D for the VPU
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, block), 1)

    def step(j, carry):
        xw, ax = carry  # ax: working magnitudes, -1 marks already-selected
        m = jnp.max(ax)
        is_max = ax == m
        p = jnp.min(jnp.where(is_max, pos, block))  # first index at the max
        sel = pos == p
        v = jnp.sum(jnp.where(sel, xw, 0.0))
        vals_ref[0, j] = v
        idx_ref[0, j] = p
        return jnp.where(sel, 0.0, xw), jnp.where(sel, -1.0, ax)

    xw, _ = jax.lax.fori_loop(0, k, step, (x, jnp.abs(x)))
    res_ref[...] = xw.astype(res_ref.dtype)


def topk_compress(
    x: jax.Array, k: int, *, block: int = 1024, interpret: bool = False
):
    """Returns (vals [nb,k] f32, idx [nb,k] i32, residual [n] like x)."""
    n = x.shape[0]
    pad = (-n) % block
    xp = jnp.pad(x, (0, pad))
    nb = xp.shape[0] // block
    xb = xp.reshape(nb, block)
    vals, idx, res = pl.pallas_call(
        functools.partial(_kernel, k=k, block=block),
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((1, k), lambda i: (i, 0)),
            pl.BlockSpec((1, k), lambda i: (i, 0)),
            pl.BlockSpec((1, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, k), jnp.float32),
            jax.ShapeDtypeStruct((nb, k), jnp.int32),
            jax.ShapeDtypeStruct((nb, block), x.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xb)
    return vals, idx, res.reshape(-1)[:n]
