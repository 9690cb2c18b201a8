"""Fletcher-32 log checksum for TPU (Pallas) — the persistence path's
transaction-integrity primitive (paper §4.2: every remote_tx_write carries a
checksum; recovery validates the tail transaction).

Hardware adaptation: the simulator's Fletcher-64 needs 64-bit modular
arithmetic, which the TPU VPU does not have.  The state-store therefore uses
Fletcher-32 over 16-bit words carried in int32 lanes; per 128-word row the
weighted partial sums stay below 2^31 and are reduced mod 65535, so the
whole computation is exact in int32.

  grid = (n_blocks,)  sequential, carry (s1, s2) in SMEM

Per chunk of L words with incoming (s1, s2):
  s2' = s2 + L*s1 + sum_t (L - t) * w_t      (t 0-indexed)
  s1' = s1 + sum_t w_t
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


MOD = 65535
ROWS, LANES = 8, 128
BLOCK = ROWS * LANES  # words per grid step


def _fold_block(w, s1, s2):
    """Fold one [ROWS, LANES] block of words (int32, < 2^16) into the
    running (s1, s2).  The row loop is unrolled: rows are static slices,
    which the TPU lowering accepts where a dynamic row index is refused."""
    weights = LANES - jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for rr in range(ROWS):
        wrow = w[rr:rr + 1]
        rs1 = jnp.sum(wrow)
        rs2 = jnp.sum(weights * wrow)
        s2 = (s2 + LANES * s1 + rs2) % MOD
        s1 = (s1 + rs1) % MOD
    return s1, s2


def _kernel(w_ref, out_ref, carry_ref):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        carry_ref[0] = 0
        carry_ref[1] = 0

    s1, s2 = _fold_block(w_ref[0], carry_ref[0], carry_ref[1])
    carry_ref[0] = s1
    carry_ref[1] = s2

    @pl.when(step == pl.num_programs(0) - 1)
    def _final():
        out_ref[0] = s1
        out_ref[1] = s2


def fletcher32(words: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Checksum of a vector of 16-bit words (given as int32 < 2^16).

    Returns uint32 ``(s2 << 16) | s1``.  Input is zero-padded to a multiple
    of 1024 words (zero words do not change the Fletcher sums' residues...
    they do advance positions, so padding is part of the checksum contract:
    both writer and verifier pad identically).
    """
    n = words.shape[0]
    pad = (-n) % BLOCK
    w = jnp.pad(words.astype(jnp.int32), (0, pad))
    nb = w.shape[0] // BLOCK
    w = w.reshape(nb, ROWS, LANES)
    out = pl.pallas_call(
        _kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec((1, ROWS, LANES), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((2,), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(w)
    s1 = out[0].astype(jnp.uint32)
    s2 = out[1].astype(jnp.uint32)
    return (s2 << 16) | s1


def _wave_kernel(meta_ref, w_ref, out_ref, carry_ref):
    """Segmented Fletcher-32: one grid walks a whole wave of log streams.

    ``meta[b, 0] == 1`` marks block ``b`` as the first block of a segment
    (carry resets); ``meta[b, 1] >= 0`` marks the last block, holding the
    segment's output row.  Between marks the (s1, s2) carry threads through
    SMEM exactly as in the single-stream kernel.
    """
    step = pl.program_id(0)

    @pl.when(meta_ref[step, 0] == 1)
    def _init():
        carry_ref[0] = 0
        carry_ref[1] = 0

    s1, s2 = _fold_block(w_ref[0], carry_ref[0], carry_ref[1])
    carry_ref[0] = s1
    carry_ref[1] = s2

    @pl.when(meta_ref[step, 1] >= 0)
    def _emit():
        seg = meta_ref[step, 1]
        out_ref[seg, 0] = s1
        out_ref[seg, 1] = s2


def fletcher32_wave(chunks, *, interpret: bool = False) -> "np.ndarray":
    """Checksum a wave of byte strings with ONE ``pallas_call``.

    Each chunk keeps the per-stream padding contract of :func:`fletcher32`
    (16-bit words, zero-padded to whole 1024-word blocks), so every output
    equals a standalone ``fletcher32`` of that chunk; the padded streams are
    concatenated and the kernel resets/emits its SMEM carry at the segment
    boundaries.  This is the TPU-side analogue of the simulator's batched
    ``oplog.fletcher64_segments`` decode path — validate a whole wave of
    transactions per launch instead of one kernel per log entry.  Runs under
    Pallas interpret mode on CPU; returns a uint32 array, one checksum per
    chunk.
    """
    if not chunks:
        return np.empty(0, dtype=np.uint32)
    streams = []
    blocks = []
    for c in chunks:
        if len(c) % 2:
            c = c + b"\x00"
        w = np.frombuffer(c, dtype="<u2").astype(np.int32)
        nb = max(1, -(-len(w) // BLOCK))
        wp = np.zeros(nb * BLOCK, np.int32)
        wp[: len(w)] = w
        streams.append(wp)
        blocks.append(nb)
    w = np.concatenate(streams).reshape(-1, ROWS, LANES)
    meta = np.full((w.shape[0], 2), -1, dtype=np.int32)
    b0 = 0
    for seg, nb in enumerate(blocks):
        meta[b0, 0] = 1
        meta[b0 + nb - 1, 1] = seg
        b0 += nb
    out = fletcher32_wave_call(meta, w, len(chunks), interpret=interpret)
    out = np.asarray(out).astype(np.uint32)
    return (out[:, 1] << 16) | out[:, 0]


def fletcher32_wave_call(meta, w, n_chunks: int, *, interpret: bool = False):
    """The wave kernel on prepared inputs: ``meta`` [blocks, 2] int32
    segment marks and ``w`` [blocks, ROWS, LANES] int32 words.  Returns
    [n_chunks, 2] int32 rows of (s1, s2)."""
    return pl.pallas_call(
        _wave_kernel,
        grid=(w.shape[0],),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, ROWS, LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((n_chunks, 2), jnp.int32),
        scratch_shapes=[pltpu.SMEM((2,), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(meta, w)


def fletcher32_padded_np(data: bytes) -> int:
    """Exact numpy mirror of the kernel contract (pad to 1024 words)."""
    pad = (-len(data)) % 2
    if pad:
        data = data + b"\x00"
    w = np.frombuffer(data, dtype="<u2").astype(np.int64)
    wpad = (-len(w)) % BLOCK
    w = np.concatenate([w, np.zeros(wpad, np.int64)])
    s1 = np.int64(0)
    s2 = np.int64(0)
    for i in range(0, len(w), LANES):
        row = w[i : i + LANES]
        s2 = (s2 + LANES * s1 + int(((LANES - np.arange(LANES)) * row).sum())) % MOD
        s1 = (s1 + int(row.sum())) % MOD
    return int((s2 << 16) | s1)
