"""Blade memory on the accelerator.

A blade arena, and each of its mirrors, is one ``uint8`` ``jax.Array`` of
``capacity`` bytes on the default JAX device.  :class:`DeviceArena` is the
only code that touches it, and every byte that moves in or out runs as a
jitted program:

* ``read_runs``: one gather for a whole wave of byte runs (one slice for a
  single run), then one device-to-host transfer;
* ``write_runs``: runs are staged on the host in arrival order and land as
  one scatter with the arena donated, so the update happens in place.  A
  staged write reaches the device before any read, copy, clone or snapshot
  of the arena, and whenever the owner calls ``flush`` (the end of a
  transaction, a crash);
* ``copy_runs``: byte runs copied inside the device from one region of the
  arena to other addresses of it and of its mirrors (group-commit apply:
  log region to data area); the bytes never come back to the host.

Wave sizes are padded to power-of-two buckets: padded gather lanes read
byte 0, padded scatter lanes carry negative indices, which
``FILL_OR_DROP`` discards.  The compiled programs are therefore bounded by
buckets x arena sizes x arenas per copy; ``compiled_programs`` counts them.
Byte indices are ``int32`` (64-bit mode stays off), which bounds an arena
to ``MAX_CAPACITY`` bytes.

With ``repro.obs.profile`` enabled, each call splits its wall-clock time
into ``arena.<read|flush|copy>.prep`` (host index building and padding) and
``.dispatch`` (the jitted call until it returns), and a read adds
``arena.read.wait``: waiting for the program on the device, with the rest
of the copy to the host as its child ``arena.read.copy``.  Counter
``arena.reads`` counts ``read_runs`` calls.  Disabled, a read does not wait
apart from its copy.
"""

from __future__ import annotations

import functools
import os
import pathlib
from typing import Iterable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..obs.profile import count, enabled as _profiling, profile

MAX_CAPACITY = 1 << 31   # int32 byte indices
MIN_BUCKET = 64          # smallest padded wave, in bytes
# largest wave one program moves; bigger ones chunk.  On a v5e a scatter of
# 2^20 lanes or more compiles for ~15 s into ~4 MB of code; 2^19 takes 0.1 s
MAX_BUCKET = 1 << 19

_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]
_BYTE_SCATTER = lax.ScatterDimensionNumbers(
    update_window_dims=(), inserted_window_dims=(0,),
    scatter_dims_to_operand_dims=(0,))


def _scatter(arena: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    # indices are unique once staged runs are de-duplicated (last write
    # wins); the negative padding lanes are unique too and are dropped
    return lax.scatter(arena, idx[:, None], vals, _BYTE_SCATTER,
                       unique_indices=True,
                       mode=lax.GatherScatterMode.FILL_OR_DROP)


@functools.partial(jax.jit, donate_argnums=0)
def scatter_program(arena: jax.Array, idx: jax.Array, vals: jax.Array) -> jax.Array:
    """Land one write wave: ``arena[idx] = vals``, in place."""
    return _scatter(arena, idx, vals)


@jax.jit
def gather_program(arena: jax.Array, idx: jax.Array) -> jax.Array:
    """Read one wave: ``arena[idx]``."""
    return arena.at[idx].get(mode="promise_in_bounds")


@functools.partial(jax.jit, static_argnums=2)
def slice_program(arena: jax.Array, start: jax.Array, size: int) -> jax.Array:
    """Read one run: ``arena[start:start + size]``, no index array shipped."""
    return lax.dynamic_slice(arena, (start,), (size,))


@functools.partial(jax.jit, donate_argnums=0)
def copy_program(arenas: Tuple[jax.Array, ...], src: jax.Array,
                 dst: jax.Array) -> Tuple[jax.Array, ...]:
    """``arenas[k][dst] = arenas[0][src]`` for every k, in place."""
    data = arenas[0].at[src].get(mode="promise_in_bounds")
    return tuple(_scatter(a, dst, data) for a in arenas)


def compiled_programs() -> int:
    """Programs compiled so far for arena traffic, across all arenas."""
    return sum(f._cache_size() for f in
               (scatter_program, gather_program, slice_program, copy_program))


@functools.cache
def _use_checkout_compile_cache() -> None:
    """Keep compiled programs in ``<checkout>/.jax_cache`` unless JAX was
    told where to keep them (``JAX_COMPILATION_CACHE_DIR`` or the config)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") or jax.config.jax_compilation_cache_dir:
        return
    jax.config.update("jax_compilation_cache_dir", str(_CHECKOUT / ".jax_cache"))


def _bucket(n: int) -> int:
    """Padded length of a wave of `n` bytes (a power of two)."""
    return max(MIN_BUCKET, 1 << max(0, n - 1).bit_length())


def _run_indices(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """Byte indices of the runs ``[starts[i], starts[i] + lens[i])``, in order."""
    total = int(lens.sum())
    offs = np.cumsum(lens) - lens
    return (np.repeat(starts - offs, lens) + np.arange(total)).astype(np.int32)


def _last_wins(dst: np.ndarray, starts: np.ndarray, lens: np.ndarray,
               *cols: np.ndarray) -> Tuple[np.ndarray, ...]:
    """Drop every byte a later run overwrites, so the scatter's indices are
    unique and its result equals applying the runs one after another."""
    if len(starts) > 1:
        order = np.argsort(starts, kind="stable")
        s = starts[order]
        if np.any(s[1:] < (s + lens[order])[:-1]):
            rev = dst[::-1]
            dst, first = np.unique(rev, return_index=True)
            cols = tuple(c[::-1][first] for c in cols)
    return (dst,) + cols


def _to_host(got: jax.Array) -> np.ndarray:
    """The result of a read program on the host; while profiling, the wait
    for the program and the rest of the copy are timed apart.  The copy is
    queued before the wait, as ``np.asarray`` alone queues it: started only
    after the wait, it would add a round trip to every read."""
    if not _profiling():
        return np.asarray(got)
    with profile("arena.read.wait"):
        got.copy_to_host_async()
        got.block_until_ready()
        with profile("arena.read.copy"):
            return np.asarray(got)


def _pad(idx: np.ndarray, fill: str) -> np.ndarray:
    pad = _bucket(len(idx)) - len(idx)
    if fill == "drop":
        tail = -1 - np.arange(pad, dtype=np.int32)
    else:
        tail = np.zeros(pad, np.int32)
    return np.concatenate([idx, tail])


class DeviceArena:
    """One blade's bytes in device memory (see the module docstring).

    ``capacity`` is at most ``MAX_CAPACITY`` (2^31) bytes, the reach of an
    ``int32`` byte index.  No host copy of the bytes is kept: reads come off
    the device, and only writes not yet landed wait on the host.
    """

    def __init__(self, capacity: int, array: Optional[jax.Array] = None):
        if not 0 < capacity <= MAX_CAPACITY:
            raise ValueError(f"arena capacity {capacity} outside (0, {MAX_CAPACITY}]")
        _use_checkout_compile_cache()
        self.capacity = capacity
        self._array = jnp.zeros(capacity, jnp.uint8) if array is None else array
        self._staged: List[Tuple[int, bytes]] = []

    def _check(self, addr: int, n: int) -> None:
        if addr < 0 or n < 0 or addr + n > self.capacity:
            raise ValueError(
                f"bytes [{addr}, {addr + n}) outside the {self.capacity}-byte arena")

    def _runs(self, runs: Sequence[Tuple[int, int]]) -> Tuple[np.ndarray, np.ndarray]:
        arr = np.array(runs, dtype=np.int64).reshape(-1, 2)
        starts, lens = arr[:, 0], arr[:, 1]
        if len(arr) and (starts.min() < 0 or lens.min() < 0
                         or (starts + lens).max() > self.capacity):
            raise ValueError(f"run outside the {self.capacity}-byte arena")
        return starts, lens

    # ------------------------------------------------------------- writes
    def write_runs(self, runs: Iterable[Tuple[int, bytes]]) -> None:
        """Stage byte runs; they land, in order, at the next ``flush``."""
        for addr, data in runs:
            self._check(addr, len(data))
            if data:
                self._staged.append((addr, bytes(data)))

    def flush(self) -> None:
        """Land every staged run as one scatter (chunked past MAX_BUCKET)."""
        if not self._staged:
            return
        staged, self._staged = self._staged, []
        with profile("arena.flush.prep"):
            if len(staged) == 1:
                addr, data = staged[0]
                dst = np.arange(addr, addr + len(data), dtype=np.int32)
                vals = np.frombuffer(data, np.uint8)
            else:  # runs were checked when staged
                starts = np.fromiter((a for a, _ in staged), np.int64, len(staged))
                lens = np.fromiter((len(d) for _, d in staged), np.int64, len(staged))
                vals = np.frombuffer(b"".join(d for _, d in staged), np.uint8)
                dst, vals = _last_wins(_run_indices(starts, lens), starts, lens, vals)
        for lo in range(0, len(dst), MAX_BUCKET):
            with profile("arena.flush.prep"):
                d = dst[lo:lo + MAX_BUCKET]
                v = np.zeros(_bucket(len(d)), np.uint8)
                v[:len(d)] = vals[lo:lo + MAX_BUCKET]
                d = _pad(d, "drop")
            with profile("arena.flush.dispatch"):
                self._array = scatter_program(self._array, d, v)

    # -------------------------------------------------------------- reads
    def read_runs(self, runs: Sequence[Tuple[int, int]]) -> List[bytes]:
        """The bytes of each ``(addr, n)`` run: one gather and one transfer
        per wave (chunked past MAX_BUCKET)."""
        self.flush()
        count("arena.reads")
        if len(runs) == 1:
            addr, n = runs[0]
            self._check(addr, n)
            size = _bucket(n)
            if size <= min(self.capacity, MAX_BUCKET):
                lo = min(addr, self.capacity - size)  # dynamic_slice would clamp
                with profile("arena.read.dispatch"):
                    got = slice_program(self._array, np.int32(lo), size)
                return [_to_host(got)[addr - lo:addr - lo + n].tobytes()]
        with profile("arena.read.prep"):
            starts, lens = self._runs(runs)
            idx = _run_indices(starts, lens)
        parts = []
        for lo in range(0, len(idx), MAX_BUCKET):
            with profile("arena.read.prep"):
                chunk = idx[lo:lo + MAX_BUCKET]
                padded = _pad(chunk, "zero")
            with profile("arena.read.dispatch"):
                got = gather_program(self._array, padded)
            parts.append(_to_host(got)[:len(chunk)].tobytes())
        buf = b"".join(parts)
        out = []
        o = 0
        for n in lens.tolist():
            out.append(buf[o:o + n])
            o += n
        return out

    def read(self, addr: int, n: int) -> bytes:
        return self.read_runs([(addr, n)])[0]

    def snapshot(self, lo: int = 0, hi: Optional[int] = None) -> bytes:
        """Bytes ``[lo, hi)`` of the whole arena image (tests and oracles)."""
        self.flush()
        return np.asarray(self._array)[lo:hi].tobytes()

    # ------------------------------------------------------ device copies
    def copy_runs(self, src: np.ndarray, dst: np.ndarray, lens: np.ndarray,
                  into: Sequence["DeviceArena"] = ()) -> None:
        """Copy runs ``[src[i], src[i] + lens[i])`` of this arena to
        ``dst[i]`` in this arena and in every arena of `into`, as if applied
        one after another.  Sources and destinations must not overlap."""
        arenas = [self, *into]
        for a in arenas:
            a.flush()
        with profile("arena.copy.prep"):
            src = np.asarray(src, np.int64)
            dst = np.asarray(dst, np.int64)
            lens = np.asarray(lens, np.int64)
            if not len(lens):
                return
            self._runs(np.stack([src, lens], 1))
            self._runs(np.stack([dst, lens], 1))
            d_idx, s_idx = _last_wins(_run_indices(dst, lens), dst, lens,
                                      _run_indices(src, lens))
        for lo in range(0, len(d_idx), MAX_BUCKET):
            with profile("arena.copy.prep"):
                d = _pad(d_idx[lo:lo + MAX_BUCKET], "drop")
                s = _pad(s_idx[lo:lo + MAX_BUCKET], "zero")
            with profile("arena.copy.dispatch"):
                new = copy_program(tuple(a._array for a in arenas), s, d)
            for a, arr in zip(arenas, new):
                a._array = arr

    def clone(self) -> "DeviceArena":
        """A second arena on the device holding the same bytes."""
        self.flush()
        return DeviceArena(self.capacity, jnp.copy(self._array))
