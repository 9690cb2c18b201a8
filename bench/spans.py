"""Wall-clock spans and counters that the benchmark takes around the store.

The program has no wall-clock spans of its own yet, so the benchmark wraps
the two boundaries it can see from outside:

* the store calls the harness makes (``get_many``, ``put_many``,
  ``range_scan``), through ``Spans.store``;
* the methods of ``repro.core.devmem.DeviceArena``, the only code that
  moves bytes in or out of device memory.  ``Spans.install`` replaces them
  on the class with wrappers that count calls and the bytes their runs ask
  to move, and time the outermost call (``read_runs`` lands staged writes
  through ``flush``; that nested time is counted once).

Each span is also a ``jax.profiler.TraceAnnotation`` named ``bench.<name>``,
so the profiler's trace shows what the host was doing while the device sat
idle.  The wrappers are installed only for a ``--trace 1`` run; a
``--trace 0`` run measures the store as it is.
"""

from __future__ import annotations

import contextlib
import time
from collections import Counter
from typing import Callable, Dict

import jax
import numpy as np

ARENA_METHODS = ("read_runs", "write_runs", "flush", "copy_runs", "clone")


def _run_bytes(method: str, arena, args, kwargs) -> int:
    """Bytes a DeviceArena call asks to move: runs read, runs staged for
    writing, and for a device copy the bytes read once and written to the
    arena and each mirror."""
    if method == "read_runs":
        return sum(int(n) for _, n in args[0])
    if method == "write_runs":
        return sum(len(d) for _, d in args[0])
    if method == "copy_runs":
        lens = args[2] if len(args) > 2 else kwargs["lens"]
        into = args[3] if len(args) > 3 else kwargs.get("into", ())
        return int(np.asarray(lens, np.int64).sum()) * (2 + len(into))
    if method == "clone":
        return 2 * arena.capacity
    return 0  # flush lands bytes that write_runs already counted


class Spans:
    def __init__(self) -> None:
        self.store_s = 0.0
        self.arena_s = 0.0
        self.arena_calls: Counter = Counter()
        self.arena_bytes = 0
        self._depth = 0
        self._saved: Dict[str, Callable] = {}

    # --------------------------------------------------------- store calls
    @contextlib.contextmanager
    def store(self, name: str):
        with jax.profiler.TraceAnnotation(f"bench.store.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.store_s += time.perf_counter() - t0

    # ------------------------------------------------------- arena methods
    def install(self) -> None:
        from repro.core.devmem import DeviceArena

        for method in ARENA_METHODS:
            orig = getattr(DeviceArena, method)
            self._saved[method] = orig
            setattr(DeviceArena, method, self._wrap(method, orig))

    def uninstall(self) -> None:
        from repro.core.devmem import DeviceArena

        for method, orig in self._saved.items():
            setattr(DeviceArena, method, orig)
        self._saved.clear()

    def _wrap(self, method: str, orig: Callable) -> Callable:
        label = f"bench.arena.{method}"

        def wrapper(arena, *args, **kwargs):
            self.arena_calls[method] += 1
            self.arena_bytes += _run_bytes(method, arena, args, kwargs)
            if self._depth:
                return orig(arena, *args, **kwargs)
            self._depth += 1
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(label):
                    return orig(arena, *args, **kwargs)
            finally:
                self.arena_s += time.perf_counter() - t0
                self._depth -= 1

        wrapper.__name__ = method
        wrapper.__doc__ = orig.__doc__
        return wrapper

    def snapshot(self) -> Dict[str, float]:
        return {"store_s": self.store_s, "arena_s": self.arena_s,
                "arena_bytes": self.arena_bytes,
                **{f"calls.{m}": self.arena_calls[m] for m in ARENA_METHODS}}
