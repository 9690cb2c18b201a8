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

In a cell whose traffic fails a blade, ``Spans(failover=True)`` also wraps
the mirror promotion's other phases, ``NVMBackend.reboot`` and
``ClusterFrontEnd.recover_blade`` (which holds the clones and the reboot),
as ``bench.failover.<method>`` spans.  Every wrapped method keeps its calls
and the seconds of its outermost calls; a clone's span waits for its device
copy, which would otherwise land in the first read after it.
"""

from __future__ import annotations

import contextlib
import time
from collections import Counter
from typing import Callable, Dict

import jax
import numpy as np

ARENA_METHODS = ("read_runs", "write_runs", "flush", "copy_runs", "clone")
FAILOVER_METHODS = ("reboot", "recover_blade")


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
    def __init__(self, failover: bool = False) -> None:
        self.failover = failover
        self.store_s = 0.0
        self.arena_s = 0.0
        self.calls: Counter = Counter()
        self.seconds: Counter = Counter()
        self.arena_bytes = 0
        self._depth = 0
        self._saved: list = []

    # --------------------------------------------------------- store calls
    @contextlib.contextmanager
    def store(self, name: str):
        with jax.profiler.TraceAnnotation(f"bench.store.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.store_s += time.perf_counter() - t0

    # ------------------------------------------------------ wrapped methods
    def _targets(self):
        from repro.core.devmem import DeviceArena

        targets = [(DeviceArena, m) for m in ARENA_METHODS]
        if self.failover:
            from repro.cluster.router import ClusterFrontEnd
            from repro.core.backend import NVMBackend

            targets += [(NVMBackend, "reboot"), (ClusterFrontEnd, "recover_blade")]
        return targets

    def install(self) -> None:
        for cls, method in self._targets():
            orig = getattr(cls, method)
            self._saved.append((cls, method, orig))
            setattr(cls, method, self._wrap(method, orig))

    def uninstall(self) -> None:
        for cls, method, orig in self._saved:
            setattr(cls, method, orig)
        self._saved.clear()

    def _wrap(self, method: str, orig: Callable) -> Callable:
        arena = method in ARENA_METHODS
        label = f"bench.{'arena' if arena else 'failover'}.{method}"

        def wrapper(obj, *args, **kwargs):
            self.calls[method] += 1
            if arena:
                self.arena_bytes += _run_bytes(method, obj, args, kwargs)
                if self._depth:
                    return orig(obj, *args, **kwargs)
                self._depth += 1
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(label):
                    out = orig(obj, *args, **kwargs)
                    if method == "clone":
                        out._array.block_until_ready()
                    return out
            finally:
                dt = time.perf_counter() - t0
                self.seconds[method] += dt
                if arena:
                    self.arena_s += dt
                    self._depth -= 1

        wrapper.__name__ = method
        wrapper.__doc__ = orig.__doc__
        return wrapper

    def snapshot(self) -> Dict[str, float]:
        methods = ARENA_METHODS + (FAILOVER_METHODS if self.failover else ())
        return {"store_s": self.store_s, "arena_s": self.arena_s,
                "arena_bytes": self.arena_bytes,
                **{f"calls.{m}": self.calls[m] for m in methods},
                **{f"seconds.{m}": self.seconds[m] for m in methods}}
