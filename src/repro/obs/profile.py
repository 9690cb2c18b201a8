"""Wall-clock spans and counters of the store's own Python and device calls.

``profile(name)`` bounds a region (a store call, a shard visit, a read wave,
a group commit, a device arena call); ``count(name, n)`` bumps a counter
(device reads by cause, commits).  Disabled — the default — ``profile``
returns a shared no-op context manager and ``count`` returns at once, so
the cost at a site is one module-global read.  ``enable()`` and
``disable()`` are the only switch.

Enabled, each span accumulates its inclusive seconds, its self seconds
(inclusive less the time of the spans opened inside it on the same thread)
and its calls, on ``time.perf_counter``.  Each span is also a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``, so in a JAX
profiler trace the spans sit on the host's plane, on the same clock as the
device's operations.  ``snapshot()`` gives ``{"seconds", "self_seconds",
"calls"}`` for a span and ``{"count"}`` for a counter; a name is one or the
other.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List

_enabled = False
_acc: Dict[str, List[float]] = {}   # span -> [seconds, self seconds, calls]
_counts: Dict[str, float] = {}      # counter -> count
_local = threading.local()          # .stack: the thread's open spans
_annotation = None                  # jax.profiler.TraceAnnotation, once enabled


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


class _Timer:
    __slots__ = ("name", "t0", "inner", "ann", "stack")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.ann = _annotation("repro." + self.name)
        self.ann.__enter__()
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self.stack = stack
        self.inner = 0.0
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        stack = self.stack
        stack.pop()
        if stack:
            stack[-1].inner += dt
        cell = _acc.get(self.name)
        if cell is None:
            _acc[self.name] = [dt, dt - self.inner, 1]
        else:
            cell[0] += dt
            cell[1] += dt - self.inner
            cell[2] += 1
        self.ann.__exit__(*exc)
        return False


def profile(name: str):
    """Context manager timing the enclosed region under ``name`` when
    profiling is enabled; a shared no-op otherwise."""
    return _Timer(name) if _enabled else _NULL


def count(name: str, n: float = 1) -> None:
    """Add `n` to counter ``name`` when profiling is enabled."""
    if _enabled:
        _counts[name] = _counts.get(name, 0) + n


def enable() -> None:
    global _enabled, _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def reset() -> None:
    _acc.clear()
    _counts.clear()


def snapshot() -> Dict[str, Dict[str, float]]:
    out: Dict[str, Dict[str, float]] = {
        k: {"seconds": v[0], "self_seconds": v[1], "calls": int(v[2])}
        for k, v in _acc.items()}
    out.update((k, {"count": v}) for k, v in _counts.items())
    return dict(sorted(out.items()))
