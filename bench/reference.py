"""Plain reference for the benchmark's key/value semantics.

A dict of the current value of every key, and, where the cell scans, the
sorted list of keys.  Wide records (``RecordReference``) are rows of one
``uint8`` array with a key -> row map, handed out as ``bytes``, so that
every comparison is byte for byte.  It imports nothing of the store and takes nothing the store
made: it is built from the same seeded records and replays the same ops in
the order the harness issued them.

The harness logs each store call of a run as one event, in issue order:

* ``("get", keys, answers)``: one ``get_many``; answers[i] is the value of
  keys[i], or None where the key is absent;
* ``("put", keys, values)``: one ``put_many``, acknowledged;
* ``("scan", lo, hi, rows)``: one ``range_scan``, rows the (key, value)
  pairs with lo <= key <= hi in key order.

``replay`` checks every answer against the state the acknowledged puts
before it left, then applies the puts.
"""

from __future__ import annotations

import bisect
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np


class Reference:
    def __init__(self, keys: Sequence[int], values: Sequence[int], ordered: bool):
        self.d: Dict[int, int] = dict(zip(keys, values))
        self.sorted: Optional[List[int]] = sorted(self.d) if ordered else None

    def get(self, k: int) -> Optional[int]:
        return self.d.get(k)

    def put(self, k: int, v: int) -> None:
        if self.sorted is not None and k not in self.d:
            bisect.insort(self.sorted, k)
        self.d[k] = v

    def scan(self, lo: int, hi: int) -> List[Tuple[int, int]]:
        i = bisect.bisect_left(self.sorted, lo)
        j = bisect.bisect_right(self.sorted, hi)
        return [(k, self.get(k)) for k in self.sorted[i:j]]


class RecordReference(Reference):
    """Wide values as ``bytes``: the loaded records are the rows of one
    ``uint8`` array (copied), row ``row[k]`` key k's; a key inserted later
    keeps its record in ``d``."""

    def __init__(self, keys: Sequence[int], rows: np.ndarray, ordered: bool):
        super().__init__((), (), ordered)
        self.rows = np.array(rows, np.uint8)
        self.row: Dict[int, int] = dict(zip(keys, range(len(keys))))
        self.sorted = sorted(self.row) if ordered else None

    def get(self, k: int) -> Optional[bytes]:
        r = self.row.get(k)
        return self.d.get(k) if r is None else self.rows[r].tobytes()

    def put(self, k: int, v: bytes) -> None:
        r = self.row.get(k)
        if r is None:
            super().put(k, bytes(v))
        else:
            self.rows[r] = np.frombuffer(v, np.uint8)


def _describe(got, want) -> str:
    """An answer against the reference's; two records by their first
    differing byte."""
    if isinstance(want, bytes) and isinstance(got, (bytes, bytearray)) \
            and len(got) == len(want):
        i = next(i for i in range(len(want)) if got[i] != want[i])
        return f"byte {i} is {got[i]}, not {want[i]}"
    return f"{str(got)[:80]} != {str(want)[:80]}"


def replay(ref: Reference, events: Iterable[tuple]) -> Tuple[int, int, str]:
    """Check every logged answer in order; returns (answers compared,
    answers wrong, a description of the first wrong one or "")."""
    compared = wrong = 0
    first = ""
    for ev in events:
        if ev[0] == "get":
            _, keys, answers = ev
            for k, got in zip(keys, answers):
                want = ref.get(k)
                compared += 1
                if got != want:
                    wrong += 1
                    first = first or f"get {k}: {_describe(got, want)}"
            if len(answers) != len(keys):
                wrong += abs(len(keys) - len(answers))
                first = first or f"get_many: {len(answers)} answers for {len(keys)} keys"
        elif ev[0] == "put":
            _, keys, values = ev
            for k, v in zip(keys, values):
                ref.put(k, v)
        elif ev[0] == "scan":
            _, lo, hi, rows = ev
            want = ref.scan(lo, hi)
            compared += 1
            if rows != want:
                wrong += 1
                first = first or (f"scan [{lo}, {hi}]: {len(rows)} rows != "
                                  f"{len(want)} rows")
        else:
            raise ValueError(f"unknown event {ev[0]!r}")
    return compared, wrong, first
