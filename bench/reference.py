"""Plain reference for the benchmark's key/value semantics.

A dict of the current value of every key, and, where the cell scans, the
sorted list of keys.  It imports nothing of the store and takes nothing the
store made: it is built from the same seeded records and replays the same
ops in the order the harness issued them.

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
        return [(k, self.d[k]) for k in self.sorted[i:j]]


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
                    first = first or f"get {k}: {got} != {want}"
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
