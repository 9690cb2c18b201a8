"""A test-only store that keeps wide values in a host dict.

It takes the place of the sharded structures where a cell's configuration
states YCSB's records (``"record": {"fieldcount": 10, "fieldlength": 100}``),
so that the harness's record path (load, read-modify-write updates,
reference, read-back) runs end to end before the store keeps such values.
The cluster is still built around it, and holds nothing of it.
"""

from __future__ import annotations

import pathlib
from typing import Dict, List, Optional, Tuple

DATA = pathlib.Path(__file__).resolve().parent / "data" / "records"
CELL = "host1k-ycsbA-zipf"


class HostRecordStore:
    def __init__(self, cfe, name: str, n_buckets: int = 0, value_bytes: int = 8):
        self.value_bytes = value_bytes
        self.d: Dict[int, bytes] = {}

    def put_many(self, pairs: List[Tuple[int, bytes]]) -> None:
        for k, v in pairs:
            if len(v) != self.value_bytes:
                raise ValueError(f"a value of {len(v)} bytes, not {self.value_bytes}")
            self.d[k] = bytes(v)

    def get_many(self, keys: List[int]) -> List[Optional[bytes]]:
        return [self.d.get(k) for k in keys]

    def drain(self) -> None:
        pass


def use_host_records(monkeypatch, run) -> None:
    """Point the harness at the fixture's cells and at this store."""
    load_cell = run.load_cell
    monkeypatch.setattr(run, "load_cell", lambda name: load_cell(
        name, index=DATA / "cells.json", workloads=DATA))
    monkeypatch.setattr(run, "structure_class", lambda name: HostRecordStore)
