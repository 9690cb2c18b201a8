"""A run whose timed path is broken underneath must come out not correct:
one test per fault the cells can have, and the control, a mirror that lags
(the store's own asynchronous replication path), which breaks the
synchronous-mirror guarantee the configurations state."""

import pytest

import run
from test_cells_cpu import CELLS, small_run


def _wrap(store, name, make):
    orig = getattr(store, name)
    setattr(store, name, make(orig))


def writes_dropped(cluster, cfe, store):
    """put_many returns with the store's state unchanged."""
    _wrap(store, "put_many", lambda orig: lambda pairs: None)


def half_of_reads(cluster, cfe, store):
    """get_many serves the first half of the batch and leaves the rest out."""
    def make(orig):
        def get_many(keys):
            half = orig(keys[:len(keys) // 2 + 1])
            return half + [None] * (len(keys) - len(half))
        return get_many
    _wrap(store, "get_many", make)


def half_of_writes(cluster, cfe, store):
    """put_many applies the first half of the batch only."""
    _wrap(store, "put_many", lambda orig: lambda pairs: orig(pairs[:len(pairs) // 2]))


def _altered(v):
    """An 8-byte value plus one, or a record with its first byte flipped."""
    return v + 1 if isinstance(v, int) else bytes([v[0] ^ 1]) + bytes(v[1:])


def answer_altered(cluster, cfe, store):
    """get_many alters one answer where it is produced."""
    def make(orig):
        def get_many(keys):
            out = orig(keys)
            if out and out[-1] is not None:
                out[-1] = _altered(out[-1])
            return out
        return get_many
    _wrap(store, "get_many", make)


def scan_row_altered(cluster, cfe, store):
    """range_scan alters the value of one row."""
    def make(orig):
        def range_scan(lo, hi):
            rows = orig(lo, hi)
            if rows:
                k, v = rows[-1]
                rows[-1] = (k, _altered(v))
            return rows
        return range_scan
    _wrap(store, "range_scan", make)


def mirrors_lag(cluster, cfe, store):
    """The control: replication runs 64 writes behind the primary."""
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.set_lag(64)


POINT_FAULTS = (writes_dropped, half_of_reads, half_of_writes, answer_altered)
SCAN_FAULTS = (writes_dropped, half_of_writes, scan_row_altered)
CASES = [(c, f) for c in CELLS
         for f in (SCAN_FAULTS if "scan" in run.load_cell(c)["traffic_data"]["ops"]
                   else POINT_FAULTS)]


@pytest.mark.parametrize("cell,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_turns_correct_false(cell, fault):
    out = small_run(cell, tamper=fault)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_mirror_lag_fails(cell):
    out = small_run(cell, tamper=mirrors_lag)
    assert out["correct"] is False
    assert out["checks"]["mirror_bytes_differ"]["value"] > 0
