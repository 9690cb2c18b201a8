"""The trace reduction on a small recorded trace: a 0.12 s slice of the
profiler trace of a hash-ycsbB-zipf run on a TPU v5e, cut down to the
device's XLA Ops and XLA Modules lines and the host's bench.* spans (the
traced-window span trimmed to the slice)."""

import gzip
import os

import pytest

import trace_reduce as trd

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "v5e_hash_ycsbB.xplane.pb.gz")


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e_hash_ycsbB.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f:
        path.write_bytes(f.read())
    return trd.load(str(path))


def _naive_busy(events, lo, hi):
    """Busy nanoseconds by marking every covered point between event edges."""
    edges = sorted({lo, hi} | {min(max(x, lo), hi) for s, e, _ in events for x in (s, e)})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(s <= mid < e for s, e, _ in events):
            busy += b - a
    return busy


def test_reduction_of_recorded_trace(profile):
    red = trd.reduce(profile)
    assert red is not None
    window = [sp for sp in trd.host_spans(profile) if sp[2] == trd.WINDOW_SPAN][0]
    lo, hi = window[0], window[1]
    assert red["window_s"] == pytest.approx((hi - lo) / 1e9)
    ops = trd.device_lines(profile, trd.OPS_LINE)
    assert len(ops) == red["devices"] == 1
    (events,) = ops.values()
    assert red["busy_s"] == pytest.approx(_naive_busy(events, lo, hi) / 1e9)
    assert 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"])
    labels = {k for k, _ in red["idle_gaps"]}
    assert labels <= {"store.get_many", "store.put_many", "arena.read_runs",
                      "arena.write_runs", "arena.flush", trd.NO_SPAN}
    assert "store.get_many" in labels
    programs = " ".join(k for k, _ in red["device_ops"])
    assert "jit_gather_program" in programs and "jit_slice_program" in programs


def test_merge_and_labels():
    ivs = [(0, 10, "a"), (5, 15, "b"), (20, 30, "c"), (29, 29, "d")]
    assert trd.merge(ivs, 2, 25) == [(2, 15), (20, 25)]
    spans = [(0, 100, "bench.traced_window"), (10, 50, "bench.store.get_many"),
             (20, 30, "bench.arena.read_runs"), (60, 70, "bench.store.put_many")]
    got = trd.label_points(spans, [25, 40, 55, 65, 5])
    assert got == ["arena.read_runs", "store.get_many", trd.NO_SPAN,
                   "store.put_many", trd.NO_SPAN]


def test_trace_without_window_gives_nothing(profile, monkeypatch):
    monkeypatch.setattr(trd, "WINDOW_SPAN", "bench.no_such_span")
    assert trd.reduce(profile) is None
