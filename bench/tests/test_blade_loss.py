"""The fault schedule: a cell whose traffic fails a blade, on the CPU at 2^10
records.  A promoted mirror that lost an acknowledged write, and a run in
which the blade never fails, both come out not correct; ``recovery_s`` and
the failover readers on synthetic records; a cell without a fault runs no
fault hook and no failover wrapper."""

import struct

import numpy as np
import pytest

import run
import spans
from test_cells_cpu import CELLS, small_run

CELL = "hash-ycsbB-zipf-bladeloss"
NO_FAULT = [c for c in CELLS if "fault" not in run.load_cell(c)["traffic_data"]]


def corrupt_coldest_in_mirror(monkeypatch, blade=1):
    """A ``tamper`` that alters, in `blade`'s mirror alone, the loaded value
    of the blade's coldest key (the last in the zipfian order), so that it
    is neither read nor written in the window: only a read-back after the
    promotion can catch it.  Patches ``run.load_records`` to see the
    records; returns the tamper and a dict that gets the key."""
    loaded = {}
    load_records = run.load_records

    def capture(store, config, rec):
        loaded["rec"] = rec
        return load_records(store, config, rec)

    def corrupt_mirror(cluster, cfe, store):
        rec = loaded["rec"]
        shards = set(cluster.directory.shards_on(blade))
        i = next(int(rec.perm[r]) for r in range(len(rec.perm) - 1, -1, -1)
                 if cfe.directory.shard_of(int(rec.keys[rec.perm[r]])) in shards)
        loaded["key"] = int(rec.keys[i])
        value = struct.pack("<q", int(rec.values[i]))
        image = cluster.blades[blade].arena.snapshot()
        offsets, o = [], image.find(value)
        while o >= 0:
            offsets.append(o)
            o = image.find(value, o + 1)
        assert offsets, f"the value is nowhere in blade {blade}'s arena"
        mirror = cluster.blades[blade].mirrors[0].arena
        altered = struct.pack("<q", int(rec.values[i]) ^ 1)
        mirror.write_runs([(o, altered) for o in offsets])
        mirror.flush()

    monkeypatch.setattr(run, "load_records", capture)
    return corrupt_mirror, loaded


@pytest.mark.parametrize("sample", [run.READBACK_KEYS, 8])
def test_promoted_mirror_missing_an_acknowledged_write_is_not_correct(sample, monkeypatch):
    """One loaded value on blade 1 altered in its mirror before the window:
    after the promotion the store serves the altered value.  With a
    read-back sample of 8 of the 1,024 records, as the cell's 16,384 of
    2^19 would leave it, the key is read back as one of the lost shards'."""
    tamper, loaded = corrupt_coldest_in_mirror(monkeypatch)
    monkeypatch.setattr(run, "READBACK_KEYS", sample)
    out = small_run(CELL, tamper=tamper)
    assert "key" in loaded
    checks = out["checks"]
    assert checks["failovers_missing"]["value"] == 0
    assert checks["readback_mismatches"]["value"] >= 1
    assert out["correct"] is False


def test_blade_that_never_fails_is_not_correct():
    out = small_run(CELL, traffic={"fault": None})
    assert out["checks"]["failovers_missing"]["value"] == 1
    assert "recovery_s" not in out["metrics"]
    assert out["correct"] is False


def test_recovery_s_reader():
    read = run.load_reader("recovery_s")
    due = np.array([0.0, 1.0, 1.5, 2.0, 3.0, 4.0])
    done = np.array([0.5, 2.5, 2.5, 2.5, 3.25, 4.25])
    lost = np.array([True, True, False, False, True, True])
    # the op due at 1.0 s, waiting when the blade fails at 1.5 s, is the first
    # on a lost shard to complete after it
    assert read({"fault": {"t": 1.5, "lost": lost}, "due_s": due, "done_s": done}) == 1.0
    assert read({"fault": {"t": 0.25, "lost": lost}, "due_s": due, "done_s": done}) == 0.25
    assert read({"fault": {"t": 2.75, "lost": lost}, "due_s": due, "done_s": done}) == 0.5
    never = done.copy()
    never[[1, 4, 5]] = np.inf
    assert read({"fault": {"t": 1.5, "lost": lost}, "due_s": due, "done_s": never}) is None
    assert read({"fault": {"t": 4.5, "lost": lost}, "due_s": due, "done_s": done}) is None
    assert read({"fault": None, "due_s": due, "done_s": done}) is None


def test_failover_readers():
    span0 = {"seconds.clone": 0.5, "seconds.reboot": 0.0, "seconds.recover_blade": 0.0,
             "calls.clone": 1, "calls.reboot": 0, "calls.recover_blade": 0}
    span1 = {"seconds.clone": 0.75, "seconds.reboot": 0.5, "seconds.recover_blade": 2.0,
             "calls.clone": 3, "calls.reboot": 1, "calls.recover_blade": 1}
    names = ("failover_clone_s", "failover_reboot_s", "failover_rest_s")
    got = {n: run.load_reader(n)({"span0": span0, "span1": span1}) for n in names}
    assert got == {"failover_clone_s": 0.25, "failover_reboot_s": 0.5,
                   "failover_rest_s": 1.25}
    no_failover = {k: v for k, v in span0.items() if "clone" in k}
    for n in names:
        assert run.load_reader(n)({"span0": span1, "span1": span1}) is None
        assert run.load_reader(n)({"span0": no_failover, "span1": no_failover}) is None


def _hooks_and_wrappers(monkeypatch):
    seen = {"hooks": [], "failover_installs": 0}
    serve = run.Harness.serve

    def serve_logged(self, ops, *, limit_s, hooks=()):
        seen["hooks"].append([(at, fn.__name__) for at, fn in hooks])
        return serve(self, ops, limit_s=limit_s, hooks=hooks)

    install = spans.Spans.install

    def install_logged(self):
        seen["failover_installs"] += self.failover
        return install(self)

    monkeypatch.setattr(run.Harness, "serve", serve_logged)
    monkeypatch.setattr(spans.Spans, "install", install_logged)
    return seen


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", NO_FAULT)
def test_cell_without_fault_runs_no_fault_code(cell, trace, monkeypatch):
    from repro.cluster import ClusterFrontEnd
    from repro.core.backend import NVMBackend

    originals = (NVMBackend.reboot, ClusterFrontEnd.recover_blade)
    seen = _hooks_and_wrappers(monkeypatch)
    out = small_run(cell, trace=trace)
    assert out["correct"] is True, out["checks"]
    warm_up, window = seen["hooks"]
    assert warm_up == []
    assert [name for _, name in window] == (["trace_on", "trace_off"] if trace else [])
    assert seen["failover_installs"] == 0
    assert (NVMBackend.reboot, ClusterFrontEnd.recover_blade) == originals
    assert out["checks"]["failovers_missing"]["value"] == 0


def test_fault_is_traced_from_before_it(monkeypatch):
    """The trace starts a fixed lead before the blade fails, so that the
    tracer's start-up stall is not stacked on the promotion's backlog."""
    seen = _hooks_and_wrappers(monkeypatch)
    out = small_run(CELL, seconds=4.0, trace=True)
    assert out["correct"] is True, out["checks"]
    warm_up, window = seen["hooks"]
    assert warm_up == []
    at = {name: at for at, name in window}
    assert set(at) == {"trace_on", "trace_off", "fail"}
    assert at["fail"] == 0.4 * 4.0
    assert at["trace_on"] == pytest.approx(at["fail"] - run.FAULT_TRACE_LEAD_S)
    assert at["trace_off"] > at["fail"]
    assert seen["failover_installs"] == 1
    assert set(out["metrics"]) >= {"failover_clone_s", "failover_reboot_s", "failover_rest_s"}
    assert out["metrics"]["failover_clone_s"]["value"] > 0
