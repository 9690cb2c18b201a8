"""Reading the store's own spans and counters: idle gaps labelled by program
span, on synthetic traces and on the recorded v5e trace with program spans
laid over it, and the counters of a traced CPU rehearsal of each cell
against the harness's wrapper counts."""

import gzip
import os
from types import SimpleNamespace as NS

import pytest

import program_spans as ps
import run
import trace_reduce as trd
from test_cells_cpu import CELLS, small_run

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "v5e_hash_ycsbB.xplane.pb.gz")


def _ev(name, s, e):
    return NS(name=name, start_ns=s, duration_ns=e - s)


def _trace(device_ops, host_spans):
    """A stand-in for ``jax.profiler.ProfileData``: one device plane with
    an ``XLA Ops`` line, one host plane with one line of spans."""
    dev = NS(name="/device:TPU:0",
             lines=[NS(name=trd.OPS_LINE, events=[_ev(n, s, e) for s, e, n in device_ops])])
    host = NS(name="/host:CPU", lines=[NS(name="main", events=[_ev(n, s, e)
                                                              for s, e, n in host_spans])])
    return NS(planes=[dev, host])


SPANS = [(0, 100, "bench.traced_window"),
         (5, 80, "bench.store.get_many"),
         (6, 79, "repro.store.get_many"),
         (12, 38, "bench.arena.read_runs"),
         (12, 38, "repro.fe.read_wave"),
         (14, 37, "repro.arena.read.wait"),
         (20, 37, "repro.arena.read.copy"),
         (50, 70, "repro.shard.probe"),
         (84, 88, "bench.store.put_many")]
SYNTHETIC = _trace([(0, 10, "op"), (40, 45, "op"), (90, 100, "op")], SPANS)


def test_synthetic_gaps_take_the_innermost_program_span():
    # gaps: [10, 40) mid 25 in the copy; [45, 90) mid 67.5 in the probe
    got = dict(ps.idle_by_program_span(SYNTHETIC))
    assert got == pytest.approx({"arena.read.copy": 30e-9, "shard.probe": 45e-9})
    mids = [13, 30, 60, 77, 79.5, 86, 95]
    assert ps.innermost(ps.program_spans(SYNTHETIC), mids) == [
        "repro.fe.read_wave", "repro.arena.read.copy", "repro.shard.probe",
        "repro.store.get_many", None, None, None]


@pytest.mark.parametrize("mid, label", [(79.5, ps.IN_STORE), (86, ps.IN_STORE),
                                        (82, ps.OUTSIDE), (39, "store.get_many")])
def test_gap_labels_outside_program_spans(mid, label):
    """A gap centred at `mid`: device busy on both sides of it."""
    lo, hi = mid - 0.5, mid + 0.5
    pd = _trace([(0, lo, "op"), (hi, 100, "op")], SPANS)
    assert ps.idle_by_program_span(pd) == [[label, pytest.approx(1e-9)]]


def test_synthetic_labels_sum_to_reduce_idle():
    red = trd.reduce(SYNTHETIC, top=100)
    prog = ps.idle_by_program_span(SYNTHETIC)
    assert sum(v for _, v in prog) == pytest.approx(sum(v for _, v in red["idle_gaps"]))


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "v5e_hash_ycsbB.xplane.pb"
    with gzip.open(FIXTURE, "rb") as f:
        path.write_bytes(f.read())
    return trd.load(str(path))


def test_recorded_trace_without_program_spans_gives_nothing(recorded):
    """The recorded trace predates the program's spans: no labels, and
    reduce's own keys are what they were."""
    assert ps.program_spans(recorded) == []
    assert ps.idle_by_program_span(recorded) is None
    red = trd.reduce(recorded)
    assert set(red) == {"busy_s", "window_s", "devices", "device_ops", "idle_gaps"}


def test_recorded_trace_with_program_spans_sums_to_reduce_idle(recorded):
    """Lay a program span over the back half of every ``bench.arena.read_runs``
    span of the recorded trace, and one over every store call: the idle time
    by program span equals reduce's idle time, and the wait span takes idle
    time inside the reads."""
    bench = trd.host_spans(recorded)
    laid = [(s, e, n) for s, e, n in bench]
    for s, e, n in bench:
        if n.startswith("bench.store."):
            laid.append((s, e, "repro." + n[len("bench."):]))
        elif n == "bench.arena.read_runs":
            laid.append(((s + e) / 2, e, "repro.arena.read.wait"))
    host = NS(name="/host:CPU", lines=[NS(name="spans", events=[_ev(n, s, e)
                                                               for s, e, n in laid])])
    devs = [p for p in recorded.planes if p.name.startswith("/device:")]
    pd = NS(planes=devs + [host])
    red = trd.reduce(pd, top=100)
    prog = dict(ps.idle_by_program_span(pd))
    idle = red["window_s"] - red["busy_s"]
    assert sum(prog.values()) == pytest.approx(idle, rel=1e-3)
    assert sum(v for _, v in red["idle_gaps"]) == pytest.approx(idle, rel=1e-3)
    assert 0 < prog["arena.read.wait"] < dict(red["idle_gaps"])["arena.read_runs"]


def test_snapshot_readers_on_a_program_without_counters():
    assert ps.reads_by_cause({}) is None
    assert ps.read_split_us({}) is None
    assert ps.arena_host_seconds({"apply_phase": {"seconds": 1.0}}) is None
    rec = {"profile": {}, "ops": 10, "trace": None}
    for name in ("name_probe_reads_per_op", "apply_log_reads_per_op",
                 "read_wait_us_per_read", "arena_host_us_per_op",
                 "group_commit_us_per_op", "device_idle_in_read_wait_pct"):
        assert run.load_reader(name)(rec) is None, name


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_counts_every_read(cell, monkeypatch):
    caught = {}
    load_reader = run.load_reader

    def catching(name):
        read = load_reader(name)

        def reader(rec):
            caught["rec"] = rec
            return read(rec)
        return reader

    monkeypatch.setattr(run, "load_reader", catching)
    out = small_run(cell, trace=True)
    assert out["correct"] is True, out["checks"]
    rec = caught["rec"]
    wrapper = rec["span1"]["calls.read_runs"] - rec["span0"]["calls.read_runs"]
    causes = ps.reads_by_cause(rec["profile"])
    assert wrapper > 0
    assert sum(causes.values()) == rec["profile"]["arena.reads"]["count"] == wrapper
    assert all(0 <= causes[c] <= wrapper for c in ps.CAUSES)
    assert causes["name_probe"] > 0
    split = ps.read_split_us(rec["profile"])
    assert split["dispatch"] > 0 and split["wait"] > 0 and split["copy"] > 0
