"""YCSB's wide records through the harness, on the CPU: a configuration's
``record`` group (10 fields of 100 bytes), one-field updates as a
read-modify-write, the byte-exact reference and read-back.  The store is
``host_records.HostRecordStore``, put in place of the sharded structures
together with the fixture cell under ``data/records``; no cell of
BENCHMARK.json uses it."""

import numpy as np
import pytest

import run
import traffic as tr
from host_records import CELL, HostRecordStore, use_host_records
from reference import RecordReference, replay

SHAPE = tr.RecordShape(10, 100)
YCSB_A = {"rate_ops_s": 100, "ops": {"read": 0.5, "update": 0.5},
          "keys": {"distribution": "zipfian", "theta": 0.99}}


@pytest.fixture
def cell(monkeypatch):
    use_host_records(monkeypatch, run)
    return CELL


def record_run(cell, seed=20261, seconds=1.0, **kw):
    return run.run_cell(cell, seed, seconds, False, require_accelerator=False,
                        log=lambda *a: None, **kw)


def wrap(store, name, make):
    setattr(store, name, make(getattr(store, name)))


# ------------------------------------------------------------------ runs
def test_record_cell_runs_correct(cell):
    seen = {}

    def watch(cluster, cfe, store):
        seen["store"] = store
        wrap(store, "get_many", lambda orig: lambda keys: seen.setdefault(
            "gets", []).append(len(keys)) or orig(keys))

    out = record_run(cell, tamper=watch)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] == 400
    store = seen["store"]
    assert isinstance(store, HostRecordStore) and store.value_bytes == 1000
    assert len(store.d) == 1024
    assert all(type(v) is bytes and len(v) == 1000 for v in store.d.values())
    # reads and the updates' reads: every op of the window and warm-up,
    # the 16,384-key read-back covering all 1,024 records
    assert sum(seen["gets"]) == 400 + 200 + 1024


def test_flipped_byte_in_one_answer_is_wrong(cell):
    flipped = []

    def flip_once(cluster, cfe, store):
        def make(orig):
            def get_many(keys):
                out = orig(keys)
                if not flipped and out:
                    flipped.append(keys[-1])
                    b = bytearray(out[-1])
                    b[437] ^= 0x01
                    out[-1] = bytes(b)
                return out
            return get_many
        wrap(store, "get_many", make)

    out = record_run(cell, tamper=flip_once)
    assert flipped
    assert out["checks"]["wrong_answers"]["value"] >= 1
    assert out["correct"] is False


def test_dropped_field_update_is_caught(cell):
    """put_many writes back the record it already holds for every key it
    has: each one-field update is lost."""
    def drop_updates(cluster, cfe, store):
        def make(orig):
            return lambda pairs: orig([(k, store.d.get(k, v)) for k, v in pairs])
        wrap(store, "put_many", make)

    out = record_run(cell, tamper=drop_updates)
    checks = out["checks"]
    assert checks["wrong_answers"]["value"] + checks["readback_mismatches"]["value"] >= 1
    assert out["correct"] is False


def test_one_byte_of_one_field_of_one_record_is_read_back(cell, monkeypatch):
    """One byte of one field of the coldest loaded record, which the window
    neither reads nor writes, altered after the load: the read-back from
    the store finds it."""
    loaded = {}
    load_records = run.load_records

    def capture(store, config, rec):
        loaded["rec"] = rec
        return load_records(store, config, rec)

    def alter_one_field(cluster, cfe, store):
        rec = loaded["rec"]
        i = int(rec.perm[-1])
        loaded["key"] = key = int(rec.keys[i])
        b = bytearray(store.d[key])
        b[SHAPE.field(6).start + 41] ^= 0x80
        store.d[key] = bytes(b)

    monkeypatch.setattr(run, "load_records", capture)
    out = record_run(cell, tamper=alter_one_field)
    assert "key" in loaded
    assert out["checks"]["readback_mismatches"]["value"] == 1
    assert out["correct"] is False


# --------------------------------------------------------------- updates
def test_two_updates_of_one_key_in_one_batch_both_land():
    rec = tr.make_records(7, 8, 0, SHAPE)
    store = HostRecordStore(None, "t", value_bytes=SHAPE.width)
    store.put_many([(int(k), v.tobytes()) for k, v in zip(rec.keys, rec.values)])
    key = int(rec.keys[3])
    data = np.zeros((3, SHAPE.width), np.uint8)
    data[0, SHAPE.field(2)] = 0xAA
    data[1, SHAPE.field(7)] = 0xBB
    data[2, SHAPE.field(2)] = 0xCC
    read, update = tr.KINDS.index(tr.READ), tr.KINDS.index(tr.UPDATE)
    ops = tr.Ops(due=np.zeros(4), kind=np.array([update, update, read, update]),
                 key=np.array([key, key, key, int(rec.keys[5])]), hi=np.zeros(4, np.int64),
                 value=np.zeros(4, np.int64), field=np.array([2, 7, -1, 2]),
                 data=np.concatenate([data[:2], np.zeros((1, SHAPE.width), np.uint8),
                                      data[2:]]), shape=SHAPE)
    h = run.Harness(store, max_batch=16)
    done = h.serve(ops, limit_s=5.0)
    assert np.all(np.isfinite(done))
    assert [ev[0] for ev in h.events] == ["get", "put"]   # one of each for the batch
    want = rec.values[3].copy()
    want[SHAPE.field(2)] = 0xAA
    want[SHAPE.field(7)] = 0xBB
    assert store.d[key] == want.tobytes()
    other = rec.values[5].copy()
    other[SHAPE.field(2)] = 0xCC
    assert store.d[int(rec.keys[5])] == other.tobytes()
    ref = RecordReference(rec.keys.tolist(), rec.values, ordered=False)
    assert replay(ref, h.events)[:2] == (4, 0)
    assert ref.get(key) == want.tobytes()


def test_inserts_carry_whole_records_updates_one_field():
    more = tr.make_records(0, 16, 12, SHAPE)
    ops = tr.make_ops({**YCSB_A, "ops": {"update": 0.5, "insert": 0.5}}, more, 0, 0.2)
    inserts = ops.kind == tr.KINDS.index(tr.INSERT)
    assert inserts.any() and (~inserts).any()
    assert np.all(ops.field[inserts] == -1) and np.all(ops.data[inserts].max(axis=1) > 0)
    for w in np.flatnonzero(~inserts):
        f = int(ops.field[w])
        assert 0 <= f < SHAPE.fieldcount
        rest = np.delete(ops.data[w], np.arange(SHAPE.width)[SHAPE.field(f)])
        assert ops.data[w, SHAPE.field(f)].max() > 0 and not rest.any()


# ---------------------------------------------------------- entry points
def records_on_the_cpu(monkeypatch, store_class=HostRecordStore):
    """The fixture's cells on `store_class`, with the CPU standing in for
    the chip, and every value the store is handed kept in ``sent``."""
    sent = []

    class Watched(store_class):
        def put_many(self, pairs):
            sent.extend(v for _, v in pairs)
            return super().put_many(pairs)

    use_host_records(monkeypatch, run)
    monkeypatch.setattr(run, "structure_class", lambda name: Watched)
    devices = run.devices
    monkeypatch.setattr(run, "devices", lambda chips, *a, **kw: devices(chips, False))
    return sent


def test_sweep_sends_records(monkeypatch, capsys):
    import json

    import sweep

    sent = records_on_the_cpu(monkeypatch)
    assert sweep.main(["--workload", CELL, "--rates", "100", "--seconds", "0.5",
                       "--seed", "20263"]) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["cell"] == CELL and line["errors"] == 0
    assert len(sent) > 1024
    assert all(type(v) is bytes and len(v) == SHAPE.width for v in sent)


class FlipOnce(HostRecordStore):
    """Flips one byte of the first record it answers."""
    flipped = False

    def get_many(self, keys):
        out = super().get_many(keys)
        if not self.flipped and out and out[0] is not None:
            self.flipped = True
            out[0] = bytes([out[0][0] ^ 1]) + out[0][1:]
        return out


@pytest.mark.parametrize("store", [HostRecordStore, FlipOnce])
def test_program_spans_cost_runs_check_records(monkeypatch, capsys, store):
    import json

    import program_spans

    sent = records_on_the_cpu(monkeypatch, store)
    assert program_spans.main(["--workload", CELL, "--seeds", "20264",
                               "--seconds", "0.5", "--cost"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["answers_compared"] > 0 and last["store_errors"] == []
    assert last["wrong_answers"] == (store is FlipOnce)
    assert all(type(v) is bytes and len(v) == SHAPE.width for v in sent)


# ------------------------------------------------------------ refusals
def test_structure_without_value_bytes_is_refused_before_the_cluster(monkeypatch):
    import repro.cluster

    class EightByteStore:
        def __init__(self, cfe, name, n_buckets=4096):
            pass

    def no_cluster(*a, **kw):
        raise AssertionError("the cluster was built")

    monkeypatch.setattr(run, "structure_class", lambda name: EightByteStore)
    monkeypatch.setattr(repro.cluster, "NVMCluster", no_cluster)
    config = run.load_cell(CELL, index=run.BENCH_DIR / "tests/data/records/cells.json",
                           workloads=run.BENCH_DIR / "tests/data/records")["config_data"]
    with pytest.raises(ValueError, match="8-byte integer keys and values"):
        run.build(config)


@pytest.mark.parametrize("override", [{"value_bytes": 999},
                                      {"record": {"fieldlength": 99}},
                                      {"record": {"fieldsize": 100}},
                                      {"record": None}])
def test_record_width_that_does_not_add_up_stops_before_any_run(cell, override, monkeypatch):
    def no_devices(*a, **kw):
        raise AssertionError("the run started")

    monkeypatch.setattr(run, "devices", no_devices)
    with pytest.raises(ValueError):
        record_run(cell, config_overrides=override)


# --------------------------------------------------------------- streams
def test_seed0_records_and_field_updates_pinned():
    rec = tr.make_records(0, 16, 4, SHAPE)
    kv8 = tr.make_records(0, 16, 4)
    # the keys, inserts' keys and rank order are the 8-byte configuration's
    assert rec.keys.tolist() == kv8.keys.tolist()
    assert rec.extra.tolist() == kv8.extra.tolist()
    assert rec.perm.tolist() == kv8.perm.tolist()
    assert rec.values.shape == (16, 1000) and rec.values.dtype == np.uint8
    assert rec.values[0, :8].tolist() == [69, 79, 120, 228, 109, 130, 75, 93]
    assert rec.values[15, -4:].tolist() == [190, 97, 10, 233]
    ops = tr.make_ops(YCSB_A, rec, 0, 0.2)
    plain = tr.make_ops(YCSB_A, kv8, 0, 0.2)
    for name in ("due", "kind", "key", "hi", "value"):
        assert getattr(ops, name).tolist() == getattr(plain, name).tolist()
    upd = np.flatnonzero(ops.kind == tr.KINDS.index(tr.UPDATE))
    assert upd[:3].tolist() == [0, 1, 5]
    assert ops.field[upd].tolist() == [0, 2, 8, 8, 1, 4, 7, 7, 7, 3]
    assert np.all(ops.field[ops.kind != tr.KINDS.index(tr.UPDATE)] == -1)
    first = ops.data[upd[0]]
    assert first[SHAPE.field(0)][:8].tolist() == [246, 62, 201, 94, 115, 19, 137, 29]
    assert not first[SHAPE.field(0).stop:].any()   # only the field's own bytes
