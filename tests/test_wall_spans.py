"""Wall-clock spans and counters of ``repro.obs.profile``, and the sites in
the store that write them."""

import json
import time

import pytest

from repro import obs
from repro.obs import profile as prof


@pytest.fixture
def spans_on():
    prof.reset()
    prof.enable()
    try:
        yield
    finally:
        prof.disable()
        prof.reset()


def test_off_is_a_shared_no_op():
    assert not prof.enabled()
    prof.reset()
    a, b = prof.profile("x"), prof.profile("y")
    assert a is b is prof._NULL
    with a:
        prof.count("reads.wave", 3)
    assert prof.snapshot() == {}


def test_nested_spans_and_counters_add_up(spans_on):
    for _ in range(2):
        with prof.profile("outer"):
            time.sleep(0.002)
            with prof.profile("inner"):
                time.sleep(0.004)
                with prof.profile("leaf"):
                    time.sleep(0.001)
            with prof.profile("inner"):
                pass
    prof.count("reads.wave")
    prof.count("reads.wave", 4)
    snap = prof.snapshot()
    outer, inner, leaf = snap["outer"], snap["inner"], snap["leaf"]
    assert (outer["calls"], inner["calls"], leaf["calls"]) == (2, 4, 2)
    assert outer["seconds"] == pytest.approx(outer["self_seconds"] + inner["seconds"])
    assert inner["seconds"] == pytest.approx(inner["self_seconds"] + leaf["seconds"])
    assert leaf["seconds"] == leaf["self_seconds"] >= 0.002
    assert outer["self_seconds"] >= 0.004 and inner["self_seconds"] >= 0.008
    assert snap["reads.wave"] == {"count": 5}
    prof.reset()
    assert prof.snapshot() == {}


def test_metrics_export_writes_counters(tmp_path):
    try:
        with obs.observe(metrics=True) as sess:
            with prof.profile("store.get_many"):
                prof.count("reads.name_probe", 2)
            jpath = sess.export_metrics(str(tmp_path / "m.prom"))
    finally:
        obs.stop()
        prof.reset()
    text = (tmp_path / "m.prom").read_text()
    assert 'rnvm_profile_count{site="reads.name_probe"} 2' in text
    assert 'rnvm_profile_calls{site="store.get_many"} 1' in text
    assert 'rnvm_profile_self_seconds{site="store.get_many"}' in text
    data = json.loads(open(jpath).read())
    assert data["profile"]["reads.name_probe"] == {"count": 2}


def test_arena_read_is_split_and_counted(spans_on):
    from repro.core.devmem import DeviceArena

    arena = DeviceArena(1 << 16)
    arena.write_runs([(0, b"abcdef")])
    arena.flush()
    prof.reset()
    assert arena.read_runs([(1, 3)]) == [b"bcd"]
    snap = prof.snapshot()
    assert snap["arena.reads"] == {"count": 1}
    assert {"arena.read.dispatch", "arena.read.wait", "arena.read.copy"} <= set(snap)
    assert arena.read_runs([(0, 2), (4, 2)]) == [b"ab", b"ef"]
    snap = prof.snapshot()
    assert snap["arena.reads"] == {"count": 2}
    for name in ("arena.read.prep", "arena.read.dispatch", "arena.read.wait"):
        assert snap[name]["calls"] >= 1, name
    wait = snap["arena.read.wait"]
    assert wait["seconds"] == pytest.approx(wait["self_seconds"]
                                            + snap["arena.read.copy"]["seconds"])
    assert arena.read(0, 1) == b"a"
    assert prof.snapshot()["arena.reads"] == {"count": 3}


def test_arena_writes_and_copies_are_split(spans_on):
    import numpy as np

    from repro.core.devmem import DeviceArena

    arenas = [DeviceArena(1 << 16) for _ in range(2)]
    arenas[0].write_runs([(0, b"xy"), (8, b"zw")])
    arenas[0].flush()
    arenas[0].copy_runs(np.array([0]), np.array([100]), np.array([2]), into=arenas[1:])
    snap = prof.snapshot()
    for name in ("arena.flush.prep", "arena.flush.dispatch",
                 "arena.copy.prep", "arena.copy.dispatch"):
        assert snap[name]["calls"] >= 1, name
    assert "arena.reads" not in snap
    assert arenas[1].read(100, 2) == b"xy"


def _tiny_bptree(n_shards=4):
    from repro.cluster import ClusterFrontEnd, NVMCluster, ShardedBPTree
    from repro.core import FEConfig

    cluster = NVMCluster(n_blades=2, n_shards=n_shards, num_mirrors=1,
                         capacity_per_blade=1 << 22)
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=1 << 14), fe_id=0)
    tree = ShardedBPTree(cfe, "t")
    tree.put_many([(k, 10 * k) for k in range(300)])
    tree.drain()
    return tree


@pytest.mark.parametrize("n_shards", [4, 8])
def test_range_scan_probes_every_shard_once(spans_on, n_shards):
    tree = _tiny_bptree(n_shards)
    tree.range_scan(0, 5)      # every shard bound and cached
    prof.reset()
    rows = tree.range_scan(20, 60)
    assert rows == [(k, 10 * k) for k in range(20, 61)]
    snap = prof.snapshot()
    assert snap["reads.name_probe"] == {"count": n_shards}
    assert snap["shard.probe"]["calls"] == n_shards
    assert snap["store.range_scan"]["calls"] == 1
    named = sum(snap.get(k, {}).get("count", 0) for k in
                ("reads.name_probe", "reads.wave", "reads.serial", "reads.apply_log"))
    assert named <= snap["arena.reads"]["count"]


def test_store_calls_and_group_commit_are_spanned(spans_on):
    tree = _tiny_bptree()
    prof.reset()
    tree.put_many([(k, k) for k in range(1000, 1040)])
    tree.drain()
    assert tree.get_many([1000, 1039, 5]) == [1000, 1039, 50]
    snap = prof.snapshot()
    assert snap["store.put_many"]["calls"] == 1
    assert snap["store.get_many"]["calls"] == 1
    assert snap["commits"]["count"] == snap["fe.group_commit"]["calls"] >= 1
    assert snap["reads.apply_log"]["count"] >= 1
    # the group commit runs the blade apply, which nests the existing sites
    assert snap["apply_phase"]["calls"] >= 1


def test_spans_land_in_a_profiler_trace(spans_on, tmp_path):
    import glob

    import jax

    tree = _tiny_bptree()
    tree.range_scan(0, 5)
    jax.profiler.start_trace(str(tmp_path))
    try:
        tree.range_scan(20, 60)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    names = {e.name for plane in pd.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events}
    assert {"repro.store.range_scan", "repro.shard.probe"} <= names
