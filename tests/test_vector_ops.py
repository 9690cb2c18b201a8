"""Vector-op batch execution path: equivalence, timing, ordering, recovery.

The load-bearing invariants:

  * batched execution is an *optimization*, not a semantic: `put_many` /
    `get_many` leave the back-end arena byte-identical to the serial loop
    and return the same values;
  * batching never costs simulated time: batched <= serial, always;
  * the combined oplog+memlog flush keeps the ordering invariant (op logs
    durable before or with the memory logs they cover), so a crash mid-batch
    replays cleanly from the group-committed op log;
  * the atomic-contention table and the migrated-shard storage are both
    reclaimed (no unbounded growth).
"""

import random
import struct

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the seeded-random shim
    from _hypothesis_shim import given, settings, st

import pytest

from repro.core import FEConfig, FrontEnd, NVMBackend
from repro.core.backend import CrashError
from repro.core.oplog import decode_oplogs
from repro.core.structures import (
    RemoteBPTree,
    RemoteBST,
    RemoteHashTable,
    RemoteSkipList,
)


def _mk_ht(cache_bytes=1 << 16, n_buckets=128, **cfg):
    be = NVMBackend(capacity=1 << 24)
    fe = FrontEnd(be, FEConfig.rcb(cache_bytes=cache_bytes, **cfg))
    return be, fe, RemoteHashTable(fe, "t", n_buckets=n_buckets)


kv_pairs = st.lists(
    st.tuples(st.integers(min_value=0, max_value=1 << 24),
              st.integers(min_value=-(1 << 30), max_value=1 << 30)),
    min_size=1, max_size=120,
)


@settings(max_examples=30, deadline=None)
@given(kv_pairs)
def test_put_many_byte_identical_to_serial(pairs):
    """Same pairs, same config: the serial loop and put_many must leave the
    two blades' arenas byte-for-byte identical (the batch path only changes
    *when* network charges happen, never what lands in NVM)."""
    be_s, fe_s, ht_s = _mk_ht()
    for k, v in pairs:
        ht_s.put(k, v)
    fe_s.drain(ht_s.h)

    be_b, fe_b, ht_b = _mk_ht()
    ht_b.put_many(pairs)
    fe_b.drain(ht_b.h)

    assert be_s.arena.snapshot() == be_b.arena.snapshot()
    keys = [k for k, _ in pairs]
    assert ht_b.get_many(keys) == [ht_s.get(k) for k in keys]
    # batching must never cost simulated time
    assert fe_b.clock.now <= fe_s.clock.now


@settings(max_examples=20, deadline=None)
@given(kv_pairs, st.data())
def test_get_many_matches_serial_gets(pairs, data):
    _, fe, ht = _mk_ht()
    ht.put_many(pairs)
    probe = [k for k, _ in pairs] + [
        data.draw(st.integers(min_value=0, max_value=1 << 24)) for _ in range(8)
    ]
    assert ht.get_many(probe) == [ht.get(k) for k in probe]


def test_tree_vector_ops_match_serial():
    rng = random.Random(3)
    pairs = sorted({rng.randrange(1 << 20): i for i in range(300)}.items())
    probes = [k for k, _ in pairs[::3]] + [rng.randrange(1 << 20) for _ in range(40)]
    for cls in (RemoteBPTree, RemoteBST, RemoteSkipList):
        be = NVMBackend(capacity=1 << 24)
        fe = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
        t = cls(fe, "t")
        for i in range(0, len(pairs), 64):
            t.insert_many(pairs[i : i + 64])
        fe.drain(t.h)
        serial = [t.find(k) for k in probes]
        t0 = fe.clock.now
        assert t.lookup_many(probes) == serial
        batched_dt = fe.clock.now - t0
        t1 = fe.clock.now
        [t.find(k) for k in probes]
        serial_dt = fe.clock.now - t1
        assert batched_dt <= serial_dt, cls.__name__


def test_batched_time_never_exceeds_serial():
    rng = random.Random(5)
    pairs = [(rng.randrange(1 << 24), i) for i in range(256)]
    _, fe_s, ht_s = _mk_ht(n_buckets=64)
    for k, v in pairs:
        ht_s.put(k, v)
    fe_s.drain(ht_s.h)
    _, fe_b, ht_b = _mk_ht(n_buckets=64)
    for i in range(0, len(pairs), 64):
        ht_b.put_many(pairs[i : i + 64])
    fe_b.drain(ht_b.h)
    assert fe_b.clock.now <= fe_s.clock.now


def test_combined_flush_ordering_invariant():
    """After any flush, every operation the persisted opsn watermark claims
    is applied must be present in the durable op log (op logs durable before
    or with the memory logs they cover)."""
    be, fe, ht = _mk_ht()
    pairs = [(i * 7, i) for i in range(100)]
    ht.put_many(pairs)
    fe.drain(ht.h)
    assert fe.stats.combined_flushes >= 1  # the fold actually happened
    opsn = be.get_name(ht.h.opsn_name)
    seq = be.get_name("t.seq")
    assert seq >= opsn  # op-log watermark never behind the data watermark
    # every op <= opsn has its log entry durable (compaction may have
    # dropped fully-applied prefixes, which is fine — check the claim that
    # nothing in the data area lacks a logged operation: seq covers opsn)
    entries = decode_oplogs(ht.h.oplog_area.read_all())
    seqs = [struct.unpack_from("<Q", e.payload, 0)[0] for e in entries]
    assert seqs == sorted(seqs)


def test_combined_flush_tear_in_memlog_replays_from_oplog():
    """Tear the combined flush inside the memory-log bytes: the op log is
    already whole (it precedes the memory logs in the posted write), the
    torn tx is dropped by checksum at reboot, and replay regenerates it.

    The combined flush's physical writes land in order: (1) op-log payload,
    (2) op-log head slot, (3) seq name slot, (4) memory-log tx payload —
    tearing write #4 models a cut inside the memory-log bytes."""
    be, fe, ht = _mk_ht()
    pairs = [(k, k + 1) for k in range(32)]  # < oplog group: all staged
    with pytest.raises(CrashError):
        with fe.batch(ht.h):
            for k, v in pairs:
                ht.put(k, v)
            be.schedule_torn_write(10, after_writes=3)
    assert not be.alive  # the tear fired inside the combined flush
    be.reboot()
    assert be.get_name("t.opsn") == 0  # torn memlog tx was discarded
    fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
    ht2 = RemoteHashTable.recover(fe2, "t")
    for k, v in pairs:
        assert ht2.get(k) == v


def test_combined_flush_tear_in_oplog_never_leaves_data_ahead():
    """Tear the combined flush inside the op-log bytes: the memory logs it
    covered never landed either, so the data area is never ahead of the op
    log (the ordering invariant's other direction)."""
    be, fe, ht = _mk_ht()
    with pytest.raises(CrashError):
        with fe.batch(ht.h):
            for k in range(32):
                ht.put(k, k + 1)
            be.schedule_torn_write(10)  # first write = op-log bytes
    assert not be.alive
    be.reboot()
    # nothing claims to be applied, and whatever op-log prefix survived is a
    # clean prefix of the batch — recovery replays it without inventing data
    assert be.get_name("t.opsn") == 0
    fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
    ht2 = RemoteHashTable.recover(fe2, "t")
    vals = [ht2.get(k) for k in range(32)]
    done = [v is not None for v in vals]
    assert done == sorted(done, reverse=True)  # a prefix, no holes
    for k, v in enumerate(vals):
        if v is not None:
            assert v == k + 1


def test_crash_mid_batch_replays_from_group_commit():
    """Front-end dies after the batch's op logs were group-committed but
    before any memory-log flush: a fresh front-end replays everything."""
    be, fe, ht = _mk_ht(batch_ops=1 << 30)  # memlogs never auto-flush
    pairs = [(k, k * 3) for k in range(64)]  # == oplog_group: one group commit
    ht.put_many(pairs)
    assert ht.h.oplog_staged_ops == 0  # group-committed
    assert be.get_name("t.opsn") == 0  # no memory logs flushed yet
    # the front-end vanishes; its wbuf/cache are gone
    fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
    ht2 = RemoteHashTable.recover(fe2, "t")
    for k, v in pairs:
        assert ht2.get(k) == v


def test_atomic_contention_table_bounded():
    be = NVMBackend(capacity=1 << 22)
    fe = FrontEnd(be, FEConfig.rcb())
    for i in range(5000):
        fe.atomic_add(8, 1)  # clock advances ~2.2us+ per atomic
    # windows are 100us wide; without eviction this would hold one bucket
    # per window (~hundreds).  With eviction only the current window stays.
    assert len(be._atomic_contention) <= 2


def test_migration_reclaims_source_blocks():
    from repro.cluster import ClusterFrontEnd, NVMCluster
    from repro.cluster.rebalance import migrate_shard
    from repro.cluster.sharded import ShardedHashTable

    cluster = NVMCluster(n_blades=2, n_shards=4)
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=1 << 16))
    ht = ShardedHashTable(cfe, "kv", n_buckets=1 << 10)
    rng = random.Random(9)
    pairs = [(rng.randrange(1 << 28), i) for i in range(400)]
    ht.put_many(pairs)
    ht.drain()
    shard = 0
    src = cluster.directory.blade_of(shard)
    dst = 1 - src
    free_before = len(cluster.blades[src]._free)
    stats = migrate_shard(ht, shard, dst)
    assert stats["reclaimed_blocks"] > 0
    # allocator free list actually grew on the source blade
    assert len(cluster.blades[src]._free) - free_before >= stats["reclaimed_blocks"]
    # data still fully readable after reclaim
    expect = dict(pairs)
    vals = ht.get_many([k for k, _ in pairs])
    assert all(v == expect[k] for (k, _), v in zip(pairs, vals))
    # a rebooted source blade must not resurrect the reclaimed areas
    cluster.blades[src].crash()
    cluster.blades[src].reboot()
    assert not cluster.blades[src].has_name(f"kv.s{shard}.seq")


def test_cluster_batch_matches_serial_routing():
    from repro.cluster import ClusterFrontEnd, NVMCluster
    from repro.cluster.sharded import ShardedHashTable

    rng = random.Random(17)
    pairs = [(rng.randrange(1 << 28), i) for i in range(300)]
    keys = [k for k, _ in pairs] + [rng.randrange(1 << 28) for _ in range(30)]

    def run(batched):
        cluster = NVMCluster(n_blades=3, n_shards=6)
        cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=1 << 16))
        ht = ShardedHashTable(cfe, "kv", n_buckets=1 << 10)
        if batched:
            ht.put_many(pairs)
            vals = ht.get_many(keys)
        else:
            for k, v in pairs:
                ht.put(k, v)
            vals = [ht.get(k) for k in keys]
        ht.drain()
        return vals, cfe.clock.now

    v_serial, t_serial = run(False)
    v_batched, t_batched = run(True)
    assert v_serial == v_batched
    assert t_batched <= t_serial


def test_frontend_execute_batch():
    _, fe, ht = _mk_ht()
    fe.execute_batch(ht.h, [lambda k=k: ht.put(k, k * 2) for k in range(10)])
    assert fe.stats.combined_flushes >= 1
    assert ht.get_many(list(range(10))) == [k * 2 for k in range(10)]


def test_frontend_batch_context_single_flush():
    be, fe, ht = _mk_ht()
    h = ht.h
    w0 = fe.stats.rdma_writes
    with fe.batch(h):
        for k in range(200):  # spans several oplog groups
            ht.put(k, k)
    # the whole window flushed as ONE combined posted write
    assert fe.stats.rdma_writes == w0 + 1
    assert fe.stats.combined_flushes >= 1
    assert ht.get(150) == 150


# ===================================================================== PR 4:
# doorbell write waves, write_many combining, cross-structure batch_all
# windows, adaptive wave sizing, and crash atomicity of combined flushes.


@pytest.mark.parametrize("cls", [RemoteBST, RemoteBPTree, RemoteSkipList])
def test_tree_put_many_byte_identical_to_serial(cls):
    """The wave-batched write path changes only cost accounting and flush
    scheduling: same pairs, same config, the serial insert loop and
    put_many must leave the two blades' arenas byte-for-byte identical —
    with a small flush cadence so several materialize/flush rounds fire
    mid-run on both sides (not just at drain)."""
    rng = random.Random(21)
    pairs = sorted({rng.randrange(1 << 22): i for i in range(300)}.items())
    cfg = dict(cache_bytes=1 << 16, batch_ops=96)

    be_s = NVMBackend(capacity=1 << 24)
    fe_s = FrontEnd(be_s, FEConfig.rcb(**cfg))
    t_s = cls(fe_s, "t")
    for k, v in pairs:
        t_s.insert(k, v)
    fe_s.drain(t_s.h)

    be_b = NVMBackend(capacity=1 << 24)
    fe_b = FrontEnd(be_b, FEConfig.rcb(**cfg))
    t_b = cls(fe_b, "t")
    for i in range(0, len(pairs), 64):
        t_b.insert_many(pairs[i : i + 64])
    fe_b.drain(t_b.h)

    assert be_s.arena.snapshot() == be_b.arena.snapshot(), cls.__name__
    assert fe_b.clock.now <= fe_s.clock.now, cls.__name__


def test_write_many_combines_adjacent_writes():
    _, fe, ht = _mk_ht()
    h = ht.h
    a1 = fe.alloc(64)
    a2 = fe.alloc(64)
    a4 = fe.alloc(64)
    assert a2 == a1 + 64  # same slab, ascending carve
    t0 = fe.clock.now
    runs = fe.write_many(h, [(a1, b"a" * 64), (a2, b"b" * 64), (a4 + 64, b"c" * 64)])
    assert runs == 2  # a1+a2 combine into one WQE; the gap breaks the run
    assert fe.stats.writes_combined == 1
    assert fe.clock.now - t0 == pytest.approx(2 * fe.cost.dram_ns)
    # staged bytes identical to what the serial loop would stage
    assert h.wbuf[a1] == b"a" * 64 and h.wbuf[a2] == b"b" * 64


def test_fixed_wave_pins_the_width():
    _, fe, _ = _mk_ht(fixed_wave=7)
    assert fe.waves.width == 7
    fe.waves.observe(0, 1000)  # adaptive feedback must not move a pinned width
    assert fe.waves.width == 7


def test_adaptive_wave_width_stays_in_cost_model_band():
    _, fe, _ = _mk_ht()
    floor, ceiling = fe.waves.floor, fe.waves.ceiling
    assert floor == fe.cost.wave_floor()
    assert ceiling == fe.cost.wave_ceiling(fe.backend.link.epoch)
    for _ in range(32):  # miss-heavy waves widen ...
        fe.waves.observe(0, 100)
    assert fe.waves.width == ceiling
    for _ in range(256):  # ... hit-heavy waves narrow
        fe.waves.observe(100, 0)
    assert fe.waves.width == floor
    assert floor >= 2


def test_write_wave_posts_and_fences():
    """Inside a wave, posted-write rounds (slab refills, group commits)
    become WQE posts with one close fence instead of synchronous rounds."""
    _, fe, ht = _mk_ht()
    pairs = [(k, k) for k in range(200)]
    ht.put_many(pairs)
    fe.drain(ht.h)
    assert fe.stats.wqe_posts > 0
    assert fe.stats.write_waves >= 1
    # and the lingering wave was fenced by drain
    assert not fe._wave_linger and fe._wave_posts == 0


def test_batch_all_combines_structures_into_one_posted_write():
    be, fe, ht = _mk_ht()
    bst = RemoteBST(fe, "b")
    w0 = fe.stats.rdma_writes
    with fe.batch_all():
        for k in range(30):
            ht.put(k, k * 2)
        for k in range(30):
            bst.insert(k, k * 3)
    assert fe.stats.rdma_writes == w0 + 1  # ONE combined posted write
    assert fe.stats.combined_flushes >= 2  # both handles folded their op logs
    assert ht.get(7) == 14 and bst.find(7) == 21


def test_batch_all_arena_identical_to_serial_apply():
    def run(batched):
        be = NVMBackend(capacity=1 << 24)
        fe = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
        ht = RemoteHashTable(fe, "a", n_buckets=64)
        t = RemoteBST(fe, "b")

        def ops():
            for k in range(40):
                ht.put(k, k + 1)
            for k in range(40):
                t.insert(k, k + 2)

        if batched:
            with fe.batch_all():
                ops()
        else:
            ops()
        fe.drain(ht.h)
        fe.drain(t.h)
        return be.arena.snapshot(), fe.clock.now

    arena_s, t_s = run(False)
    arena_b, t_b = run(True)
    assert arena_s == arena_b
    assert t_b <= t_s


def test_batch_all_torn_combined_flush_is_all_or_none_per_structure():
    """Crash mid-cross-structure-batch: whatever physical write of the
    combined flush the power loss lands on, recovery must show, for EACH
    structure in the window, either all of its window ops or none — the seq
    watermark slot written after the entry bytes is the commit record, and
    8-byte slot writes are persist-atomic."""
    hit = 0
    for after_writes in range(0, 12):
        be = NVMBackend(capacity=1 << 24)
        fe = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
        ht = RemoteHashTable(fe, "a", n_buckets=64)
        t = RemoteBST(fe, "b")
        try:
            with fe.batch_all():
                for k in range(20):
                    ht.put(k, k + 1)
                for k in range(20):
                    t.insert(k, k + 2)
                be.schedule_torn_write(3, after_writes=after_writes)
        except CrashError:
            pass
        if be.alive:
            be._torn_write_at = None  # flush used fewer writes; tear unused
            continue
        hit += 1
        be.reboot()
        fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16), fe_id=1)
        ht2 = RemoteHashTable.recover(fe2, "a")
        t2 = RemoteBST.recover(fe2, "b")
        for vals, off in (([ht2.get(k) for k in range(20)], 1),
                          ([t2.find(k) for k in range(20)], 2)):
            got = [v is not None for v in vals]
            assert all(got) or not any(got), (after_writes, vals)
            for k, v in enumerate(vals):
                if v is not None:
                    assert v == k + off
    assert hit >= 6  # the sweep actually exercised tears across the flush


def test_crash_mid_wave_replays_a_clean_prefix():
    """Tear the blade during a put_many wave (at an op-log group commit):
    recovery replays exactly the groups whose watermark committed — a clean
    prefix of the batch, no holes, no partial group."""
    be = NVMBackend(capacity=1 << 24)
    fe = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16, batch_ops=1 << 30))
    ht = RemoteHashTable(fe, "t", n_buckets=128)
    pairs = [(k, k + 9) for k in range(160)]  # several op-log groups of 64
    be.schedule_torn_write(5, after_writes=3)  # dies inside the 2nd group
    with pytest.raises(CrashError):
        ht.put_many(pairs)
    be.reboot()
    fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16), fe_id=1)
    ht2 = RemoteHashTable.recover(fe2, "t")
    vals = [ht2.get(k) for k, _ in pairs]
    done = [v is not None for v in vals]
    assert done == sorted(done, reverse=True)  # a prefix, no holes
    assert done.count(True) % 64 == 0  # whole committed groups only
    for (k, v), got in zip(pairs, vals):
        if got is not None:
            assert got == v


def test_link_epoch_buckets_are_pruned():
    from repro.core.sim import CostModel, Link

    link = Link(CostModel())
    for i in range(10_000):  # one fresh epoch per transfer
        link.transfer(i * link.epoch, 100)
    assert len(link.bytes_in_epoch) <= Link.HORIZON_EPOCHS + 1
    assert len(link.msgs_in_epoch) <= Link.HORIZON_EPOCHS + 1
    assert 0.0 <= link.utilization(9_999 * link.epoch) <= 1.0


def test_cluster_blade_sub_batch_is_one_combined_write():
    from repro.cluster import ClusterFrontEnd, NVMCluster
    from repro.cluster.sharded import ShardedHashTable

    cluster = NVMCluster(n_blades=2, n_shards=4)
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=1 << 16))
    ht = ShardedHashTable(cfe, "kv", n_buckets=1 << 8)
    rng = random.Random(23)
    pairs = [(rng.randrange(1 << 26), i) for i in range(200)]
    ht.put_many(pairs)  # ~50 ops per shard: below the group size, so every
    # blade's sub-batch drains only through its batch_all() combined flush
    stats = cfe.aggregate_stats()
    assert 0 < stats["rdma_writes"] <= len(cluster.blades)
    assert stats["combined_flushes"] >= 2  # several shard handles per write
    expect = dict(pairs)
    vals = ht.get_many([k for k, _ in pairs])
    assert all(v == expect[k] for (k, _), v in zip(pairs, vals))


def test_serial_op_fences_a_lingering_wave():
    """A lingering vector-op wave must not leak its batch cost accounting
    into later serial ops: the first serial op_begin fences it, and serial
    ops charge the full per-op CPU cost again."""
    _, fe, ht = _mk_ht()
    ht.put_many([(k, k) for k in range(100)])
    assert fe._wave_linger  # controller kept the wave open past the call
    busy0 = fe.busy_ns
    ht.put(1000, 1)  # serial op: fences the wave, pays serial costs
    assert not fe._wave_linger and fe._wave_posts == 0
    assert fe.busy_ns - busy0 >= fe.cost.cpu_op_ns


def test_cluster_execute_batch_combined_window():
    """ClusterFrontEnd.execute_batch(combined=True) — the default — wraps
    each blade sub-batch in that front-end's batch_all() window: ops over
    several handles on one blade drain in one combined posted write, and
    the results match per-op routing."""
    from repro.cluster import ClusterFrontEnd, NVMCluster
    from repro.core.structures import RemoteBST, RemoteHashTable

    cluster = NVMCluster(n_blades=2, n_shards=4)
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=1 << 16))
    objs = {}

    def setup(fe):
        objs[fe.backend.blade_id] = (
            RemoteHashTable(fe, f"h{fe.backend.blade_id}", n_buckets=64),
            RemoteBST(fe, f"b{fe.backend.blade_id}"),
        )

    for bid in cluster.blades:
        cfe.run_on(bid, setup)
    w0 = {bid: cfe.fe_for_blade(bid).stats.rdma_writes for bid in cluster.blades}

    def work(fe):
        ht, bst = objs[fe.backend.blade_id]
        for k in range(25):
            ht.put(k, k * 2)
            bst.insert(k, k * 3)

    cfe.execute_batch({bid: work for bid in cluster.blades})  # combined=True
    for bid in cluster.blades:
        fe = cfe.fe_for_blade(bid)
        assert fe.stats.rdma_writes == w0[bid] + 1  # one combined write/blade
        ht, bst = objs[bid]
        assert ht.get(7) == 14 and bst.find(7) == 21
