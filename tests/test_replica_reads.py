"""Replica-aware read path: mirror read routing, bounded staleness,
directory leases, and revoke-before-swap (PR 5).

The contract under test:

  * mirror endpoints serve byte-identical data when replication is
    synchronous (the default), and never data older than the advertised
    staleness bound when it lags;
  * read-your-writes survives replica routing: keys a front-end wrote are
    pinned to the primary until the mirrors' applied watermark provably
    covers the write;
  * a front-end holding a directory lease validates locally — and every
    reconfiguration revokes outstanding leases BEFORE swapping the mapping,
    so no lease holder ever reads a tombstoned source.
"""

import random

import pytest

from repro.cluster import (
    ClusterFrontEnd,
    LeaseTable,
    NVMCluster,
    ReadPolicy,
    ShardedHashTable,
    migrate_shard,
    rebalance,
)
from repro.core import CrashError, FEConfig, FrontEnd, NVMBackend
from repro.core.structures import RemoteHashTable

try:
    from hypothesis import given, settings, strategies as st
except Exception:  # pragma: no cover - container without hypothesis
    from _hypothesis_shim import given, settings, strategies as st


def _mk_cluster(n_blades=2, n_shards=8, **kw):
    return NVMCluster(n_blades=n_blades, n_shards=n_shards,
                      capacity_per_blade=1 << 25, **kw)


# ------------------------------------------------------------- byte identity
def test_mirror_reads_byte_identical_to_primary():
    """With synchronous replication (default), a replica-routed read
    returns exactly the primary's bytes — for every byte of the arena."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=2)
    fe = FrontEnd(be, FEConfig.rcb(cache_bytes=4096))
    ht = RemoteHashTable(fe, "h", n_buckets=256)
    rng = random.Random(3)
    model = {}
    for _ in range(600):
        k = rng.randrange(250)
        if rng.random() < 0.75:
            v = rng.randrange(1 << 30)
            ht.put(k, v)
            model[k] = v
        else:
            ht.delete(k)
            model.pop(k, None)
    fe.drain(ht.h)
    for idx in range(2):
        assert be.mirrors[idx].arena.snapshot() == be.arena.snapshot()
    # replica-routed reads return the same values the primary serves
    with fe.replica_reads(ReadPolicy(mode="mirror", max_staleness_ops=0)):
        got = ht.get_many(sorted(model))
    assert got == [model[k] for k in sorted(model)]
    assert fe.stats.replica_reads > 0
    assert fe.stats.replica_fallbacks == 0


def test_promoted_blade_mirrors_serve_replica_reads():
    """promote_mirror must re-seed the fresh blade's own mirror set: a
    fresh empty mirror receiving only post-promotion deltas would advertise
    lag 0 (its seq-slot copy updates) while holding none of the data."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig(use_oplog=True, use_cache=False, use_batch=False))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    for k in range(50):
        ht.put(k, k * 2)
    fe.drain(ht.h)
    promoted = be.promote_mirror(0)
    assert promoted.mirrors[0].arena.snapshot() == promoted.arena.snapshot()
    fe2 = FrontEnd(promoted, FEConfig(use_oplog=True, use_cache=False,
                                      use_batch=False), fe_id=1)
    ht2 = RemoteHashTable.recover(fe2, "h")
    ht2.put(99, 7)
    fe2.drain(ht2.h)
    with fe2.replica_reads(ReadPolicy(mode="mirror", max_staleness_ops=0)):
        got = [ht2.get(k) for k in range(50)] + [ht2.get(99)]
    assert got == [k * 2 for k in range(50)] + [7]
    assert fe2.stats.replica_reads > 0


def test_lagging_replica_bytes_never_enter_the_cache():
    """Bytes fetched from a lagging mirror must not pollute the front-end
    page cache: the cache outlives the policy scope, and a later
    primary-routed read hitting them would extend staleness past the
    contract."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig.rc())  # cache on, serial reads
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    for k in range(30):
        ht.put(k, k)
    fe.drain(ht.h)
    be.mirrors[0].lag_writes = 1 << 30  # freeze replication
    for k in range(30):
        ht.put(k, k + 1000)  # stale values now live only on the mirror
    fe.drain(ht.h)
    fe.cache.clear()  # drop write-through entries: force remote reads
    with fe.replica_reads(ReadPolicy(mode="mirror", max_staleness_ops=1 << 40)):
        stale = [ht.get(k) for k in range(30)]
    assert stale == list(range(30))  # bounded-stale values, as contracted
    # out of policy scope, primary reads must see the fresh values — a
    # cached stale byte would leak them here
    assert [ht.get(k) for k in range(30)] == [k + 1000 for k in range(30)]


def test_replica_read_does_not_require_live_primary():
    """A mirror is its own physical blade: replica reads keep working after
    the primary crashes (the read-side availability win)."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig(use_oplog=True, use_cache=False, use_batch=False))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    for k in range(50):
        ht.put(k, k * 2)
    fe.drain(ht.h)
    be.crash()
    with fe.replica_reads(ReadPolicy(mode="mirror", max_staleness_ops=0)):
        assert ht.get(7) == 14
    with pytest.raises(CrashError):
        ht.get(7)  # primary routing still faults


# --------------------------------------------------------- bounded staleness
def _unique_value_workload(lag_writes: int, bound: int, ops: int, seed: int):
    """Interleave writes (globally unique values) with replica-routed point
    reads against a mirror lagging `lag_writes` physical writes; check every
    replica-served value against the per-key version history."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    be.mirrors[0].lag_writes = lag_writes
    # serial config, per-op flush: the applied watermark advances op by op,
    # so the bound check is exercised at its finest granularity
    fe = FrontEnd(be, FEConfig(use_oplog=True, use_cache=False, use_batch=False,
                               oplog_pipeline=1))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    policy = ReadPolicy(mode="mirror", max_staleness_ops=bound)
    rng = random.Random(seed)
    history = {}        # key -> list of (write seq, value)
    value_seq = {}      # unique value -> seq of the write that produced it
    next_value = 1
    violations = []
    for _ in range(ops):
        k = rng.randrange(16)
        if rng.random() < 0.6 or k not in history:
            ht.put(k, next_value)
            history.setdefault(k, []).append((ht.h.seq, next_value))
            value_seq[next_value] = ht.h.seq
            next_value += 1
            continue
        committed = ht.h.seq
        applied = be.replica_applied_seq("h")
        before = fe.stats.replica_fallbacks
        with fe.replica_reads(policy):
            got = ht.get(k)
        served_by_replica = fe.stats.replica_fallbacks == before
        if served_by_replica:
            # THE contract: a replica never serves past the bound
            if committed - applied > bound:
                violations.append(("bound", k, committed, applied))
                continue
            # value-level consistency: the mirror cut fully reflects ops
            # <= applied - 1 and nothing past op `applied`, so the served
            # value must lie between k's last write at or below applied-1
            # (the freshness floor) and its last write at or below applied
            floor = [s for s, _ in history[k] if s <= applied - 1]
            if got is None:
                ok = not floor
            else:
                ok = (got in value_seq
                      and value_seq[got] <= applied
                      and (not floor or value_seq[got] >= max(floor)))
            if not ok:
                violations.append(("value", k, got, committed, applied))
        else:
            # primary fallback serves the freshest committed value
            if got != history[k][-1][1]:
                violations.append(("primary", k, got, committed))
    return violations, fe


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=0, max_value=40),
       st.integers(min_value=0, max_value=30),
       st.integers(min_value=0, max_value=999))
def test_replica_reads_never_exceed_staleness_bound(lag, bound, seed):
    violations, _ = _unique_value_workload(lag, bound, ops=120, seed=seed)
    assert not violations, violations


def test_over_lag_mirror_falls_back_to_primary():
    """A mirror further behind than the bound never serves: every read falls
    back to the primary and returns the freshest value."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    be.mirrors[0].lag_writes = 10_000  # never catches up mid-run
    fe = FrontEnd(be, FEConfig(use_oplog=True, use_cache=False, use_batch=False,
                               oplog_pipeline=1))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    policy = ReadPolicy(mode="mirror", max_staleness_ops=3)
    for k in range(40):
        ht.put(k, k + 100)
    with fe.replica_reads(policy):
        got = [ht.get(k) for k in range(40)]
    assert got == [k + 100 for k in range(40)]
    assert fe.stats.replica_reads == 0
    assert fe.stats.replica_fallbacks > 0


# ----------------------------------------------------- read-your-writes pins
def test_read_your_writes_under_lease_with_lagging_mirrors():
    """Keys written by this front-end read back their own writes through the
    replica policy even when every mirror lags arbitrarily: pins hold them
    on the primary until the mirror watermark provably covers the write."""
    cluster = _mk_cluster(n_blades=2, num_mirrors=1)
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.lag_writes = 1 << 30  # mirrors effectively frozen
    policy = ReadPolicy(mode="auto", max_staleness_ops=1 << 40)  # no bound
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=4096), fe_id=0)
    ht = ShardedHashTable(cfe, "ht", read_policy=policy)
    rng = random.Random(9)
    model = {}
    for round_ in range(6):
        pairs = [(rng.randrange(1 << 16), round_ * 1000 + j) for j in range(80)]
        ht.put_many(pairs)
        for k, v in pairs:
            model[k] = v
        keys = [k for k, _ in pairs]
        assert ht.get_many(keys) == [model[k] for k in keys]  # immediate RYW
        assert ht.get(keys[0]) == model[keys[0]]
    # the frozen mirrors must never have served these keys
    assert all(k in ht._pinned for k in model)
    # once the mirrors catch up, pins release and replicas serve
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.lag_writes = 0
            m.sync()
    ht.drain()
    keys = sorted(model)
    assert ht.get_many(keys) == [model[k] for k in keys]
    stats = cfe.aggregate_stats()
    assert stats["replica_reads"] > 0
    assert not ht._pinned  # every pin released by the watermark


def test_read_your_writes_survives_migration_with_lagging_dst_mirror():
    """Pin seqs are recorded against the source shard's op stream; after a
    migration the destination renumbers every op, so pins must be rebased
    at rebind — comparing a source seq to the destination watermark would
    wrongly release pins and serve this front-end's own writes from a
    lagging destination mirror."""
    cluster = _mk_cluster(n_blades=2, n_shards=8, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=1 << 40)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    ht = ShardedHashTable(cfe, "ht", read_policy=policy)
    model = {}
    for k in range(600):
        ht.put(k, k + 50)
        model[k] = k + 50
    ht.drain()

    shard = 0
    dst = cluster.add_blade()
    # the destination blade's mirror never applies anything
    for m in cluster.blades[dst].mirrors:
        m.lag_writes = 1 << 30
    migrate_shard(ht, shard, dst)
    # every write this front-end made must still read back, pinned to the
    # destination primary (its mirror holds nothing)
    assert [ht.get(k) for k in sorted(model)] == [model[k] for k in sorted(model)]
    keys = sorted(model)
    assert ht.get_many(keys) == [model[k] for k in keys]


def test_no_mirror_cluster_records_no_pins():
    """Pins exist to keep replica reads correct; a cluster with no mirrors
    can never serve a replica read, so writes must not accumulate pin
    state."""
    cluster = _mk_cluster(n_blades=2, num_mirrors=0)
    policy = ReadPolicy(mode="auto", max_staleness_ops=64)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    ht = ShardedHashTable(cfe, "ht", read_policy=policy)
    for k in range(500):
        ht.put(k, k)
    ht.put_many([(k, k) for k in range(500, 700)])
    assert not ht._pinned
    assert ht.get_many(list(range(700))) == list(range(700))


# ------------------------------------------------------------------- leases
def test_lease_validates_locally_and_renews_on_expiry():
    cluster = _mk_cluster(n_blades=2, lease_ttl_ns=50_000.0)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    ht = ShardedHashTable(cfe, "ht")
    for k in range(120):
        ht.put(k, k)
    ht.drain()
    assert cfe.lease_validations > 0
    # epoch never moved, yet the tiny TTL forced periodic renewal fetches
    assert cluster.directory.epoch == 0
    assert cfe.directory_fetches > 1
    # a roomy TTL pays exactly one fetch for the same workload
    cluster2 = _mk_cluster(n_blades=2, lease_ttl_ns=1e12)
    cfe2 = ClusterFrontEnd(cluster2, FEConfig.rc(), fe_id=0)
    ht2 = ShardedHashTable(cfe2, "ht")
    for k in range(120):
        ht2.put(k, k)
    ht2.drain()
    assert cfe2.directory_fetches == 1
    assert cfe2.lease_validations > 100


def test_lease_table_roundtrip_and_bootstrap():
    t = LeaseTable()
    t.grant(0, 3, 1000.0, 500.0)
    t.grant(7, 3, 2000.0, 500.0)
    raw = t.encode()
    t2 = LeaseTable.decode(raw)
    assert t2 is not None and t2.leases == t.leases
    broken = bytearray(raw)
    broken[5] ^= 0x10
    assert LeaseTable.decode(bytes(broken)) is None
    # persisted on every live blade; bootstrap recovers from any survivor
    cluster = _mk_cluster(n_blades=3)
    t.persist(cluster.blades)
    cluster.blades[0].crash()
    got = LeaseTable.bootstrap(cluster.blades)
    assert got.leases == t.leases


def test_migration_revokes_lease_before_swap():
    """A second front-end validating locally under its lease must fault and
    refresh after a migration — never read the tombstoned (and reclaimed)
    source copy."""
    cluster = _mk_cluster(n_blades=2, n_shards=8)
    cfe_a = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    cfe_b = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=1)
    ht_a = ShardedHashTable(cfe_a, "ht")
    ht_b = ShardedHashTable(cfe_b, "ht")
    model = {}
    for k in range(300):
        ht_a.put(k, k * 3)
        model[k] = k * 3
    ht_a.drain()
    # B reads through its own lease and binds the source blade
    assert all(ht_b.get(k) == model[k] for k in range(0, 300, 17))
    assert cluster.leases.valid(cfe_b.fe_id, cfe_b.epoch, cfe_b.clock.now)

    shard = 3
    dst = cluster.add_blade()
    epoch_before = cfe_b.epoch
    migrate_shard(ht_a, shard, dst)
    # the swap revoked B's lease BEFORE flipping the assignment
    assert not cluster.leases.valid(cfe_b.fe_id, cfe_b.epoch, cfe_b.clock.now)
    fetches_before = cfe_b.directory_fetches
    # B's next ops must re-fetch, rebind, and route to the destination —
    # the source copy is destroyed, so stale routing would misread
    assert all(ht_b.get(k) == v for k, v in model.items())
    assert cfe_b.epoch > epoch_before
    assert cfe_b.epoch == cluster.directory.epoch
    assert cfe_b.directory.blade_of(shard) == dst
    assert cfe_b.directory_fetches > fetches_before


def test_failover_revokes_lease_before_promotion_swap():
    """Mirror promotion revokes every lease before swapping the fresh blade
    in: a stale front-end transparently refreshes, and replica-routed reads
    keep returning every committed value."""
    cluster = _mk_cluster(n_blades=2, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=256)
    cfe_a = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    cfe_b = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=1)
    ht_a = ShardedHashTable(cfe_a, "ht")
    ht_b = ShardedHashTable(cfe_b, "ht", read_policy=policy)
    model = {}
    for k in range(240):
        ht_a.put(k, k + 5)
        model[k] = k + 5
    ht_a.drain()
    assert ht_b.get(11) == 16  # B holds a lease now

    cluster.blades[1].fail_permanently()
    # A notices first and performs the promotion (epoch bump + revocation)
    for k in range(240, 320):
        ht_a.put(k, k + 5)
        model[k] = k + 5
    ht_a.drain()
    assert cluster.failovers == 1
    assert not cluster.leases.valid(cfe_b.fe_id, cfe_b.epoch, cfe_b.clock.now)
    # B refreshes on its next op and reads everything, replicas included
    keys = sorted(model)
    assert ht_b.get_many(keys) == [model[k] for k in keys]
    assert cfe_b.epoch == cluster.directory.epoch
    assert cluster.failovers == 1  # no duplicate promotion


# --------------------------------------------------------- weighted rebalance
def test_rebalance_weighs_per_shard_op_counts():
    """Two hot shards must not stay colocated after scale-out: the weighted
    rebalancer evens *load*, not raw shard counts."""
    cluster = _mk_cluster(n_blades=2, n_shards=8)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    ht = ShardedHashTable(cfe, "ht")
    model = {}
    keyspace = list(range(4000))
    for k in keyspace[:400]:
        ht.put(k, k)
        model[k] = k
    ht.drain()
    directory = cluster.directory
    # hammer the two shards of one blade hottest
    hot_blade = 0
    hot_shards = directory.shards_on(hot_blade)[:2]
    hot_keys = [k for k in keyspace if directory.shard_of(k) in hot_shards][:40]
    for _ in range(20):
        for k in hot_keys:
            if k in model:
                assert ht.get(k) == model[k]
            else:
                ht.put(k, k)
                model[k] = k
    w_hot = [directory.shard_weight(s) for s in hot_shards]
    assert min(w_hot) > 3 * max(
        directory.shard_weight(s) for s in range(8) if s not in hot_shards
    )
    cluster.add_blade()
    moves = rebalance(ht)
    assert moves, "scale-out must migrate shards"
    # terminal guarantee of the greedy: no remaining move strictly improves
    weights = {b: w for b, w in directory.load_weights().items()}
    hi = max(weights, key=lambda b: (weights[b], b))
    lo = min(weights, key=lambda b: (weights[b], b))
    gap = weights[hi] - weights[lo]
    assert all(directory.shard_weight(s) >= gap for s in directory.shards_on(hi))
    # the two hot shards ended up on different blades
    assert len({directory.blade_of(s) for s in hot_shards}) == 2
    # and nothing was lost on the way
    assert sorted(ht.items()) == sorted(model.items())


# ------------------------------------------------------- naive doorbell waves
def test_naive_multi_location_op_posts_one_write_wave():
    """The naive variant's per-location posted writes share one doorbell:
    one wave per op, every location a WQE, completion fenced once."""
    be = NVMBackend(capacity=1 << 24)
    fe = FrontEnd(be, FEConfig.naive())
    ht = RemoteHashTable(fe, "h", n_buckets=32)
    for k in range(60):
        ht.put(k, k)  # most ops touch >= 2 locations (node + bucket head)
    assert fe.stats.write_waves == 60
    assert fe.stats.wqe_posts == fe.stats.rdma_writes
    assert fe.stats.wqe_posts > fe.stats.write_waves  # real batching happened


# ------------------------------------------------------- mirror-routed scans
def test_items_scan_routes_to_mirrors_under_policy():
    """A whole-structure scan fans out its leaf reads to mirror endpoints
    under the read policy — the scan's read wave hits replica arenas, not
    the primary — and still returns exactly the written contents."""
    cluster = _mk_cluster(n_blades=2, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=1 << 40)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    ht = ShardedHashTable(cfe, "ht", read_policy=policy)
    model = {k: k * 3 + 1 for k in range(400)}
    ht.put_many(sorted(model.items()))
    ht.drain()  # synchronous mirrors: watermarks cover every write
    before = cfe.aggregate_stats()["replica_reads"]
    assert sorted(ht.items()) == sorted(model.items())
    assert cfe.aggregate_stats()["replica_reads"] > before
    assert not ht._pinned  # the scan released every covered pin


def test_scan_with_fresh_pins_stays_on_primary():
    """A scan touches every key, so one unreleased pin (a local write not
    yet provably applied on any mirror) keeps that shard's whole scan on
    the primary — no replica read may serve a scan that could miss this
    front-end's own writes."""
    cluster = _mk_cluster(n_blades=2, num_mirrors=1)
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.lag_writes = 1 << 30  # mirrors frozen: pins never release
    policy = ReadPolicy(mode="auto", max_staleness_ops=1 << 40)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    ht = ShardedHashTable(cfe, "ht", read_policy=policy)
    model = {k: k + 7 for k in range(300)}
    ht.put_many(sorted(model.items()))
    assert sorted(ht.items()) == sorted(model.items())  # RYW via primary
    assert cfe.aggregate_stats()["replica_reads"] == 0
    # once mirrors catch up, the same scan is free to leave the primary
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.lag_writes = 0
            m.sync()
    ht.drain()
    assert sorted(ht.items()) == sorted(model.items())
    assert cfe.aggregate_stats()["replica_reads"] > 0


def test_range_scan_routes_to_mirrors_under_policy():
    """range_scan's per-shard leaf-chain walks route through the same
    mirror read waves and merge to a globally sorted, correct result."""
    from repro.cluster import ShardedBPTree

    cluster = _mk_cluster(n_blades=2, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=1 << 40)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    bt = ShardedBPTree(cfe, "bt", read_policy=policy)
    model = {k: k * 5 for k in range(0, 900, 3)}
    for k, v in model.items():
        bt.insert(k, v)
    bt.drain()
    before = cfe.aggregate_stats()["replica_reads"]
    want = sorted((k, v) for k, v in model.items() if 100 <= k <= 700)
    assert bt.range_scan(100, 700) == want
    assert cfe.aggregate_stats()["replica_reads"] > before
    assert bt.items() == sorted(model.items())
