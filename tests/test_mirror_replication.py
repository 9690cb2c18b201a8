"""Mirror replication path of core/backend.py (paper §4.3).

The cluster failover (repro.cluster.failover) leans entirely on the
invariant that a blade's mirror arena is a byte-exact replacement for the
primary at every commit point, and that a torn (partial) write never reaches
the mirror — so promotion + reboot recovers exactly the committed prefix.
"""

import random

import pytest

from repro.core import CrashError, FEConfig, FrontEnd, NVMBackend
from repro.core.structures import RemoteBST, RemoteHashTable


def test_mirror_arena_byte_exact_after_clean_workload():
    be = NVMBackend(capacity=1 << 24, num_mirrors=2)
    fe = FrontEnd(be, FEConfig.rcb(batch_ops=64, oplog_group=16))
    ht = RemoteHashTable(fe, "h", n_buckets=256)
    rng = random.Random(11)
    for _ in range(500):
        k = rng.randrange(200)
        if rng.random() < 0.8:
            ht.put(k, rng.randrange(1 << 30))
        else:
            ht.delete(k)
    fe.drain(ht.h)
    for m in be.mirrors:
        assert m.arena.snapshot() == be.arena.snapshot()
        assert m.bytes_replicated > 0


def test_torn_write_never_reaches_the_mirror():
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig.rcb(batch_ops=1024, oplog_group=1024))
    ht = RemoteHashTable(fe, "h", n_buckets=128)
    for k in range(120):
        ht.put(k, k * 7)
    fe.drain(ht.h)
    assert be.mirrors[0].arena.snapshot() == be.arena.snapshot()

    # stage ops client-side (large groups: no log flushes; only slab-alloc
    # RPCs reach the blade), then let the flush tear mid-write
    for k in range(120, 140):
        ht.put(k, 1)
    snapshot = be.arena.snapshot()
    assert be.mirrors[0].arena.snapshot() == snapshot
    be.schedule_torn_write(17)
    with pytest.raises(CrashError):
        fe.drain(ht.h)
        fe.drain(ht.h)  # second drain hits the dead blade if first "worked"
    # the partial write mutated the primary ...
    assert be.arena.snapshot() != snapshot
    # ... but the mirror still matches the last commit point byte for byte
    assert be.mirrors[0].arena.snapshot() == snapshot


def test_promotion_equals_reboot_after_torn_write_crash():
    """Recovering from the mirror and recovering the primary in place must
    yield the same committed structure state (arena-level equivalence of the
    two recovery paths)."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig.rcb(batch_ops=32, oplog_group=8))
    t = RemoteBST(fe, "t")
    ks = random.Random(5).sample(range(100000), 300)
    for k in ks:
        t.insert(k, k)
    fe.drain(t.h)
    for k in range(100000, 100040):
        t.insert(k, k)
    be.schedule_torn_write(9)
    with pytest.raises(CrashError):
        fe.drain(t.h)
        fe.drain(t.h)

    # promotion snapshot must be taken before the primary reboots (reboot
    # replays logs and would re-replicate into the mirror)
    promoted = be.promote_mirror(0)
    be.reboot()

    fe_p = FrontEnd(promoted, FEConfig.rcb(), fe_id=1)
    fe_r = FrontEnd(be, FEConfig.rcb(), fe_id=2)
    t_p = RemoteBST.recover(fe_p, "t")
    t_r = RemoteBST.recover(fe_r, "t")
    items_p, items_r = t_p.items(), t_r.items()
    assert items_p == items_r
    # all committed (drained) inserts survived on both paths
    got = dict(items_p)
    assert all(got.get(k) == k for k in ks)


def test_promoted_blade_reseeds_its_own_mirrors():
    from repro.cluster import NVMCluster, ClusterFrontEnd, ShardedHashTable

    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 25, num_mirrors=1)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(), fe_id=0)
    ht = ShardedHashTable(cfe, "ht")
    for k in range(200):
        ht.put(k, k)
    ht.drain()
    cluster.blades[1].fail_permanently()
    for k in range(200, 300):
        ht.put(k, k)
    ht.drain()
    assert cluster.failovers == 1
    # the promoted blade can itself fail permanently and recover again
    cluster.blades[1].fail_permanently()
    for k in range(300, 400):
        ht.put(k, k)
    ht.drain()
    assert cluster.failovers == 2
    assert sorted(ht.items()) == [(k, k) for k in range(400)]
