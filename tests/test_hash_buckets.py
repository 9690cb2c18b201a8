"""Bucket layout of the hash table under the shard router, the device reads
it costs, and the memory-log order of the flushes that load it."""

import struct

import numpy as np
import pytest

from repro.cluster import ClusterFrontEnd, NVMCluster, ShardedHashTable
from repro.core import FEConfig
from repro.core.frontend import group_by_width
from repro.core.oplog import decode_txs_columnar
from repro.core.structures import RemoteHashTable
from repro.obs import profile as prof

N_KEYS = 1 << 14          # load factor 1: as many buckets as keys
N_SHARDS = 16


@pytest.fixture
def spans_on():
    prof.reset()
    prof.enable()
    try:
        yield
    finally:
        prof.disable()
        prof.reset()


def _counts():
    return {k: v["count"] for k, v in prof.snapshot().items() if "count" in v}


def _table(n_keys=N_KEYS):
    """A 16-shard table over 4 blades with one mirror each, caches holding
    about a tenth of the table, as the benchmark's hash configuration has
    them; its keys and the value each holds."""
    cluster = NVMCluster(n_blades=4, n_shards=N_SHARDS, num_mirrors=1,
                         capacity_per_blade=1 << 23)
    cfe = ClusterFrontEnd(cluster, FEConfig.rcb(cache_bytes=16384), fe_id=0)
    table = ShardedHashTable(cfe, "t", n_buckets=n_keys)
    keys = np.random.default_rng(5).integers(0, 1 << 62, n_keys, dtype=np.int64).tolist()
    return table, keys


def _loaded():
    table, keys = _table()
    table.put_many([(k, k ^ 7) for k in keys])
    table.drain()
    return table, keys


def test_every_shard_spreads_over_its_buckets():
    table, _ = _loaded()
    shards = table.shard_objects()
    assert len(shards) == N_SHARDS
    for s, t in shards.items():
        assert t.n_buckets == N_KEYS // N_SHARDS
        raw = t.fe.backend.arena.snapshot(t.base, t.base + t.n_buckets * 8)
        occupied = np.mean(np.frombuffer(raw, dtype="<u8") != 0)
        # 1 - 1/e of the buckets at load factor 1; 1/16 if the bucket index
        # shared the router's bits
        assert occupied >= 0.55, (s, occupied)


@pytest.mark.parametrize("n_buckets", [1, 1000, 1 << 15, (1 << 32) - 1])
def test_bucket_addr_scalar_equals_vector(n_buckets):
    t = RemoteHashTable.__new__(RemoteHashTable)
    t.base, t.n_buckets = 4096, n_buckets
    rng = np.random.default_rng(n_buckets)
    keys = (rng.integers(0, 1 << 63, 2000, dtype=np.uint64).astype(object)
            * rng.integers(1, 3, 2000).astype(object)).tolist()
    keys += [0, 1, (1 << 63) - 1, 1 << 63, (1 << 64) - 1, -1, -(1 << 63)]
    assert any(k >= 1 << 63 for k in keys) and any(k < 0 for k in keys)
    scalar = [t._bucket_addr(k) for k in keys]
    assert scalar == t._bucket_addrs(keys)
    assert all(4096 <= a < 4096 + 8 * n_buckets and a % 8 == 0 for a in scalar)


def test_get_many_walks_short_chains(spans_on):
    table, keys = _loaded()
    rng = np.random.default_rng(11)
    for _ in range(2):
        q = [keys[i] for i in rng.integers(0, len(keys), 512)]
        prof.reset()
        assert table.get_many(q) == [k ^ 7 for k in q]
        c = _counts()
        # 16 shard probes and a few chain levels a shard; about 330 when each
        # shard's keys shared one bucket in 16
        assert c["arena.reads"] <= 160, c
        assert c["hash.lookups"] == 512
        assert c["hash.chain_nodes"] / c["hash.lookups"] < 2, c


def test_bulk_put_many_fetches_nothing_it_staged(spans_on, monkeypatch):
    table, keys = _table()
    in_apply = []
    apply = RemoteHashTable._apply_puts

    def counted(self, *a):
        before = _counts().get("arena.reads", 0)
        apply(self, *a)
        in_apply.append(_counts().get("arena.reads", 0) - before)

    monkeypatch.setattr(RemoteHashTable, "_apply_puts", counted)
    table.put_many([(k, k ^ 7) for k in keys])
    c = _counts()
    # every head and node the apply pass reads was staged by the batch's
    # read waves; a small cache evicts most of them before the pass, which
    # then admits the staged bytes instead of reading them back
    assert len(in_apply) == N_SHARDS and sum(in_apply) == 0, in_apply
    assert c.get("reads.serial", 0) == 0 and c["reads.wave"] == N_SHARDS, c
    table.drain()
    assert table.get_many(keys) == [k ^ 7 for k in keys]


def test_flush_groups_memory_log_by_width(monkeypatch):
    table, keys = _table(1 << 10)
    shard = table.cfe.directory.shard_of(keys[0])
    mine = [k for k in keys if table.cfe.directory.shard_of(k) == shard]
    table.put_many([(k, 1) for k in mine[:1]])
    table.drain()
    t = table.shard_objects()[shard]
    fe, h, be = t.fe, t.h, t.fe.backend
    payloads = []
    orig = be.tx_append

    def spy(area, payload, *a, **kw):
        if area is h.txlog_area:
            payloads.append(payload)
        return orig(area, payload, *a, **kw)

    monkeypatch.setattr(be, "tx_append", spy)
    with fe.batch(h):
        for k in mine[1:40]:
            t.put(k, 2)
        staged = dict(h.wbuf)
    assert len(payloads) == 1
    widths = [len(d) for d in staged.values()]
    assert set(widths) == {8, 24}
    assert widths != sorted(widths)          # staged interleaved
    addrs, offs, lens, n_txs, _ = decode_txs_columnar(payloads[0])
    assert n_txs == 1
    pairs = list(zip(addrs.tolist(), lens.tolist()))
    opsn = be.name_slot_addr(h.opsn_name)
    assert pairs[-1] == (opsn, 8)
    assert sorted(pairs[:-1]) == sorted((a, len(d)) for a, d in staged.items())
    assert lens[:-1].tolist() == sorted(lens[:-1].tolist())   # one run a width
    assert table.get_many(mine[1:40]) == [2] * 39


def test_group_by_width_keeps_order_where_ranges_overlap():
    disjoint = {0: bytes(24), 100: bytes(8), 200: bytes(24), 300: bytes(8)}
    assert [a for a, _ in group_by_width(disjoint)] == [100, 300, 0, 200]
    overlapping = {0: bytes(24), 8: struct.pack("<Q", 5), 200: bytes(24)}
    assert [a for a, _ in group_by_width(overlapping)] == [0, 8, 200]
    assert group_by_width({}) == [] and group_by_width({4: b"x"}) == [(4, b"x")]
