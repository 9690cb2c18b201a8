"""Byte-identity property tests for vectorized wave execution (PR 7).

The tentpole contract: the array-native apply path (numpy columnar staging
in the structures, batched Fletcher decode in ``decode_txs_columnar`` /
``fletcher64_segments``) changes ONLY wall-clock cost — never a byte of the
arena, never a returned value, never what recovery reconstructs.  Random
workloads (hypothesis, shimmed when absent) pin each structure's batched
path against the serial loop, and torn combined flushes must replay through
the batched decoder to the same all-or-none per-op outcome.
"""

import random

from repro.core import FEConfig, FrontEnd, NVMBackend
from repro.core.backend import CrashError
from repro.core.oplog import (
    decode_txs,
    decode_txs_columnar,
    encode_tx,
    fletcher64,
    fletcher64_segments,
    MemLog,
)
from repro.core.structures import (
    RemoteBPTree,
    RemoteBST,
    RemoteHashTable,
    RemoteSkipList,
)

try:
    from hypothesis import given, settings, strategies as st
except Exception:  # pragma: no cover - container without hypothesis
    from _hypothesis_shim import given, settings, strategies as st

STRUCTS = [RemoteHashTable, RemoteBST, RemoteBPTree, RemoteSkipList]


def _mk(cls, **cfg):
    be = NVMBackend(capacity=1 << 24)
    fe = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16, **cfg))
    if cls is RemoteHashTable:
        return be, fe, cls(fe, "t", n_buckets=128)
    return be, fe, cls(fe, "t")


def _put(obj, k, v):
    (obj.put if isinstance(obj, RemoteHashTable) else obj.insert)(k, v)


def _get(obj, k):
    return (obj.get if isinstance(obj, RemoteHashTable) else obj.find)(k)


raw_kvs = st.lists(
    st.tuples(st.integers(min_value=0, max_value=1 << 22),
              st.integers(min_value=-(1 << 30), max_value=1 << 30)),
    min_size=1, max_size=150,
)


def _uniq(pairs):
    """Unique keys: with duplicates, put_many's key-sort legitimately
    reorders same-key updates (last-wins by sorted order, not arrival
    order) — a semantic difference, not a vectorization bug."""
    return sorted(dict(pairs).items())


@settings(max_examples=8, deadline=None)
@given(raw_kvs)
def test_vectorized_apply_byte_identical_to_serial(pairs):
    """Same pairs, same config: the per-op serial loop and the vectorized
    put_many leave the two blades' arenas byte-for-byte identical, for any
    random workload — the numpy staging only changes when CPU time is
    spent, never what lands in NVM.  (Structures loop inside the body: the
    hypothesis shim's @given wrapper is zero-arg, so it cannot compose with
    pytest.mark.parametrize.)"""
    pairs = _uniq(pairs)
    for cls in STRUCTS:
        be_s, fe_s, t_s = _mk(cls)
        for k, v in pairs:
            _put(t_s, k, v)
        fe_s.drain(t_s.h)

        be_b, fe_b, t_b = _mk(cls)
        t_b.put_many(pairs)
        fe_b.drain(t_b.h)

        assert be_s.arena.snapshot() == be_b.arena.snapshot(), cls.__name__
        assert fe_b.clock.now <= fe_s.clock.now, cls.__name__


@settings(max_examples=6, deadline=None)
@given(raw_kvs, st.lists(st.integers(min_value=0, max_value=1 << 22),
                         min_size=1, max_size=60))
def test_batched_decode_matches_serial_lookups(pairs, extra):
    """get_many's columnar frombuffer decode returns exactly what per-key
    serial lookups return — present keys and misses alike."""
    pairs = _uniq(pairs)
    for cls in STRUCTS:
        _, fe, t = _mk(cls)
        t.put_many(pairs)
        probes = [k for k, _ in pairs] + extra
        random.Random(1).shuffle(probes)
        assert t.get_many(probes) == [_get(t, k) for k in probes], cls.__name__


@settings(max_examples=15, deadline=None)
@given(raw_kvs, st.integers(min_value=0, max_value=200),
       st.integers(min_value=0, max_value=6))
def test_torn_flush_recovers_through_batched_decoder(pairs, keep, after):
    """Tear the combined flush at a random write/byte position, reboot, and
    recover with a fresh front-end: the batched decoder must reconstruct an
    all-or-none per-op state — every key reads back either its full new
    value or nothing, with no torn bytes surfacing as values."""
    pairs = _uniq(pairs)
    be, fe, ht = _mk(RemoteHashTable)
    try:
        with fe.batch(ht.h):
            for k, v in pairs:
                ht.put(k, v)
            be.schedule_torn_write(keep, after_writes=after)
    except CrashError:
        pass
    if be.alive:
        # batch finished before the armed tear fired (few writes): the tear
        # hits the next flush instead — force it, then proceed identically.
        try:
            ht.put(1 << 23, 0)
            fe.drain(ht.h)
        except CrashError:
            pass
    if not be.alive:
        be.reboot()
    fe2 = FrontEnd(be, FEConfig.rcb(cache_bytes=1 << 16))
    ht2 = RemoteHashTable.recover(fe2, "t")
    want = dict(pairs)
    for k, v in want.items():
        got = ht2.get(k)
        assert got in (v, None)  # all-or-none: never a torn value


@settings(max_examples=25, deadline=None)
@given(st.lists(st.binary(min_size=0, max_size=300), min_size=1, max_size=12))
def test_fletcher_segments_bit_identical_to_scalar(bodies):
    """The wave-batched segment checksum is bit-identical to the scalar
    fletcher64 on every body — the batched decode path validates with it."""
    assert fletcher64_segments(bodies) == [fletcher64(b) for b in bodies]


@settings(max_examples=20, deadline=None)
@given(st.lists(
    st.lists(st.tuples(st.integers(min_value=0, max_value=1 << 20),
                       st.binary(min_size=1, max_size=64)),
             min_size=1, max_size=6),
    min_size=1, max_size=10,
), st.integers(min_value=0, max_value=1 << 12))
def test_columnar_tx_decode_matches_scalar_on_torn_tails(txs, cut):
    """decode_txs_columnar agrees with decode_txs entry-for-entry on any
    buffer, including a torn tail cut at a random byte: same consumed
    offset, same (addr, data) stream."""
    buf = b"".join(
        encode_tx([MemLog(addr=a, data=d) for a, d in tx]) for tx in txs
    )
    buf = buf[: max(0, len(buf) - cut % (len(buf) + 1))]
    ref, ref_consumed = decode_txs(buf)
    addrs, offs, lens, n_txs, consumed = decode_txs_columnar(buf)
    assert consumed == ref_consumed
    assert n_txs == len(ref)
    flat = [(e.addr, bytes(e.data)) for tx in ref for e in tx]
    got = [
        (a, buf[o : o + ln])
        for a, o, ln in zip(addrs.tolist(), offs.tolist(), lens.tolist())
    ]
    assert got == flat
