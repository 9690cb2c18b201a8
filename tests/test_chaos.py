"""Fault injection + self-healing path (chaos harness).

What must hold:

  * the durability oracle over seeded random fault schedules — every acked
    op survives recovery, unacked ops land whole or not at all, the healed
    state matches a fault-free replay of the acked prefix;
  * the data path heals itself: deadlines + bounded retries absorb
    transient drops, the per-link breaker trips on a persistently
    unreachable blade, and the front-end fences + promotes the mirror with
    NO test-orchestrated failover call;
  * a tear landing exactly on the 8-byte seq-watermark write commits the
    group or erases it — never a torn middle (targeted
    ``schedule_torn_write``);
  * the PR 5 staleness/RYW contract survives mirror lag spikes injected
    mid-run, and lagging-mirror bytes stay out of the page cache;
  * a cold re-attach replays a committed-but-unapplied op-log tail on
    FIRST touch (crash -> reboot -> rejoin end to end).
"""

import random

import pytest

from repro import obs
from repro.cluster import ClusterFrontEnd, NVMCluster, ReadPolicy, ShardedHashTable
from repro.core import (CircuitBreaker, CrashError, EndpointUnreachable,
                        FEConfig, FrontEnd, NVMBackend)
from repro.core.structures import RemoteHashTable
from repro.faults import (ALL_FAULT_KINDS, FaultInjector, FaultPlan,
                          run_chaos_schedule, run_steal_schedule)
from repro.faults.harness import _stale_epoch_total

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # fall back to the seeded-random shim
    from _hypothesis_shim import given, settings, st


DURABLE = dict(cache_bytes=4096, oplog_pipeline=1)


# ------------------------------------------------------------ chaos sweeps
def test_chaos_sweep_all_fault_classes():
    """Seeded random schedules over every fault class pass the durability
    oracle (the benchmark runs the full 200-schedule sweep; this keeps a
    representative slice in tier-1)."""
    seen = set()
    for seed in range(30):
        r = run_chaos_schedule(seed)
        assert r.ok, f"seed {seed}: {r.violations[:5]}"
        seen.update(r.injected)
    # the sweep must genuinely exercise the fault surface, not no-op
    assert len(seen) >= 9, f"only {sorted(seen)} injected"


def test_chaos_single_fault_classes():
    """Each fault class alone passes the oracle (failures localize)."""
    for kind in ALL_FAULT_KINDS:
        r = run_chaos_schedule(7, kinds=[kind], n_faults=4)
        assert r.ok, f"kind {kind}: {r.violations[:5]}"


def test_chaos_reports_fault_mix_and_heals():
    r = run_chaos_schedule(3, ensure=("nic_dead", "crash"))
    assert r.ok, r.violations[:5]
    assert r.injected.get("nic_dead", 0) >= 1
    assert r.injected.get("crash", 0) >= 1
    # nic_dead is unreachable-forever: healing requires a promotion that
    # was initiated by the data path, not the test
    assert r.promotions >= 1
    assert r.failovers_initiated >= 1


# ----------------------------------------- self-healing: retries & breaker
def test_wqe_drops_absorbed_by_bounded_retries():
    """Drops below the breaker threshold cost timeouts + backoff on the sim
    clock and the op still acks; nothing escapes to the caller."""
    be = NVMBackend(capacity=1 << 22)
    fe = FrontEnd(be, FEConfig.rc(**DURABLE))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    ht.put(1, 1)
    fe.drain(ht.h)
    t0 = fe.clock.now
    be.link.inject().drop_pending = 2
    ht.put(2, 2)
    assert fe.stats.op_timeouts == 2
    assert fe.stats.op_retries == 2
    assert fe.stats.breaker_trips == 0
    # each lost completion charges the full deadline before the resend
    assert fe.clock.now - t0 >= 2 * fe.cost.op_timeout_ns
    assert ht.get(2) == 2


def test_breaker_trips_and_fails_fast():
    """Consecutive timeouts past the threshold open the breaker; further
    rounds fail fast with EndpointUnreachable until the cooldown."""
    be = NVMBackend(capacity=1 << 22)
    fe = FrontEnd(be, FEConfig.rc(**DURABLE))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    ht.put(1, 1)
    be.link.inject().drop_pending = 1 << 30
    with pytest.raises(EndpointUnreachable):
        ht.put(2, 2)
    assert fe.stats.breaker_trips == 1
    assert be.link.breaker.state == "open"
    # fail-fast: no further timeout charged while open
    timeouts = fe.stats.op_timeouts
    with pytest.raises(EndpointUnreachable):
        ht.put(3, 3)
    assert fe.stats.op_timeouts == timeouts
    # cooldown elapses -> half-open -> a clean round closes it
    be.link.fault.drop_pending = 0
    fe.clock.advance(fe.cost.breaker_cooldown_ns)
    ht.put(4, 4)
    assert be.link.breaker.state == "closed"
    assert ht.get(4) == 4
    # the unacked puts are allowed either outcome; acked state must hold
    assert ht.get(1) == 1
    assert ht.get(2) in (None, 2)
    assert ht.get(3) in (None, 3)


def test_retry_backoff_is_deterministic():
    """Same seed/config twice -> identical sim-time trajectory (jitter is
    hashed from sim state, never wall-clock random)."""
    def run():
        be = NVMBackend(capacity=1 << 22)
        fe = FrontEnd(be, FEConfig.rc(**DURABLE))
        ht = RemoteHashTable(fe, "h", n_buckets=64)
        ht.put(1, 1)
        be.link.inject().drop_pending = 3
        try:
            ht.put(2, 2)
        except CrashError:
            pass
        return fe.clock.now, fe.stats.op_retries
    assert run() == run()


# ------------------------------------- front-end-initiated auto-promotion
def test_data_path_initiates_promotion_on_unreachable_primary():
    """A blade that stops answering (alive, NIC dead) is fenced and its
    mirror promoted BY THE DATA PATH: no test code calls crash(),
    fail_permanently(), promote_blade(), or handle_blade_failure()."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256)
    model = {}
    for k in range(60):
        t.put(k, k)
        model[k] = k
    t.drain()
    victim = 1
    cluster.blades[victim].link.inject().drop_pending = 1 << 30  # NIC dies
    for k in range(60, 90):  # ops keep flowing; some hit the sick blade
        t.put(k, k)
        model[k] = k
    assert cluster.failovers >= 1
    assert cfe.failovers_initiated >= 1
    assert cluster.blades[victim].alive  # promoted replacement serves
    got = t.get_many(sorted(model))
    assert got == [model[k] for k in sorted(model)]


def test_transient_breaker_open_heals_without_promotion():
    """A breaker opened by a burst of drops on an otherwise-healthy blade
    is probed and reset by recover_blade — no fencing, no promotion."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256)
    for k in range(40):
        t.put(k, k)
    t.drain()
    # exactly enough drops to trip the breaker, none left for the probe
    cluster.blades[1].link.inject().drop_pending = 3
    for k in range(40, 60):
        t.put(k, k)
    assert cluster.failovers == 0
    assert cfe.failovers_initiated == 0
    assert t.get_many(list(range(60))) == list(range(60))


# ----------------------------------------------- torn watermark regression
def _armed_table():
    be = NVMBackend(capacity=1 << 22)
    fe = FrontEnd(be, FEConfig.rc(**DURABLE))
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    for k in range(10):
        ht.put(k, k)
    fe.drain(ht.h)
    return be, fe, ht


def _put_through_power_loss(be, ht, k, v):
    """Issue a put whose flush dies at the armed tear; the blade may die
    after the put's last WQE, so the caller sees either an ack or a crash."""
    try:
        ht.put(k, v)
    except CrashError:
        pass
    assert not be.alive  # the tear fired


def test_tear_on_watermark_keep0_erases_the_group():
    """keep_bytes < 8 on the watermark slot: the commit record never
    persists, so recovery must treat the whole flushed group as unwritten
    — the acked prefix survives, the torn group vanishes."""
    be, fe, ht = _armed_table()
    be.schedule_torn_write(0, at_name="h.seq")
    _put_through_power_loss(be, ht, 99, 99)
    be.reboot()
    fe2 = FrontEnd(be, FEConfig.rc(**DURABLE))
    ht2 = RemoteHashTable.recover(fe2, "h")
    assert ht2.get(99) is None
    assert [ht2.get(k) for k in range(10)] == list(range(10))


def test_tear_on_watermark_keep8_commits_the_group():
    """keep_bytes >= 8 on the watermark slot: the 8-byte commit record
    lands whole before the power loss, so recovery must replay the group
    even though the writer never saw the completion."""
    be, fe, ht = _armed_table()
    be.schedule_torn_write(8, at_name="h.seq")
    _put_through_power_loss(be, ht, 99, 99)
    be.reboot()
    fe2 = FrontEnd(be, FEConfig.rc(**DURABLE))
    ht2 = RemoteHashTable.recover(fe2, "h")
    assert ht2.get(99) == 99
    assert [ht2.get(k) for k in range(10)] == list(range(10))


def test_watermark_tear_is_persist_atomic_either_way():
    """No torn middle: after a tear targeted at the watermark, the slot
    holds either the old seq or the new seq — never a partial value."""
    for keep in (0, 3, 7, 8):
        be, fe, ht = _armed_table()
        old = be.get_name("h.seq")
        be.schedule_torn_write(keep, at_name="h.seq")
        _put_through_power_loss(be, ht, 99, 99)
        # inspect the persisted arena bytes directly: the blade is down
        got = int.from_bytes(
            be.arena.snapshot(be.name_slot_addr("h.seq"),
                              be.name_slot_addr("h.seq") + 8), "little")
        if keep >= 8:
            assert got > old, f"keep={keep}: watermark should have landed"
        else:
            assert got == old, f"keep={keep}: watermark should not move"


def test_untargeted_tear_still_cuts_mid_entry():
    """The counter form keeps its historical semantics: a tear landing in
    a multi-word write persists exactly keep_bytes bytes."""
    be = NVMBackend(capacity=1 << 22)
    be.schedule_torn_write(5)
    be.write(be.heap_start, b"\xaa" * 16)
    assert not be.alive
    assert be.arena.snapshot(be.heap_start, be.heap_start + 16) == \
        b"\xaa" * 5 + b"\x00" * 11


def test_cancel_torn_write_disarms():
    be = NVMBackend(capacity=1 << 22)
    be.set_name("x", 1)
    be.schedule_torn_write(0, at_name="x")
    be.cancel_torn_write()
    be.set_name("x", 7)
    assert be.alive
    assert be.get_name("x") == 7


# ----------------------------------- staleness contract under lag spikes
@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=200),
       st.integers(min_value=0, max_value=999))
def test_lag_spike_mid_run_never_violates_ryw_pins(spike, seed):
    """Inject a mirror lag spike in the middle of a replica-routed
    read/write mix: read-your-writes must hold for every key this client
    wrote (the pins keep lagging replicas out of the read path)."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=8)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256, read_policy=policy)
    rng = random.Random(seed)
    model = {}
    pairs = [(k, k) for k in range(48)]
    t.put_many(pairs)
    model.update(pairs)
    for step in range(12):
        if step == 5:  # mid-run spike on every blade's mirror
            for be in cluster.blades.values():
                be.mirrors[0].set_lag(spike)
        ks = [rng.randrange(64) for _ in range(16)]
        if rng.random() < 0.5:
            t.put_many([(k, 1000 + step * 100 + j) for j, k in enumerate(ks)])
            for j, k in enumerate(ks):
                model[k] = 1000 + step * 100 + j
        else:
            got = t.get_many(ks)
            for k, v in zip(ks, got):
                # RYW through pins: every key this client reads it also
                # wrote, so only the freshest value may be served
                assert v == model.get(k), (step, k, v, model.get(k))
    for be in cluster.blades.values():
        be.mirrors[0].set_lag(0)


def test_lagging_mirror_bytes_stay_out_of_cache_under_spike():
    """ReadTarget.cache_safe under a set_lag spike: bytes served by a
    lagging mirror are not inserted into the page cache, so post-spike
    primary reads see fresh values instead of cached stale ones."""
    be = NVMBackend(capacity=1 << 24, num_mirrors=1)
    fe = FrontEnd(be, FEConfig.rc())  # cache on
    ht = RemoteHashTable(fe, "h", n_buckets=64)
    for k in range(20):
        ht.put(k, k)
    fe.drain(ht.h)
    be.mirrors[0].set_lag(1 << 20)  # spike: replication frozen
    for k in range(20):
        ht.put(k, k + 500)
    fe.drain(ht.h)
    fe.cache.clear()  # drop write-through entries: force remote reads
    with fe.replica_reads(ReadPolicy(mode="mirror", max_staleness_ops=1 << 40)):
        stale = [ht.get(k) for k in range(20)]
    assert stale == list(range(20))          # bounded-stale, as contracted
    assert [ht.get(k) for k in range(20)] == [k + 500 for k in range(20)]
    be.mirrors[0].set_lag(0)  # spike ends: queued writes drain
    be.mirrors[0].sync()
    assert be.mirrors[0].arena.snapshot() == be.arena.snapshot()


# ------------------------------------------- write-lease fencing chaos
def test_steal_schedule_sweep_no_durability_or_fence_violations():
    """Two writers racing lease steals under lease_expiry + crash faults:
    every acked op survives, no stale-epoch op is ever committed, and the
    sweep genuinely exercises the steal path (steals > 0 per run)."""
    kinds = set()
    for seed in range(8):
        r = run_steal_schedule(seed)
        assert r.ok, f"seed {seed}: {r.violations[:5]}"
        assert r.stats["write_lease_steals"] > 0, f"seed {seed}: no steals"
        assert r.stats["stale_epoch_entries"] == 0
        kinds.update(r.injected)
    assert {"lease_expiry", "crash"} <= kinds, f"only {sorted(kinds)} injected"


def test_fenced_stale_writer_group_commit_vanishes_whole():
    """The tentpole fencing contract, deterministically: writer A stages a
    group-commit window, its lease expires, writer B acquires the shard
    (epoch bumps, no graceful surrender — A never saw the steal) and
    commits.  A's later flush must be rejected at the blade by the epoch
    fence: its staged ops vanish whole (never interleave with B's stream)
    and A's next read sees B's value."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    a = ClusterFrontEnd(cluster, FEConfig.rcb(), fe_id=0)
    b = ClusterFrontEnd(cluster, FEConfig.rcb(), fe_id=1)
    ta = ShardedHashTable(a, "f", n_buckets=256)
    tb = ShardedHashTable(b, "f", n_buckets=256)
    for k in range(16):
        ta.put(k, k)
    ta.drain()
    ta.put(3, 111)              # staged under A's epoch, not yet flushed
    # B's clock runs past the TTL: A's lease is expired at acquisition
    # time, so the epoch bumps with stolen=False and no surrender drain
    b.clock.advance_to(a.clock.now + cluster.lease_ttl_ns + 1)
    tb.put(3, 222)
    tb.drain()
    fenced0 = sum(fe.stats.fenced_appends for fe in a.fes.values())
    ta.drain()                  # flush rejected at the blade, then retried empty
    fenced1 = sum(fe.stats.fenced_appends for fe in a.fes.values())
    assert fenced1 > fenced0, "stale writer's group commit was not fenced"
    assert ta.get(3) == 222     # A's 111 vanished whole; A resynced
    assert tb.get(3) == 222
    assert _stale_epoch_total(cluster) == 0
    # untouched keys are unaffected by the fence
    assert ta.get_many([k for k in range(16) if k != 3]) == \
        [k for k in range(16) if k != 3]


# --------------------------------- replication channel v2: sim-time lag
def test_mirror_lag_ns_holds_bytes_until_sim_time():
    """set_lag_ns holds replicated units until now >= arrival + lag_ns,
    composes with lag_writes depth, and reads drain time-held units as
    sim time advances with no new writes."""
    be = NVMBackend(capacity=1 << 22, num_mirrors=1)
    m = be.mirrors[0]
    m.set_lag_ns(1_000.0)
    addr = be.heap_start
    t0 = be.clock.now
    be.write(addr, b"\xab" * 16)
    assert not m.synchronous
    assert m.arena.snapshot(addr, addr + 16) == b"\x00" * 16  # held by time
    assert m.read(addr, 16) == b"\x00" * 16                # still too young
    be.clock.advance_to(t0 + 1_001.0)
    assert m.read(addr, 16) == b"\xab" * 16  # read drained the held unit
    # depth AND delay compose: a unit applies only when both release it
    m.lag_writes = 4
    t1 = be.clock.now
    be.write(addr + 64, b"\xcd" * 8)
    be.clock.advance_to(t1 + 10_000.0)       # time constraint long released
    assert m.read(addr + 64, 8) == b"\x00" * 8  # depth still holds it
    for i in range(4):
        be.write(addr + 128 + i * 8, b"\xee" * 8)
    assert m.read(addr + 64, 8) == b"\xcd" * 8  # pushed through by depth
    # spike ends: zeroing both knobs + sync restores byte-identity
    m.lag_writes = 0
    m.set_lag_ns(0)
    m.sync()
    assert m.arena.snapshot() == be.arena.snapshot()
    assert m.synchronous


@settings(max_examples=6, deadline=None)
@given(st.integers(min_value=1, max_value=1 << 40),
       st.integers(min_value=0, max_value=999))
def test_lag_ns_spike_mid_run_never_violates_ryw_pins(spike_ns, seed):
    """Satellite regression: a *timestamp*-lagged mirror (set_lag_ns)
    injected mid-run composes with the staleness/RYW pins exactly like a
    depth-lagged one — every key this client wrote reads back fresh."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    policy = ReadPolicy(mode="auto", max_staleness_ops=8)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(cache_bytes=4096), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256, read_policy=policy)
    rng = random.Random(seed)
    model = {}
    pairs = [(k, k) for k in range(48)]
    t.put_many(pairs)
    model.update(pairs)
    for step in range(12):
        if step == 5:  # mid-run sim-time spike on every blade's mirror
            for be in cluster.blades.values():
                be.mirrors[0].set_lag_ns(float(spike_ns))
        if step == 8:  # compose: depth lag joins the time lag mid-wave
            for be in cluster.blades.values():
                be.mirrors[0].set_lag(3)
        ks = [rng.randrange(64) for _ in range(16)]
        if rng.random() < 0.5:
            t.put_many([(k, 1000 + step * 100 + j) for j, k in enumerate(ks)])
            for j, k in enumerate(ks):
                model[k] = 1000 + step * 100 + j
        else:
            got = t.get_many(ks)
            for k, v in zip(ks, got):
                assert v == model.get(k), (step, k, v, model.get(k))
    for be in cluster.blades.values():
        be.mirrors[0].set_lag_ns(0)
        be.mirrors[0].set_lag(0)


# ------------------------------------- crash -> reboot -> rejoin
def test_cold_reattach_replays_committed_tail_on_first_touch():
    """A writer dies with ops committed to the op log but not applied;
    the blades reboot; a COLD client — one that never bound these shards —
    must replay the tail on first touch instead of serving pre-crash
    state."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256)
    for k in range(40):
        t.put(k, k)
    t.drain()
    # second wave: per-op flush commits each entry, but the writer dies
    # before draining the applies
    for k in range(40):
        t.put(k, k + 1000)
    del t, cfe  # front-end crash: staged memory-log state is gone
    for be in cluster.blades.values():
        be.crash()
        be.reboot()
    cold = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=5)
    t2 = ShardedHashTable(cold, "t", n_buckets=256)
    assert t2.get_many(list(range(40))) == [k + 1000 for k in range(40)]


def test_cluster_reboot_rejoins_directory_with_epoch_bump():
    """handle_blade_failure distinguishes transient from permanent: a
    crashed blade reboots in place (no promotion), revokes leases, and
    bumps the epoch so every client rebinds."""
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    cfe = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=0)
    t = ShardedHashTable(cfe, "t", n_buckets=256)
    for k in range(30):
        t.put(k, k)
    t.drain()
    epoch0 = cluster.directory.epoch
    cluster.blades[1].crash()
    for k in range(30, 45):  # the data path notices and recovers
        t.put(k, k)
    assert cluster.failovers == 0          # transient: reboot, not promote
    assert cluster.directory.epoch > epoch0
    assert t.get_many(list(range(45))) == list(range(45))


# --------------------------------------------------- obs integration
def test_fault_metrics_and_counters_exported():
    try:
        with obs.observe(metrics=True) as sess:
            r = run_chaos_schedule(11, ensure=("nic_dead",))
            assert r.ok, r.violations[:3]
            totals, _ = sess.fe_totals()
            text = sess.build_registry().to_prometheus()
    finally:
        obs.stop()
    assert totals.get("op_retries", 0) >= 1
    assert totals.get("op_timeouts", 0) >= 1
    assert sess.counters.get("retries_total", 0) >= 1
    assert sess.counters.get("failovers_initiated", 0) >= 1
    assert sess.counters.get("fault_nic_dead", 0) >= 1
    assert "rnvm_fe_op_retries" in text
    assert "rnvm_retries_total" in text


def test_breaker_state_gauge_exported_per_blade():
    try:
        with obs.observe(metrics=True) as sess:
            cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                                 n_shards=4, num_mirrors=1)
            cfe = ClusterFrontEnd(cluster, FEConfig.rc(**DURABLE), fe_id=0)
            t = ShardedHashTable(cfe, "t", n_buckets=256)
            for k in range(20):
                t.put(k, k)
            t.drain()
            lk = cluster.blades[0].link
            lk.breaker = CircuitBreaker(cluster.cost)
            lk.breaker.opened_at = cfe.clock.now  # blade-0 breaker: open
            text = sess.build_registry().to_prometheus()
    finally:
        obs.stop()
    lines = [l for l in text.splitlines() if l.startswith("rnvm_breaker_state{")]
    assert len(lines) >= 2                          # one gauge per blade
    assert any('blade="0"' in l and l.endswith(" 1") for l in lines)
    assert any('blade="1"' in l and l.endswith(" 0") for l in lines)


def test_fault_plan_is_deterministic_and_sorted():
    p1 = FaultPlan.random(42, 100, 3)
    p2 = FaultPlan.random(42, 100, 3)
    assert p1.specs == p2.specs
    assert [s.at_op for s in p1.specs] == sorted(s.at_op for s in p1.specs)
    assert FaultPlan.random(43, 100, 3).specs != p1.specs


def test_injector_counts_and_finish_disarms():
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 22,
                         n_shards=4, num_mirrors=1)
    plan = FaultPlan.random(5, 50, 2, n_faults=5,
                            kinds=["wqe_drop", "nic_stall", "lag_spike"])
    inj = FaultInjector(plan, cluster, None)
    for i in range(50):
        inj.step(i)
    assert sum(inj.injected.values()) == 5
    inj.finish()
    for be in cluster.blades.values():
        f = be.link.fault
        assert f is None or (f.drop_pending == 0 and f.stall_until == 0.0)
        assert be._torn_write_at is None
