"""Figure 10 v2: contended multi-writer scaling over SHARED structures.

The original figure dodged concurrency control: each front-end wrote its
own private structure, so "multi-front-end scaling" measured only NIC
contention.  Since the write-fencing PR every front-end must hold a
shard's write lease before appending to that shard's op log, so the
figure now measures the thing the paper's concurrency-control pillar
actually claims: many writers mutating ONE sharded structure, fenced by
epochs, scaling with writer count.

Two contention regimes, both zipfian(theta=0.99) via ``benchmarks.
keydist`` and both open-loop (seeded Poisson arrivals dispatched in
arrival order, as in fig_open_loop):

  * ``low``  — writers draw from disjoint *shard* partitions (keys are
    filtered by ``directory.shard_of``): write leases settle immediately
    and throughput should scale near-linearly — the headline
    ``speedup_8v1`` row CI guards (>= 2x at 8 writers on 2 blades).
  * ``high`` — every writer draws from one shared zipfian keyspace:
    shards ping-pong until the lease table flips them into shared mode
    and writers serialize through the writer mutex; the figure reports
    steals, shared-mode shard counts and fenced (rejected) appends.

Correctness is asserted, not assumed: after every cell the blade op logs
are scanned for committed stale-epoch entries (``committed_stale_epochs``
must be ZERO — a fenced writer's ops may vanish whole but never land),
and a full read-back of every writer's acked model must match.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from typing import Dict, List

from repro.cluster import ClusterFrontEnd, NVMCluster, ShardedHashTable
from repro.core import FEConfig
from repro.core.oplog import stale_epoch_entries
from repro.core.sim import OpenLoopEngine, OpenLoopOp, OpenLoopStation, poisson_arrivals

from .common import add_obs_args, kops, obs_finish, obs_rebase, obs_start
from .keydist import zipf_keys

N_SHARDS = 8
ZIPF_THETA = 0.99
MAX_BATCH = 32
COUNTS = (1, 2, 4, 8)
LOAD_FRAC = 0.9  # offered load per writer as a fraction of probed capacity


def _fe_config() -> FEConfig:
    # group commit on (staged windows can span a lease movement, so the
    # fencing path is genuinely exercised), page cache small but present
    return FEConfig.rcb(cache_bytes=1 << 16, batch_ops=64, oplog_group=16)


class _Writer:
    """One writer front-end sharing the cluster-wide table ``mw``."""

    def __init__(self, cluster: NVMCluster, idx: int, pool: int):
        self.cfe = ClusterFrontEnd(cluster, _fe_config(), fe_id=idx)
        self.table = ShardedHashTable(self.cfe, "mw", n_buckets=max(256, pool))
        self.model: Dict[int, int] = {}
        self._next_val = 1 + (idx << 32)  # writer-tagged values

    def execute(self, batch: List[OpenLoopOp]) -> None:
        pairs = []
        for op in batch:
            pairs.append((op.key, self._next_val))
            self._next_val += 1
        self.table.put_many(pairs)
        self.model.update(pairs)


def _committed_stale_epochs(cluster: NVMCluster) -> int:
    """Committed stale-epoch op-log entries across every blade: any entry
    appended under an epoch older than one already present in its log.
    The write fence must keep this at exactly zero."""
    total = 0
    for be in cluster.blades.values():
        for name, area in be._log_areas.items():
            if name.endswith(".oplog"):
                buf = be.arena.snapshot(area.addr, area.addr + area.size)
                total += stale_epoch_entries(buf)
    return total


def _build(n_writers: int, pool: int):
    cluster = NVMCluster(n_blades=2, capacity_per_blade=1 << 24,
                         n_shards=N_SHARDS, num_mirrors=0)
    writers = [_Writer(cluster, i, pool) for i in range(n_writers)]
    writers[0].table.put_many([(k, k) for k in range(pool)])
    writers[0].table.drain()
    # models track only the measured run's writes (preload is background)
    # preload/measurement barrier
    for be in cluster.blades.values():
        be.link.reset()
    for w in writers:
        w.cfe.clock.now = 0.0
        for fe in w.cfe.fes.values():
            fe.clock.now = 0.0
    obs_rebase()
    return cluster, writers


def _keys_for(cluster: NVMCluster, idx: int, n_writers: int, n_ops: int,
              pool: int, mode: str, seed: int) -> List[int]:
    """Zipfian key stream for one writer.  ``low`` filters the draw to the
    writer's own shard partition (disjoint lease footprints); ``high``
    shares the whole keyspace so hot shards collide across writers."""
    shard_of = cluster.directory.shard_of
    # shards are placed round-robin over blades (blade = shard % n_blades),
    # so CONTIGUOUS shard chunks alternate blades: chunking gives each
    # writer a disjoint lease footprint that still spans every blade
    chunk = max(1, cluster.directory.n_shards // n_writers)
    out: List[int] = []
    draw = 0
    while len(out) < n_ops:
        ks = zipf_keys(max(n_ops, 256), pool, theta=ZIPF_THETA,
                       seed=seed + 101 * draw)
        draw += 1
        for k in ks:
            k = int(k)
            if mode == "high" or \
                    min(shard_of(k) // chunk, n_writers - 1) == idx:
                out.append(k)
                if len(out) == n_ops:
                    break
    return out


def probe_capacity(pool: int, n_ops: int) -> float:
    """Closed-loop single-writer put capacity (ops/s, virtual time): the
    per-writer offered-load yardstick for the open-loop cells."""
    cluster, writers = _build(1, pool)
    w = writers[0]
    keys = _keys_for(cluster, 0, 1, n_ops, pool, "high", seed=5)
    t0 = w.cfe.clock.now
    for i in range(0, n_ops, MAX_BATCH):
        w.execute([OpenLoopOp(0.0, "put", key=k)
                   for k in keys[i:i + MAX_BATCH]])
    w.table.drain()
    return n_ops / ((w.cfe.clock.now - t0) / 1e9)


def run_cell(n_writers: int, pool: int, ops_per_writer: int, mode: str,
             rate: float) -> Dict:
    """One (writers, contention-mode) cell: fresh cluster, one shared
    table, Poisson arrivals at ``rate`` per writer, full drain + checks."""
    cluster, writers = _build(n_writers, pool)
    stations = []
    for i, w in enumerate(writers):
        keys = _keys_for(cluster, i, n_writers, ops_per_writer, pool, mode,
                         seed=7919 * i + (17 if mode == "high" else 23))
        ts = poisson_arrivals(rate, ops_per_writer, seed=31 * i + 7)
        ops = [OpenLoopOp(float(t), "put", key=k, tenant=i)
               for t, k in zip(ts, keys)]
        st = OpenLoopStation(w.cfe.clock, w.execute, station_id=i,
                             max_batch=MAX_BATCH)
        st.offer(ops)
        stations.append(st)
    eng = OpenLoopEngine(stations)
    summary = eng.run()
    for w in writers:
        w.table.drain()

    # --- correctness: committed stale epochs + acked read-back.  Keys
    # written by exactly one writer must read back as that writer's last
    # value (multi-writer keys have a racy last-writer, skip those).
    stale = _committed_stale_epochs(cluster)
    mismatches = 0
    reader = writers[0]
    owners: Dict[int, set] = {}
    for i, w in enumerate(writers):
        for k in w.model:
            owners.setdefault(k, set()).add(i)
    solo = [k for k, who in owners.items() if len(who) == 1]
    got = reader.table.get_many(solo)
    for k, v in zip(solo, got):
        i = next(iter(owners[k]))
        if v != writers[i].model[k]:
            mismatches += 1

    steals = cluster.leases.steals
    fenced = sum(int(fe.stats.fenced_appends)
                 for w in writers for fe in w.cfe.fes.values())
    steal_hists = [w.cfe.op_hist.get("lease_steal") for w in writers]
    steal_hists = [h for h in steal_hists if h is not None and h.count]
    steal_p99 = max((h.percentile(99) for h in steal_hists), default=0.0)
    return {
        "mode": mode,
        "writers": n_writers,
        "aggregate_kops": round(kops(summary["served"],
                                     summary["makespan_ns"]), 2),
        "write_lease_steals": steals,
        "fenced_appends": fenced,
        "shared_mode_shards": len(cluster.leases.shared_shards),
        "steal_p99_us": round(steal_p99 / 1e3, 2),
        "committed_stale_epochs": stale,
        "read_back_mismatches": mismatches,
    }


def main(counts=COUNTS, pool: int = 4096, ops_per_writer: int = 1500) -> List[Dict]:
    wall0 = time.time()
    cap = probe_capacity(pool, min(ops_per_writer, 512))
    rate = LOAD_FRAC * cap
    print(f"probed single-writer put capacity: {cap / 1e3:.1f} kops "
          f"(offering {LOAD_FRAC:.0%} per writer)")
    by_mode: Dict[str, List[Dict]] = {"low": [], "high": []}
    for mode in ("low", "high"):
        for n in counts:
            pt = run_cell(n, pool, ops_per_writer, mode, rate)
            by_mode[mode].append(pt)
            print(f"  {mode:>4} contention writers={n}: "
                  f"aggregate={pt['aggregate_kops']:>8} kops "
                  f"steals={pt['write_lease_steals']:>4} "
                  f"fenced={pt['fenced_appends']:>3} "
                  f"shared={pt['shared_mode_shards']} "
                  f"steal_p99={pt['steal_p99_us']:>7}us "
                  f"stale={pt['committed_stale_epochs']} "
                  f"mism={pt['read_back_mismatches']}")

    lo = by_mode["low"]
    speedup = (lo[-1]["aggregate_kops"] / lo[0]["aggregate_kops"]
               if lo[0]["aggregate_kops"] else 0.0)
    stale = sum(p["committed_stale_epochs"] for pts in by_mode.values()
                for p in pts)
    mism = sum(p["read_back_mismatches"] for pts in by_mode.values()
               for p in pts)
    steals = sum(p["write_lease_steals"] for p in by_mode["high"])
    fenced = sum(p["fenced_appends"] for pts in by_mode.values() for p in pts)
    steal_p99 = max(p["steal_p99_us"] for pts in by_mode.values() for p in pts)
    print(f"low-contention scaling {counts[0]}->{counts[-1]} writers: "
          f"{speedup:.2f}x; high-contention steals={steals} "
          f"fenced_appends={fenced}; committed stale epochs={stale}; "
          f"read-back mismatches={mism}")

    rows: List[Dict] = [{
        "name": "multi_writer_sweep",
        "speedup_8v1": round(speedup, 2),
        "agg_kops_1w": lo[0]["aggregate_kops"],
        "agg_kops_8w": lo[-1]["aggregate_kops"],
        "write_lease_steals": steals,
        "fenced_appends": fenced,
        "shared_mode_shards_high": by_mode["high"][-1]["shared_mode_shards"],
        "steal_p99_us": steal_p99,
        "committed_stale_epochs": stale,
        "read_back_mismatches": mism,
    }]
    for mode in ("low", "high"):
        for pt in by_mode[mode]:
            rows.append({"name": f"multi_writer_{mode}_{pt['writers']}w", **pt})
    rows.append({
        "name": "multi_writer_bench_meta",
        "preload": pool,
        "n_ops": sum(counts) * ops_per_writer * 2,
        "wall_clock_seconds": round(time.time() - wall0, 1),
    })
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny sizes")
    ap.add_argument("--quick", action="store_true",
                    help="the CI-guarded sizes (BENCH_multi_writer.json)")
    ap.add_argument("--json", default=None,
                    help="write the BENCH_multi_writer-format record here")
    add_obs_args(ap)
    args = ap.parse_args()
    obs_start(args)
    if args.smoke:
        rows = main(counts=(1, 2, 4), pool=512, ops_per_writer=250)
    elif args.quick:
        rows = main(counts=(1, 2, 4, 8), pool=2048, ops_per_writer=600)
    else:
        rows = main()
    obs_finish(args)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json}")
    summary = rows[0]
    if summary["committed_stale_epochs"] or summary["read_back_mismatches"]:
        sys.exit(1)
