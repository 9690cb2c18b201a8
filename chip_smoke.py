#!/usr/bin/env python3
"""Drive the store's served path once on the chip and check every answer.

Builds the README "Cluster quick start" deployment with its blade arenas in
device memory (4 blades, 1 mirror each, 1 GiB per arena, 16 shards), loads
seeded 8-byte key/value records into a sharded hash table and a sharded
B+tree through the batched front-end (``FEConfig.rcb()``), answers point
lookups and range scans, fails one blade permanently and reads every record
back through mirror promotion.  Each answer is compared with a plain dict /
sorted-list reference built from the same seed.

    python3 chip_smoke.py [--seed S]

Needs a TPU: on any other platform it exits nonzero before doing any work.
Earlier lines report each phase's seconds, the compiled arena programs, the
device bytes in use and the answers compared; the last line is one JSON
object naming the device.
"""

from __future__ import annotations

import argparse
import bisect
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))


RECORDS = 100_000        # per structure
QUERIES = 10_000         # get_many keys per structure, half of them absent
SCANS = 100
CAPACITY = 1 << 30       # bytes per blade arena: 8 arenas, half the v5e's HBM
BATCH = 4096             # records per put_many / get_many call


class SmokeMismatch(AssertionError):
    """The store's answer differs from the reference."""


def _check(what: str, got, want) -> int:
    if got != want:
        bad = next((i for i, (g, w) in enumerate(zip(got, want)) if g != w),
                   min(len(got), len(want)))
        raise SmokeMismatch(
            f"{what}: {len(got)} answers vs {len(want)} expected, first "
            f"difference at {bad}: {got[bad:bad + 1]} != {want[bad:bad + 1]}")
    return len(want)


def run_phases(*, records: int, queries: int, scans: int, capacity: int,
               seed: int, log=print) -> dict:
    """Load, query, fail a blade and re-read; raise SmokeMismatch on any
    wrong answer.  Returns the counts of answers compared per phase."""
    import jax
    import numpy as np

    from repro.cluster import ClusterFrontEnd, NVMCluster, ShardedBPTree, ShardedHashTable
    from repro.core import FEConfig, devmem

    rng = np.random.default_rng(seed)
    keys = np.unique(rng.integers(1, 1 << 62, size=3 * records + queries, dtype=np.int64))
    keys = rng.permutation(keys)[: 2 * records + queries // 2].tolist()
    ht_keys = keys[:records]
    bt_keys = keys[records:2 * records]
    absent = keys[2 * records:]
    vals = rng.integers(-(1 << 62), 1 << 62, size=2 * records, dtype=np.int64).tolist()
    ht_ref = dict(zip(ht_keys, vals[:records]))
    bt_ref = dict(zip(bt_keys, vals[records:]))
    bt_sorted = sorted(bt_ref.items())
    counts = {}
    log(f"records per structure: {records}, get_many keys: {queries}, "
        f"range scans: {scans}, seed: {seed}")

    def timed(name, fn):
        t0 = time.perf_counter()
        out = fn()
        log(f"phase {name}: {time.perf_counter() - t0:.3f} s, "
            f"{devmem.compiled_programs()} compiled arena programs")
        return out

    def build():
        cluster = NVMCluster(n_blades=4, n_shards=16, num_mirrors=1,
                             capacity_per_blade=capacity)
        cfe = ClusterFrontEnd(cluster, FEConfig.rcb(), fe_id=0)
        return cluster, cfe, ShardedHashTable(cfe, "ht"), ShardedBPTree(cfe, "bt")

    cluster, cfe, ht, bt = timed("build", build)
    stats = jax.devices()[0].memory_stats() or {}
    arenas = len(cluster.blades) * (1 + cluster.num_mirrors)
    log(f"device bytes_in_use after build: {stats.get('bytes_in_use', 'not reported')} "
        f"({arenas} arenas of {capacity} bytes)")

    def load():
        pairs = list(ht_ref.items())
        for i in range(0, records, BATCH):
            ht.put_many(pairs[i:i + BATCH])
        for i in range(0, records, BATCH):
            bt.put_many(bt_sorted[i:i + BATCH])
        ht.drain()
        bt.drain()

    timed("load", load)

    def query():
        half = queries // 2
        present_ht = rng.choice(records, size=min(half, records), replace=False)
        present_bt = rng.choice(records, size=min(half, records), replace=False)
        for name, tree, present, ref in (
                ("hash get_many", ht, [ht_keys[i] for i in present_ht], ht_ref),
                ("bptree get_many", bt, [bt_keys[i] for i in present_bt], bt_ref)):
            qs = rng.permutation(present + absent).tolist()
            counts[name] = _check(name, tree.get_many(qs), [ref.get(k) for k in qs])
        bt_list = [k for k, _ in bt_sorted]
        n = 0
        for _ in range(scans):
            i = int(rng.integers(0, records))
            j = min(records - 1, i + int(rng.integers(0, 200)))
            lo, hi = bt_list[i], bt_list[j]
            want = bt_sorted[bisect.bisect_left(bt_list, lo):bisect.bisect_right(bt_list, hi)]
            n += _check(f"range_scan [{lo}, {hi}]", bt.range_scan(lo, hi), want)
        counts["range_scan rows"] = n

    timed("query", query)

    def failover():
        failovers = cluster.failovers
        cluster.blades[1].fail_permanently()
        for name, tree, ref in (("hash after failover", ht, ht_ref),
                                ("bptree after failover", bt, bt_ref)):
            ks = list(ref)
            got = []
            for i in range(0, len(ks), BATCH):
                got.extend(tree.get_many(ks[i:i + BATCH]))
            counts[name] = _check(name, got, [ref[k] for k in ks])
        if cluster.failovers != failovers + 1:
            raise SmokeMismatch(f"expected one mirror promotion, saw "
                                f"{cluster.failovers - failovers}")

    timed("failover", failover)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"device bytes_in_use at end: {stats.get('bytes_in_use', 'not reported')}, "
        f"peak: {stats.get('peak_bytes_in_use', 'not reported')}")
    log(f"answers compared: {json.dumps(counts)} "
        f"(total {sum(counts.values())}), all equal to the reference")
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="seed of keys and values")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}", file=sys.stderr)
        return 1
    run_phases(records=RECORDS, queries=QUERIES, scans=SCANS,
               capacity=CAPACITY, seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
