#!/usr/bin/env python3
"""Find a cell's knee: offer its traffic at a series of fixed rates after
one set-up, and report for each the ops completed per second and whether
the backlog grew over the window.

    python3 bench/sweep.py --workload <cell>[,<cell>...] --rates r1,r2,... \
        --seconds S --seed N

Cells given together share one configuration and one set-up, and are swept
one after another on the same store.  Each rate runs as an open loop for
``S`` seconds with the ops due in that time and no drain: the backlog is
the ops due but not yet served, read at half the window and at its end.
A rate may repeat: each window draws its ops from a seed of its own, so
repeats read the spread of one set-up's windows.  ``compiled`` counts the
arena programs compiled inside a window.
The knee is the highest rate whose backlog did not grow.  One JSON line
per rate on standard output; the benchmark's runs never call this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import run
import traffic as tr


def backlog(due: np.ndarray, done: np.ndarray, t: float) -> int:
    return int(np.sum(due <= t) - np.sum(done <= t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cells = [run.load_cell(n) for n in args.workload.split(",")]
    if len({c["config"] for c in cells}) != 1:
        raise SystemExit("cells swept together must share their configuration")
    config = cells[0]["config_data"]
    rates = [float(r) for r in args.rates.split(",")]

    t = time.perf_counter()
    run.use_compile_cache()
    devs = run.devices(max(c["chips"] for c in cells), require_accelerator=True)
    from repro.core import devmem
    worst = max(rates)
    n_extra = sum(tr.inserts_needed({**c["traffic_data"], "rate_ops_s": worst},
                                    args.seconds) * len(rates) for c in cells) + 1
    rec = run.records_for(config, args.seed, n_extra)
    cluster, cfe, store = run.build(config)
    run.load_records(store, config, rec)
    print(f"set-up {time.perf_counter() - t:.3f} s on {devs[0].device_kind}",
          file=sys.stderr)
    first_extra = 0
    seeds = tr.rngs(args.seed + 2, len(cells) * len(rates))
    for ci, cell in enumerate(cells):
        harness = run.Harness(store, int(cell["traffic_data"]["max_batch"]))
        warm = tr.make_ops(cell["traffic_data"], rec, args.seed, 2.0,
                           first_extra=first_extra)
        first_extra += int(np.sum(warm.kind == tr.KINDS.index(tr.INSERT)))
        harness.serve(warm, limit_s=60.0)
        for ri, rate in enumerate(rates):
            traffic = {**cell["traffic_data"], "rate_ops_s": rate}
            ops = tr.make_ops(traffic, rec, int(seeds[ci * len(rates) + ri].integers(1 << 62)),
                              args.seconds, first_extra=first_extra)
            first_extra += int(np.sum(ops.kind == tr.KINDS.index(tr.INSERT)))
            programs0 = devmem.compiled_programs()
            done = harness.serve(ops, limit_s=args.seconds)
            compiled = devmem.compiled_programs() - programs0
            lat = np.sort(done - ops.due)
            mid, end = (backlog(ops.due, done, args.seconds / 2),
                        backlog(ops.due, done, args.seconds))
            print(json.dumps({
                "cell": cell["name"], "offered_ops_s": rate,
                "completed_ops_s": float(np.sum(done <= args.seconds) / args.seconds),
                "backlog_mid": mid, "backlog_end": end,
                "p50_ms": float(lat[(len(lat) - 1) // 2] * 1e3),
                "p95_ms": float(lat[int(0.95 * (len(lat) - 1))] * 1e3),
                "compiled": compiled, "errors": len(harness.errors)}), flush=True)
            harness.events.clear()
            store.drain()
    return 0


if __name__ == "__main__":
    sys.exit(main())
