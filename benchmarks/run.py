"""Benchmark entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (us_per_call is virtual
microseconds per operation on the paper's fabric model; derived is the
headline ratio the paper reports for that experiment).

``--smoke`` shrinks every experiment to toy sizes so the whole suite —
every figure script end to end, including the cluster scaling/availability
runs — finishes in under a minute; CI uses it to keep all benchmark code
paths exercised.
"""

from __future__ import annotations

import argparse
import json
import sys


def _write_record(path: str, rows: list, prefix: str, preload: int,
                  n_ops: int, wall_s: float, phases: dict = None) -> None:
    """Emit a perf record in the schema scripts/check_bench.py guards: the
    measurement rows plus a ``{prefix}_bench_meta`` provenance entry (run
    sizes + wall clock; under ``--profile`` also the obs.profile per-phase
    seconds/call-counts) so the guard compares like-for-like."""
    meta = {
        "name": f"{prefix}_bench_meta",
        "preload": preload,
        "n_ops": n_ops,
        "wall_clock_seconds": round(wall_s, 1),
    }
    if phases:
        spans = {k: v for k, v in phases.items() if "seconds" in v}
        meta["profile_phase_seconds"] = {
            k: round(v["seconds"], 3) for k, v in spans.items()
        }
        meta["profile_phase_calls"] = {k: v["calls"] for k, v in spans.items()}
    record = rows + [meta]
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
    print(f"[{prefix}] perf record -> {path} ({wall_s:.0f}s wall)")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true", help="smaller sizes")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes: every figure end-to-end in under a minute")
    ap.add_argument("--only", default=None,
                    help="comma list: table2,table3,fig7,fig9,fig10,fig11,apps,cluster,vector")
    ap.add_argument("--bench-json", default=None,
                    help="where the vector-ops perf record is written "
                         "(default BENCH_vector_ops.json; --smoke runs write "
                         "a .smoke.json sibling so toy-size numbers never "
                         "clobber the committed baseline)")
    ap.add_argument("--cluster-json", default=None,
                    help="where the cluster replica-read perf record is "
                         "written (default BENCH_cluster_reads.json, same "
                         "--smoke guard)")
    ap.add_argument("--profile", action="store_true",
                    help="enable obs.profile around the perf-record runs and "
                         "write per-phase wall seconds into *_bench_meta")
    from .common import add_obs_args, obs_finish, obs_start
    add_obs_args(ap)
    args = ap.parse_args(argv)
    obs_start(args)
    if args.profile:
        from repro.obs import profile as _prof
        _prof.enable()

    def _phase_snapshot():
        """Per-record obs.profile totals (reset between records so each
        perf record carries only its own phases); None without --profile."""
        if not args.profile:
            return None
        snap = _prof.snapshot()
        _prof.reset()
        return snap
    if args.bench_json is None:
        args.bench_json = ("BENCH_vector_ops.smoke.json" if args.smoke
                           else "BENCH_vector_ops.json")
    if args.cluster_json is None:
        args.cluster_json = ("BENCH_cluster_reads.smoke.json" if args.smoke
                             else "BENCH_cluster_reads.json")
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        preload, n_ops = (400, 120)
    elif args.quick:
        preload, n_ops = (8000, 1200)
    else:
        preload, n_ops = (15000, 2500)

    csv = []

    def emit(name, us_per_call, derived):
        csv.append(f"{name},{us_per_call:.3f},{derived}")

    def want(name):
        return only is None or name in only

    if want("table2"):
        from .table2_allocators import main as t2
        rows = t2(n=1500 if args.smoke else 20000)
        emit("table2_two_tier_1024_alloc", 1.0 / rows["two-tier-1024"][0],
             f"vs_pmem={rows['two-tier-1024'][0] / rows['pmem'][0]:.2f}x")

    if want("table3"):
        from .table3_throughput import main as t3
        rows = t3(preload=preload, n_ops=n_ops)
        for row in rows:
            s = row["structure"]
            best = row.get("rcb") or row.get("rc")
            speed = best / row["naive"]
            emit(f"table3_{s}_rcb", 1e3 / best, f"rcb_vs_naive={speed:.1f}x")
        speeds = [(r.get("rcb") or r.get("rc")) / r["naive"] for r in rows]
        emit("table3_speedup_band", 0.0,
             f"min={min(speeds):.1f}x_max={max(speeds):.1f}x_paper=6-22x")

    if want("fig7"):
        from .fig_sweeps import main as sweeps
        if args.smoke:
            out = sweeps(preload=preload, n_ops=n_ops, batches=(1, 1024),
                         fracs=(0.10, 1.0), write_fracs=(1.0, 0.5))
        else:
            out = sweeps(preload=preload, n_ops=n_ops)
        row = out["fig7"]["mv_bst"]
        emit("fig7_mvbst_batch1024", 1e3 / row[1024],
             f"batch_gain={row[1024]/row[1]:.2f}x_paper=3.38x")

    if want("fig9"):
        from .fig9_scalability import main as f9
        out = f9(reader_counts=(1, 6), preload=preload,
                 writer_ops=n_ops, reader_ops=n_ops)
        lock6, mv6 = out["lock"][6], out["mv"][6]
        emit("fig9_mv_reader_advantage", 1e3 / mv6["reader_kops_avg"],
             f"mv_vs_lock_readers={mv6['reader_kops_avg']/lock6['reader_kops_avg']:.2f}x_paper=3.0-3.2x")
        wdeg_lock = 1 - out["lock"][6]["writer_kops"] / out["lock"][1]["writer_kops"]
        wdeg_mv = 1 - out["mv"][6]["writer_kops"] / out["mv"][1]["writer_kops"]
        emit("fig9_writer_degradation", 0.0,
             f"lock={wdeg_lock*100:.0f}%_mv={wdeg_mv*100:.0f}%_paper=26%/8%")

    if want("fig10"):
        from .fig10_multi_frontend import main as f10
        rows = f10(counts=(1, 2) if args.smoke else (1, 2, 4, 8),
                   pool=min(preload, 2048),
                   ops_per_writer=max(150, n_ops // 4))
        summary = rows[0]
        last = summary.get("agg_kops_8w") or 1.0
        emit("fig10_multi_writer", 1e3 / last,
             f"scaling={summary['speedup_8v1']:.2f}x_stale="
             f"{summary['committed_stale_epochs']}")

    if want("fig11"):
        from .fig11_replication_cpu import main as f11
        out = f11(preload=min(preload, 10000), ops=n_ops)
        emit("fig11_blade_replication", 0.0,
             f"overhead={out['overhead_blade']*100:.1f}%_fe_driven={out['overhead_fe']*100:.1f}%")

    if want("cluster"):
        import time

        from .fig_cluster_scaling import main as fcluster
        _phase_snapshot()  # drop phases accumulated by earlier sections
        wall0 = time.perf_counter()
        if args.smoke:
            cpreload, cops = 80, 150
            out = fcluster(blades=(1, 2, 4), preload=cpreload, ops=cops)
        elif args.quick:
            cpreload, cops = 250, 400
            out = fcluster(blades=(1, 2, 4), preload=cpreload, ops=cops)
        else:
            cpreload, cops = 400, 600
            out = fcluster()
        wall_s = time.perf_counter() - wall0
        scaling = out["scaling"]
        lo, hi = min(scaling), max(scaling)
        gain = scaling[hi]["aggregate_kops"] / scaling[lo]["aggregate_kops"]
        emit(f"cluster_scaling_{hi}_blades",
             1e3 / scaling[hi]["per_client_kops"],
             f"aggregate_gain_{lo}to{hi}={gain:.2f}x")
        a = out["availability"]
        emit("cluster_availability", 0.0,
             f"failovers={a['failovers']}_lost_committed={a['lost_committed']}")
        rr = out["replica_reads"]
        emit("cluster_replica_get_many", 1e3 / rr["replica_kops"],
             f"replica_vs_primary={rr['speedup']:.2f}x")
        # replica-read perf record: guarded by scripts/check_bench.py like
        # the vector-ops record (same schema, sibling file)
        cluster_row = {
            "name": "cluster_replica_get_many",
            "simulated_us_per_op": 1e3 / rr["replica_kops"],
            "replica_read_frac": round(rr["replica_read_frac"], 3),
            "speedup_vs_serial": round(rr["speedup"], 2),
        }
        # cluster-wide sim-latency percentiles (virtual µs) ride along in
        # the baseline so regressions in tail latency are visible too
        for key in ("replica_get_many_service_p50_us",
                    "replica_get_many_service_p99_us",
                    "replica_get_many_service_p999_us",
                    "replica_put_many_service_p50_us",
                    "replica_put_many_service_p99_us",
                    "replica_put_many_service_p999_us"):
            if key in rr:
                cluster_row[key] = rr[key]
        _write_record(args.cluster_json, [cluster_row],
                      "cluster", cpreload, cops, wall_s,
                      phases=_phase_snapshot())

    if want("vector"):
        import time

        from .fig_vector_ops import main as fvec
        _phase_snapshot()  # drop phases accumulated by earlier sections
        wall0 = time.perf_counter()
        out = fvec(preload=preload, n_ops=max(n_ops, 128))
        wall_s = time.perf_counter() - wall0
        row = out["hashtable"]
        emit("vector_hashtable_put_many", 1e3 / row["batched_put_kops"],
             f"batched_vs_serial={row['put_speedup']:.1f}x")
        rows = []
        for name, r in out.items():
            for op in ("put", "get"):
                if f"batched_{op}_kops" not in r:
                    continue
                vrow = {
                    "name": f"vector_{name}_{op}_many",
                    "simulated_us_per_op": 1e3 / r[f"batched_{op}_kops"],
                    "wall_clock_ops_per_sec": round(r[f"batched_{op}_wall_ops"], 1),
                    "speedup_vs_serial": round(r[f"{op}_speedup"], 2),
                }
                for p in ("p50", "p99", "p999"):
                    if f"{op}_service_{p}_us" in r:
                        vrow[f"service_{p}_us"] = r[f"{op}_service_{p}_us"]
                rows.append(vrow)
        _write_record(args.bench_json, rows, "vector", preload,
                      max(n_ops, 128), wall_s, phases=_phase_snapshot())

    if want("apps"):
        from .common import kops, make_fe
        from repro.core.apps import SmallBank, TATP
        accounts = 1000 if args.smoke else 50000
        subscribers = 300 if args.smoke else 5000
        for name, mk in [("smallbank", lambda fe: SmallBank(fe, "sb", n_accounts=accounts)),
                         ("tatp", lambda fe: TATP(fe, "tp", n_subscribers=subscribers))]:
            for variant in ("sym", "naive", "r", "rc"):
                fe = make_fe(variant)
                app = mk(fe)
                if name == "tatp":
                    app.populate(subscribers)
                t0 = fe.clock.now
                app.run_mix(n_ops, write_frac=1.0, seed=1)
                (fe.drain(app.h) if name == "smallbank" else app.drain())
                k = kops(n_ops, fe.clock.now - t0)
                emit(f"apps_{name}_{variant}", 1e3 / k, f"kops={k:.1f}")

    print("\n== CSV ==")
    print("name,us_per_call,derived")
    for line in csv:
        print(line)
    obs_finish(args)


if __name__ == "__main__":
    main()
