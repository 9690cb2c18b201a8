#!/usr/bin/env python3
"""The store's own wall-clock spans and counters in a benchmark run.

The program keeps them in ``repro.obs.profile``: spans such as
``store.range_scan``, ``shard.probe``, ``fe.read_wave``, ``fe.group_commit``
and ``arena.read.{prep,dispatch,wait,copy}``, each also a
``jax.profiler.TraceAnnotation`` named ``repro.<span>``, and counters such
as ``arena.reads`` and the read causes ``reads.<cause>``.  ``bench/run.py``
switches them on for a ``--trace 1`` run and hands the window's snapshot to
the metric readers as ``rec["profile"]``.  This module reads them:

* ``reads_by_cause`` and ``read_split_us``: device reads per cause, and one
  read's time split by span, from the snapshot;
* ``idle_by_program_span``: the traced window's idle gaps, the same ones
  ``trace_reduce.reduce`` finds, labelled by the innermost ``repro.*`` span
  open at each gap's middle (``in store, outside program spans`` where only
  the harness's ``bench.store.*`` span is open, ``outside the store`` where
  none is).  ``traced_idle`` applies it to the run's own trace.

A program without these spans and counters (an older checkout) gives None
from each, never an error.

Run as a script, it measures with them:

    python3 bench/program_spans.py --workload <cell> --seeds a,b --seconds S
        one traced run per seed, as ``bench/run.py --trace 1`` makes it; prints
        its result line with one more key, ``program``: the snapshot, reads
        per op by cause and one read's time split, and the idle time by
        program span
    python3 bench/program_spans.py --workload <cell> --seeds a,b --seconds S --cost
        the cost of the spans: one set-up, then per seed a window with the
        spans off and one with them on (the profiler off in both), in
        alternating order; one line per window with its throughput, p50 and
        p95 (ops due in the window; an op not served within 5 s of its close
        counts as slowest), and at the end every answer checked against the
        reference
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
from collections import defaultdict
from typing import Dict, List, Optional

import trace_reduce as trd

PREFIX = "repro."
IN_STORE = "in store, outside program spans"
OUTSIDE = "outside the store"
STORE_SPAN = "bench.store."
CAUSES = ("name_probe", "wave", "serial", "apply_log")
READ_PARTS = ("prep", "dispatch", "wait", "copy")
# where bench/run.py writes the profiler trace of a --trace 1 run
TRACE_DIR = pathlib.Path(__file__).resolve().parents[1] / ".bench_out" / "trace"


# ------------------------------------------------------------ the snapshot
def reads_by_cause(prof: Dict) -> Optional[Dict[str, float]]:
    """Device reads (``read_runs`` calls) by the cause that issued them, and
    ``rest``: those no named cause counted.  None where the program counts
    no reads."""
    if "arena.reads" not in prof:
        return None
    out = {c: prof.get(f"reads.{c}", {}).get("count", 0) for c in CAUSES}
    out["rest"] = prof["arena.reads"]["count"] - sum(out.values())
    return out


def read_split_us(prof: Dict) -> Optional[Dict[str, float]]:
    """One device read's host microseconds by part: index building
    (``prep``), the jitted call (``dispatch``), the wait for the device
    (``wait``, less the copy) and the copy to the host (``copy``)."""
    reads = prof.get("arena.reads", {}).get("count")
    if not reads:
        return None
    key = {"wait": "self_seconds"}
    return {p: prof.get(f"arena.read.{p}", {}).get(key.get(p, "seconds"), 0.0) / reads * 1e6
            for p in READ_PARTS}


def arena_host_seconds(prof: Dict) -> Optional[float]:
    """Host seconds in the arena's index building and jitted calls (every
    ``arena.*.prep`` and ``arena.*.dispatch`` span); None where there are
    none."""
    parts = [v["seconds"] for k, v in prof.items() if k.startswith("arena.")
             and k.endswith((".prep", ".dispatch"))]
    return sum(parts) if parts else None


# --------------------------------------------------------------- the trace
def program_spans(pd) -> List[trd.Interval]:
    out: List[trd.Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                           for e in line.events if e.name.startswith(PREFIX))
    return out


def innermost(spans: List[trd.Interval], points: List[float]) -> List[Optional[str]]:
    """For each time in `points`, the name of the innermost span open at it
    (spans of one thread nest: the latest-starting one still open), or
    None."""
    spans = sorted(spans, key=lambda sp: (sp[0], -sp[1]))
    out: List[Optional[str]] = [None] * len(points)
    stack: List[trd.Interval] = []
    i = 0
    for j in sorted(range(len(points)), key=points.__getitem__):
        t = points[j]
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] <= spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] <= t:
            stack.pop()
        if stack:
            out[j] = stack[-1][2]
    return out


def idle_by_program_span(pd) -> Optional[List[List]]:
    """``[[label, idle seconds], ...]``, largest first, over the gaps that
    ``trace_reduce.reduce`` counts: they sum to its idle time.  None where
    reduce finds nothing or the trace holds no program span."""
    bench = trd.host_spans(pd)
    windows = [sp for sp in bench if sp[2] == trd.WINDOW_SPAN]
    ops = trd.device_lines(pd, trd.OPS_LINE)
    prog = program_spans(pd)
    if not windows or not ops or not prog:
        return None
    lo, hi = windows[0][0], windows[0][1]
    busy_by_dev = {d: trd.merge(evs, lo, hi) for d, evs in ops.items()}
    busy_by_dev = {d: b for d, b in busy_by_dev.items() if b}
    if not busy_by_dev:
        return None
    store = [sp for sp in bench if sp[2].startswith(STORE_SPAN)]
    idle: Dict[str, float] = defaultdict(float)
    for busy in busy_by_dev.values():
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(s, e) for s, e in zip(edges[0::2], edges[1::2]) if e > s]
        mids = [(s + e) / 2 for s, e in gaps]
        for (s, e), p, b in zip(gaps, innermost(prog, mids), innermost(store, mids)):
            label = p[len(PREFIX):] if p else (IN_STORE if b else OUTSIDE)
            idle[label] += (e - s) / 1e9 / len(busy_by_dev)
    return [[k, v] for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]


def traced_idle(rec: Dict) -> Optional[List[List]]:
    """``idle_by_program_span`` of the run's own trace, computed once per
    record; None where the run has no device trace."""
    if "idle_by_program_span" not in rec:
        path = trd.newest_xplane(str(TRACE_DIR)) if rec.get("trace") else None
        rec["idle_by_program_span"] = (idle_by_program_span(trd.load(path))
                                       if path else None)
    return rec["idle_by_program_span"]


def program_summary(rec: Dict) -> Dict:
    prof = rec["profile"]
    causes = reads_by_cause(prof)
    out = {"profile": prof,
           "reads_per_op": causes and {k: v / rec["ops"] for k, v in causes.items()},
           "read_split_us": read_split_us(prof)}
    if rec.get("span0"):
        out["bench_read_runs"] = (rec["span1"]["calls.read_runs"]
                                  - rec["span0"]["calls.read_runs"])
    if rec.get("trace"):
        out["idle_by_program_span"] = traced_idle(rec)
    return out


# -------------------------------------------------------------------- runs
def traced_runs(args, run) -> None:
    """One ``run.run_cell`` per seed with ``--trace 1``; its record is
    caught where the metric readers receive it."""
    caught: Dict = {}
    load_reader = run.load_reader

    def catching(name):
        read = load_reader(name)

        def reader(rec):
            caught["rec"] = rec
            return read(rec)
        return reader

    run.load_reader = catching
    for seed in args.seeds:
        out = run.run_cell(args.workload, seed, args.seconds, True)
        out["program"] = program_summary(caught.pop("rec"))
        print(json.dumps({"workload": args.workload, "seed": seed, **out}), flush=True)


def cost_runs(args, run) -> None:
    """Windows with the spans off and on over one set-up (see the module
    docstring)."""
    import numpy as np

    import traffic as tr
    from reference import replay

    from repro.obs import profile

    cell = run.load_cell(args.workload)
    config, traffic = cell["config_data"], cell["traffic_data"]
    run.use_compile_cache()
    run.devices(cell["chips"], True)
    warm_s = float(traffic["warmup_s"])
    n_windows = 2 * len(args.seeds)
    n_extra = (tr.inserts_needed(traffic, warm_s)
               + n_windows * tr.inserts_needed(traffic, args.seconds))
    rec = run.records_for(config, args.seeds[0], n_extra)
    run.warm_arena_programs(config)
    cluster, cfe, store = run.build(config)
    run.load_records(store, config, rec)
    harness = run.Harness(store, int(traffic["max_batch"]))
    ins = tr.KINDS.index(tr.INSERT)

    def phase(seed: int, seconds: float, first_extra: int):
        ops = tr.make_ops(traffic, rec, seed, seconds, first_extra=first_extra)
        done = harness.serve(ops, limit_s=seconds + 5.0)
        store.drain()
        return ops, done, first_extra + int(np.sum(ops.kind == ins))

    _, _, extra = phase(args.seeds[0] + 1, warm_s, 0)
    for i, seed in enumerate(args.seeds):
        for on in ((False, True) if i % 2 == 0 else (True, False)):
            profile.reset()
            if on:
                profile.enable()
            try:
                ops, done, extra = phase(seed, args.seconds, extra)
            finally:
                profile.disable()
            lat = np.sort(done - ops.due)
            print(json.dumps({
                "workload": args.workload, "seed": seed, "spans": on,
                "throughput_ops_s": float(np.sum(done <= args.seconds)) / args.seconds,
                "latency_p50_ms": float(lat[(len(lat) - 1) // 2]) * 1e3,
                "latency_p95_ms": float(lat[max(0, int(np.ceil(0.95 * len(lat))) - 1)]) * 1e3,
                "failed": int(np.sum(~np.isfinite(done))),
                "arena_reads": profile.snapshot().get("arena.reads", {}).get("count")}),
                flush=True)
    compared, wrong, first = replay(run.reference_for(config, rec), harness.events)
    print(json.dumps({"workload": args.workload, "answers_compared": compared,
                      "wrong_answers": wrong, "first_wrong": first,
                      "store_errors": harness.errors[:3]}), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cost", action="store_true")
    args = ap.parse_args(argv)
    import run

    (cost_runs if args.cost else traced_runs)(args, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
