#!/usr/bin/env python3
"""Run one benchmark cell once, in this process, and check every answer.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; its configuration (``bench/configs/<config>.json``) fixes the
cluster, the structure and its sizing, and its traffic
(``bench/workloads/<traffic>.json``) the op mix, key distribution and rate.
The run compiles every arena program a wave can need, builds the cluster
on the accelerator, loads the seeded records through the store's own
``put_many``, in one batch, runs the cell's own traffic as warm-up, then
offers the traffic open loop for ``--seconds``:
each iteration takes every op that is due, up to ``max_batch``, issues the
reads as one ``get_many``, each scan as one ``range_scan``, then the writes
as one ``put_many``.  An op's latency runs from its due time to the return
of the call that served it.  After the window the backlog is served (a
minute at most), the store drained and its answers replayed against the
plain reference (``bench/reference.py``), then read back from the blades
with the page caches emptied, and each mirror arena compared with its
primary.

Records.  Keys are 8-byte integers.  A configuration without a
``record`` group has 8-byte integer values (``value_bytes`` 8): the store
is handed ``int`` values and answers ``int``.  One with ``"record":
{"fieldcount": F, "fieldlength": L}`` has YCSB's records, ``value_bytes``
= F x L (else the run stops before it starts): the structure is built with
``value_bytes=F*L`` (a constructor without that parameter is refused
before the cluster is built), handed each record as ``bytes`` of exactly
that length, and has to answer ``bytes`` (or ``bytearray``) equal byte for
byte.  A read returns the whole record (YCSB's ``readallfields``).  An
insert writes a whole record; an update replaces one field (YCSB's
default, ``writeallfields`` false) and is a read-modify-write, as YCSB's
RocksDB binding does it: its key joins the batch's reads in the one
``get_many``, the harness replaces the field in the answer (two updates
of one key apply in op order), and the new records go out in the batch's
one ``put_many``, whose return completes the update.  The reference
checks that read as any other.  ``records_for`` and ``reference_for``
make the records and the reference for every entry point (this module,
``sweep.py``, ``program_spans.py``).

A traffic file may state a ``fault``, such as ``{"kind":
"blade_fails_permanently", "blade": 1, "at": 0.4}``: at that share of the
window, between two batches, the blade fails for good.  The store has to
find the failure itself, on its next call that needs the blade, and
promote the blade's mirror; the harness only records when the blade went
and which shards it held, and counts the promotions against the one the
file asks for.  After the window every key of the lost shards is read
back as well, from the promoted blade.  Without a ``fault`` none of this
runs.

With ``--trace 0`` the last line of standard output carries the cell's
end-to-end metrics; with ``--trace 1`` the store calls and the device arena
are wrapped (``bench/spans.py``), a few seconds of the window are traced
with the JAX profiler, and the line carries the cell's per-layer metrics
(``bench/metrics/<name>.py``).  The numbers compared for ``correct`` are the
last lines of standard error and the last key of the result line.

Exits nonzero, printing no result, where JAX finds no accelerator or fewer
devices than the cell asks for.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse
import importlib.util
import json
import os
import pathlib
import shutil
import sys
import traceback
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(ROOT / "src"))

import traffic as tr  # noqa: E402  (bench/traffic.py)
from reference import RecordReference, Reference, replay  # noqa: E402

DRAIN_LIMIT_S = 60.0        # an op due in the window may complete this late
READBACK_KEYS = 16384       # keys read back from the blades after the window
TRACE_START, TRACE_SECONDS = 0.4, 3.0   # traced sub-window: start share, length
FAULT_TRACE_LEAD_S = 1.0    # where a blade fails, the trace starts this long before
FAULT_KINDS = ("blade_fails_permanently",)
OUT_DIR = ROOT / ".bench_out"


class NoAccelerator(RuntimeError):
    pass


# ------------------------------------------------------------------ cells
def load_cell(name: str, index: pathlib.Path = ROOT / "BENCHMARK.json",
              workloads: pathlib.Path = BENCH_DIR / "workloads") -> Dict:
    """Cell `name` of `index`, with its configuration (its ``file`` taken
    from `index`'s directory) and its traffic file from `workloads`."""
    bench = json.loads(index.read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config_data"] = json.loads((index.parent / cfg_entry["file"]).read_text())
    cell["traffic_data"] = json.loads((workloads / f"{cell['traffic']}.json").read_text())
    if cell["traffic_data"]["config"] != cell["config"]:
        raise SystemExit(f"{cell['traffic']}.json is for {cell['traffic_data']['config']}, "
                         f"the cell for {cell['config']}")
    cell["end_to_end"] = [m for m in bench["end_to_end"]
                          if name in m.get("workloads", [name])]
    cell["per_layer"] = [m for m in bench["per_layer"]
                         if name in m.get("workloads", [name])]
    return cell


def use_compile_cache() -> None:
    """Persist every compiled program in ``<checkout>/.jax_cache`` (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), however quickly it compiled, so
    only a checkout's first run compiles."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def devices(chips: int, require_accelerator: bool):
    # the TPU runtime logs under /tmp unless told otherwise
    os.environ.setdefault("TPU_LOG_DIR", str(OUT_DIR / "tpu_logs"))
    import jax

    devs = jax.devices()
    if require_accelerator and (devs[0].platform == "cpu" or len(devs) < chips):
        raise NoAccelerator(f"cell needs {chips} accelerator chip(s); JAX found "
                            f"{len(devs)} {devs[0].platform} device(s)")
    return devs


# ------------------------------------------------------------------ store
def structure_class(name: str):
    """The store's class for a configuration's ``structure``."""
    from repro.cluster import ShardedBPTree, ShardedHashTable

    classes = {"hash": ShardedHashTable, "bptree": ShardedBPTree}
    if name not in classes:
        raise ValueError(f"unknown structure {name!r}")
    return classes[name]


def build(config: Dict):
    """The cluster, its client front-end and the structure, as the
    configuration states them.  Every field of its ``frontend`` group is
    passed to ``FEConfig`` through the named preset; a field ``FEConfig``
    lacks, or a record width the structure cannot take, is an error, so
    the file states exactly what runs."""
    import dataclasses
    import inspect

    from repro.cluster import ClusterFrontEnd, NVMCluster
    from repro.core import FEConfig

    cls = structure_class(config["structure"])
    kwargs = {"n_buckets": config["n_buckets"]} if config["structure"] == "hash" else {}
    shape = tr.record_shape(config)
    if shape is not None:
        if "value_bytes" not in inspect.signature(cls).parameters:
            raise ValueError("the store keeps 8-byte integer keys and values: "
                             f"{cls.__name__} takes no value_bytes")
        kwargs["value_bytes"] = shape.width
    cl = config["cluster"]
    cluster = NVMCluster(n_blades=cl["n_blades"], n_shards=cl["n_shards"],
                         num_mirrors=cl["num_mirrors"],
                         capacity_per_blade=cl["capacity_per_blade"])
    fe = dict(config["frontend"])
    preset = getattr(FEConfig, fe.pop("preset"))
    known = {f.name for f in dataclasses.fields(FEConfig)}
    if set(fe) - known:
        raise ValueError(f"FEConfig has no field {sorted(set(fe) - known)}")
    cfe = ClusterFrontEnd(cluster, preset(**fe), fe_id=0)
    return cluster, cfe, cls(cfe, "ycsb", **kwargs)


def warm_arena_programs(config: Dict, clone: bool = False) -> int:
    """Compile, before the cluster exists, every arena program a wave can
    need, so that none compiles in the window: for each power-of-two wave
    bucket a one-run read (slice), a many-run read (gather), a write
    (scatter) and a device copy into the mirrors, all through
    ``DeviceArena``'s own methods on scratch arenas of the cluster's size,
    which are freed before the cluster is built.  With `clone`, for a cell
    whose blade fails, also a whole-arena clone, the copy a mirror's
    promotion makes twice (``NVMBackend.promote_mirror``); the reads of
    the promoted blade's ``reboot`` fall in the buckets above.  Returns the
    arena programs compiled; where the arena's interface has changed, warms
    nothing and says so."""
    import gc

    from repro.core import devmem

    before = devmem.compiled_programs()
    cl = config["cluster"]
    try:
        arenas = [devmem.DeviceArena(cl["capacity_per_blade"])
                  for _ in range(1 + cl["num_mirrors"])]
        size = devmem.MIN_BUCKET
        while size <= devmem.MAX_BUCKET:
            a = arenas[0]
            a.read_runs([(0, size)])
            a.read_runs([(0, size // 2), (size, size // 2)])
            a.write_runs([(0, bytes(size))])
            a.flush()
            a.copy_runs(np.array([0]), np.array([size]), np.array([size]),
                        into=arenas[1:])
            size *= 2
        if clone:
            arenas.append(arenas[0].clone())
        for a in arenas:   # wait for their last programs, so they free at once
            a.read_runs([(0, 1)])
    except (AttributeError, TypeError) as e:
        print(f"arena programs not warmed: {e!r}", file=sys.stderr)
    a = arenas = None
    gc.collect()
    return devmem.compiled_programs() - before


def heap_bytes_in_use(cluster) -> int:
    """Bytes of the live primaries' heaps in use: the structure's nodes,
    its logs and the name table, not the arenas reserved around them."""
    return sum((be._next_fresh - len(be._free)) * be.block_size
               for be in cluster.blades.values() if be.alive)


def records_for(config: Dict, seed: int, n_extra: int) -> tr.Records:
    """The seeded records a run of `config` loads, 8-byte integers or
    records of the shape its ``record`` group states, and `n_extra` fresh
    keys for inserts.  A ``record`` group that does not add up to
    ``value_bytes`` is refused here, before the cluster is built."""
    return tr.make_records(seed, int(config["recordcount"]), n_extra,
                           tr.record_shape(config))


def reference_for(config: Dict, rec: tr.Records) -> Reference:
    """The plain reference over `rec`, as `config`'s structure answers:
    ordered for the B+tree's scans, byte for byte for records."""
    ordered = config["structure"] == "bptree"
    if rec.shape is None:
        return Reference(rec.keys.tolist(), rec.values.tolist(), ordered)
    return RecordReference(rec.keys.tolist(), rec.values, ordered)


def load_records(store, config: Dict, rec: tr.Records) -> None:
    """Insert every record in one ``put_many``, then drain: the hash table
    in load order, the B+tree in key order (its bulk-build path).  One batch
    is the store's cheapest load path."""
    keys, vals = rec.keys, rec.values
    if config["structure"] == "bptree":
        order = np.argsort(keys)
        keys, vals = keys[order], vals[order]
    vals = vals.tolist() if rec.shape is None else [v.tobytes() for v in vals]
    store.put_many(list(zip(keys.tolist(), vals)))
    store.drain()


class Harness:
    """The open-loop load generator: serves an op stream against the store
    on the wall clock and logs every call for the reference."""

    def __init__(self, store, max_batch: int, spans=None):
        self.store = store
        self.max_batch = max_batch
        self.spans = spans
        self.events: List[tuple] = []
        self.errors: List[str] = []

    def _call(self, name: str, fn: Callable):
        if self.spans is None:
            return fn()
        with self.spans.store(name):
            return fn()

    def serve(self, ops: tr.Ops, *, limit_s: float, hooks=()) -> np.ndarray:
        """Serve `ops` (due times in seconds from now); returns each op's
        completion time in seconds from the same origin, inf where it failed
        or was not served within `limit_s`.  ``hooks`` are (at_s, fn) pairs
        run once, between batches, when their time has come."""
        n = len(ops)
        done = np.full(n, np.inf)
        kinds = ops.kind
        read_c, upd_c = tr.KINDS.index(tr.READ), tr.KINDS.index(tr.UPDATE)
        scan_c, ins_c = tr.KINDS.index(tr.SCAN), tr.KINDS.index(tr.INSERT)
        hooks = sorted(hooks, key=lambda h: h[0])
        t0 = time.perf_counter()
        i = 0
        while i < n:
            now = time.perf_counter() - t0
            while hooks and now >= hooks[0][0]:
                hooks.pop(0)[1](t0)
                now = time.perf_counter() - t0
            if now > limit_s:
                break
            if ops.due[i] > now:
                time.sleep(min(ops.due[i] - now, hooks[0][0] - now if hooks else 1.0))
                continue
            j = min(int(np.searchsorted(ops.due, now, side="right")), i + self.max_batch)
            k = kinds[i:j]
            idx = np.arange(i, j)
            gets = k == read_c
            if ops.field is not None:   # one-field updates read their record first
                gets |= ops.field[i:j] >= 0
            reads = idx[gets]
            answered = ([], [])
            if len(reads):
                keys = ops.key[reads].tolist()
                ok, got = self._guard("get_many", lambda: self.store.get_many(keys))
                if ok:
                    self.events.append(("get", keys, got))
                    done[reads[k[gets] == read_c]] = time.perf_counter() - t0
                    answered = (keys, got)
            for s in idx[k == scan_c]:
                lo, hi = int(ops.key[s]), int(ops.hi[s])
                ok, rows = self._guard("range_scan", lambda: self.store.range_scan(lo, hi))
                if ok:
                    self.events.append(("scan", lo, hi, rows))
                    done[s] = time.perf_counter() - t0
            writes = idx[(k == upd_c) | (k == ins_c)]
            if ops.field is None:
                vals = ops.value[writes].tolist()
            else:
                writes, vals = self._new_records(ops, writes, answered)
            if len(writes):
                keys = ops.key[writes].tolist()
                ok, _ = self._guard("put_many",
                                    lambda: self.store.put_many(list(zip(keys, vals))))
                if ok:
                    self.events.append(("put", keys, vals))
                    done[writes] = time.perf_counter() - t0
            i = j
        return done

    @staticmethod
    def _new_records(ops: tr.Ops, writes: np.ndarray, answered):
        """The records a batch's writes put, in op order: a whole record as
        drawn, or one field replaced in the record the batch's ``get_many``
        answered, or an earlier write of the batch left.  A one-field
        update whose record did not come back whole cannot be built: it
        is not sent, and fails."""
        shape, latest = ops.shape, dict(zip(*answered))
        sent, vals = [], []
        for w in writes.tolist():
            key, f = int(ops.key[w]), int(ops.field[w])
            if f < 0:
                new = ops.data[w].tobytes()
            else:
                old = latest.get(key)
                if old is None or len(old) != shape.width:
                    continue
                part = shape.field(f)
                new = bytearray(old)
                new[part] = ops.data[w, part].tobytes()
                new = bytes(new)
            latest[key] = new
            sent.append(w)
            vals.append(new)
        return np.array(sent, np.int64), vals

    def _guard(self, name: str, fn: Callable):
        """(True, result), or (False, None) where the call raised: its ops
        count as failed and the run goes on."""
        try:
            return True, self._call(name, fn)
        except Exception:
            self.errors.append(f"{name}: {traceback.format_exc(limit=4)}")
            return False, None


# --------------------------------------------------------------- faults
def fault_hook(fault: Dict, seconds: float, cluster, harness: Harness, out: Dict):
    """The window's ``(at_s, fn)`` hook for a traffic file's `fault`: between
    two batches the blade fails for good, and `out` records when (seconds
    from the window's start), the shards it held then, and how many store
    calls the harness had logged.  Finding the failure and promoting the
    mirror is left to the store."""
    if fault["kind"] not in FAULT_KINDS:
        raise ValueError(f"unknown fault kind {fault['kind']!r}; known: {FAULT_KINDS}")
    blade = int(fault["blade"])
    if blade not in cluster.blades:
        raise ValueError(f"the cluster has no blade {blade}")

    def fail(t0):
        out["blade"] = blade
        out["shards"] = sorted(cluster.directory.shards_on(blade))
        cluster.blades[blade].fail_permanently()
        out["t"] = time.perf_counter() - t0
        out["events"] = len(harness.events)

    return float(fault["at"]) * seconds, fail


def on_shards(cfe, keys, shards) -> np.ndarray:
    """Which of `keys` the router puts on one of `shards`."""
    shard_of, lost = cfe.directory.shard_of, set(shards)
    return np.fromiter((shard_of(k) in lost for k in keys), bool, len(keys))


# --------------------------------------------------------------- checks
def clear_page_caches(cfe) -> None:
    for fe in cfe.fes.values():
        fe.cache.clear()


def readback(store, ref: Reference, keys: List[int]) -> int:
    """Read `keys` back through ``get_many``; mismatches against `ref`."""
    bad = 0
    for i in range(0, len(keys), 4096):
        part = keys[i:i + 4096]
        got = store.get_many(part)
        bad += sum(1 for k, g in zip(part, got) if g != ref.get(k))
    return bad


def mirror_bytes_differ(cluster) -> int:
    """Bytes in which a live blade's mirror arenas differ from its primary
    (the configuration states a synchronous mirror: none may)."""
    import jax
    import jax.numpy as jnp

    count = jax.jit(lambda a, b: jnp.sum(a != b, dtype=jnp.int32))
    total = 0
    for be in cluster.blades.values():
        if not be.alive:
            continue
        be.arena.flush()
        for m in be.mirrors:
            m.arena.flush()
            total += int(count(be.arena._array, m.arena._array))
    return total


# ---------------------------------------------------------------- metrics
def load_reader(name: str):
    """The reader of metric `name`: ``bench/metrics/<name>.py``, or, for a
    metric split by a suffix (``host_us_per_op.tail``), the reader of its
    quantity, ``bench/metrics/<base>.py``."""
    metrics = BENCH_DIR / "metrics"
    path = metrics / f"{name}.py"
    if not path.exists():
        path = metrics / f"{name.rsplit('.', 1)[0]}.py"
    if str(metrics) not in sys.path:
        sys.path.append(str(metrics))   # a reader may import another's
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peak_memory(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    return int(max(peaks))


# -------------------------------------------------------------------- run
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             require_accelerator: bool = True,
             config_overrides: Optional[Dict] = None,
             traffic_overrides: Optional[Dict] = None,
             tamper: Optional[Callable] = None,
             log=lambda *a: print(*a, file=sys.stderr)) -> Dict:
    """One run of cell `name`; returns the result line as a dict.
    ``config_overrides`` replace top-level keys of the configuration (and
    of its ``cluster``/``frontend`` groups), ``traffic_overrides`` keys of
    the traffic, and ``tamper(cluster, cfe, store)`` runs after the load:
    they serve the tests and the control."""
    cell = load_cell(name)
    config = cell["config_data"]
    for k, v in (config_overrides or {}).items():
        if isinstance(v, dict):
            config[k] = {**config[k], **v}
        else:
            config[k] = v
    traffic = {**cell["traffic_data"], **(traffic_overrides or {})}
    fault = traffic.get("fault")
    tr.record_shape(config)     # a record group that does not add up stops here
    setup: Dict[str, float] = {}

    t = time.perf_counter()
    use_compile_cache()
    devs = devices(cell["chips"], require_accelerator)
    setup["jax_init_s"] = time.perf_counter() - t
    from repro.core import devmem
    from repro.obs import profile

    t = time.perf_counter()
    warm_s = float(traffic["warmup_s"])
    n_extra = (tr.inserts_needed(traffic, warm_s) + tr.inserts_needed(traffic, seconds))
    rec = records_for(config, seed, n_extra)
    r_warm, r_win = tr.rngs(seed + 1, 2)
    warm_ops = tr.make_ops(traffic, rec, int(r_warm.integers(1 << 62)), warm_s)
    n_warm_ins = int(np.sum(warm_ops.kind == tr.KINDS.index(tr.INSERT)))
    win_ops = tr.make_ops(traffic, rec, int(r_win.integers(1 << 62)), seconds,
                          first_extra=n_warm_ins)
    setup["traffic_s"] = time.perf_counter() - t

    t = time.perf_counter()
    n_warmed = warm_arena_programs(config, clone=bool(fault))
    setup["arena_programs_s"] = time.perf_counter() - t
    t = time.perf_counter()
    cluster, cfe, store = build(config)
    setup["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    load_records(store, config, rec)
    setup["load_s"] = time.perf_counter() - t
    if tamper is not None:
        tamper(cluster, cfe, store)
    failovers0 = cluster.failovers

    spans = None
    if trace:
        from spans import Spans

        spans = Spans(failover=bool(fault))
        spans.install()
        profile.enable()
    t = time.perf_counter()
    harness = Harness(store, int(traffic["max_batch"]), spans)
    warm_done = harness.serve(warm_ops, limit_s=warm_s + DRAIN_LIMIT_S)
    store.drain()
    setup["warmup_s"] = time.perf_counter() - t
    setup_s = time.perf_counter() - T_PROCESS
    log("setup parts: " + json.dumps({k: round(v, 3) for k, v in setup.items()})
        + f", setup_s {setup_s:.3f}, arena programs warmed {n_warmed}, warm-up ops {len(warm_ops)}"
        f" ({int(np.sum(np.isfinite(warm_done)))} served), heap bytes in use on the"
        f" primaries {heap_bytes_in_use(cluster)}")

    # ------------------------------------------------------------ window
    programs0 = devmem.compiled_programs()
    stats0 = cfe.stats()["total"]
    span0 = spans.snapshot() if spans else None
    profile.reset()
    hooks = []
    traced: Dict = {}
    if trace:
        import jax

        trace_dir = OUT_DIR / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        # a fault is traced with its promotion, the tracer's start-up stall
        # served before the blade fails
        t_on = (max(0.0, float(fault["at"]) * seconds - FAULT_TRACE_LEAD_S) if fault
                else TRACE_START * seconds)
        t_off = t_on + min(TRACE_SECONDS, 0.3 * seconds)

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0   # host spans are the bench.* annotations

        def trace_on(t0):
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
            traced["ann"] = jax.profiler.TraceAnnotation("bench.traced_window")
            traced["ann"].__enter__()
            traced["bytes0"] = spans.arena_bytes

        def trace_off(t0):
            traced["bytes"] = spans.arena_bytes - traced["bytes0"]
            traced["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()

        hooks += [(t_on, trace_on), (t_off, trace_off)]
    faulted: Dict = {}
    if fault:
        hooks.append(fault_hook(fault, seconds, cluster, harness, faulted))
    done = harness.serve(win_ops, limit_s=seconds + DRAIN_LIMIT_S, hooks=hooks)
    if "ann" in traced and "bytes" not in traced:
        trace_off(None)
    compiled_in_window = devmem.compiled_programs() - programs0
    stats1 = cfe.stats()["total"]
    span1 = spans.snapshot() if spans else None
    prof = profile.snapshot()
    if spans:
        spans.uninstall()
        profile.disable()
    store.drain()
    memory_peak = peak_memory(devs)
    finite = done[np.isfinite(done)]
    log(f"window: {len(win_ops)} ops due in {seconds} s, arena programs compiled "
        f"in the window: {compiled_in_window}, last op served at "
        f"{finite.max() if len(finite) else float('nan'):.3f} s")

    # ------------------------------------------------------------ checks
    lat = done - win_ops.due
    failed = int(np.sum(~np.isfinite(done)))
    in_window = int(np.sum(done <= seconds))
    ref = reference_for(config, rec)
    compared, wrong, first = replay(ref, harness.events)
    written = sorted({k for ev in harness.events if ev[0] == "put" for k in ev[1]})
    rb = np.random.default_rng(seed % (1 << 64))
    sample = rec.keys[rb.choice(len(rec.keys), size=min(READBACK_KEYS, len(rec.keys)),
                                replace=False)].tolist()
    seen = set(written)
    rb_keys = (written + [k for k in sample if k not in seen])[:READBACK_KEYS]
    lost = None
    if faulted:
        # every key written, and every key of the lost shards, is read back:
        # the promoted blade has to hold each acknowledged write
        lost = on_shards(cfe, win_ops.key.tolist(), faulted["shards"])
        keys = rec.keys.tolist()
        on_lost = [k for k, on in zip(keys, on_shards(cfe, keys, faulted["shards"])) if on]
        seen = set(rb_keys)
        rb_keys += [k for k in dict.fromkeys(written + on_lost) if k not in seen]
        n_before = len({k for ev in harness.events[:faulted["events"]] if ev[0] == "put"
                        for k in ev[1]} & set(on_lost))
    clear_page_caches(cfe)
    readback_bad = readback(store, ref, rb_keys)
    mirror_bad = mirror_bytes_differ(cluster)
    promoted = cluster.failovers - failovers0
    asked = 1 if cell["traffic_data"].get("fault") else 0   # as the cell's file states
    if faulted:
        log(f"blade {faulted['blade']} failed at {faulted['t']:.3f} s holding shards "
            f"{faulted['shards']}; mirror promotions {promoted}; {len(on_lost)} keys "
            f"there, {n_before} of them written before the failure, all read back")
    for err in harness.errors[:5]:
        log("store call failed: " + err)
    if first:
        log("first wrong answer: " + first)
    checks = {
        "failed_ops": {"value": failed, "limit": 0},
        "wrong_answers": {"value": wrong, "limit": 0},
        "readback_mismatches": {"value": readback_bad, "limit": 0},
        "mirror_bytes_differ": {"value": mirror_bad, "limit": 0},
        "failovers_missing": {"value": abs(promoted - asked), "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"answers compared: {compared} in window and warm-up, "
        f"{len(rb_keys)} read back from the blades")

    # ------------------------------------------------------------ metrics
    metrics: Dict[str, Dict] = {}
    record = {
        "cell": name, "seconds": seconds, "ops": len(win_ops),
        "completed_in_window": in_window, "latency_s": lat,
        "due_s": win_ops.due, "done_s": done,
        "fault": dict(faulted, lost=lost) if faulted else None,
        "stats0": stats0, "stats1": stats1, "span0": span0, "span1": span1,
        "profile": prof, "traced_bytes": traced.get("bytes"),
    }
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    breakdown = None
    if trace:
        import trace_reduce

        path = trace_reduce.newest_xplane(str(OUT_DIR / "trace"))
        red = trace_reduce.reduce(trace_reduce.load(path)) if path else None
        record["trace"] = red
        record["peaks"] = json.loads((BENCH_DIR / "peaks.json").read_text())
        record["device_kind"] = devs[0].device_kind
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
        wanted = cell["per_layer"]
    else:
        wanted = cell["end_to_end"]
    for m in wanted:
        value = (setup_s if m["name"] == "setup_s"
                 else load_reader(m["name"])(record))
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    out = {"correct": bool(correct), "attempted": len(win_ops), "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']} (limit {c['limit']})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"bench/run.py: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
