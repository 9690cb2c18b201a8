"""Every cell end to end on the CPU at 2^10 records: the same harness,
store path and checks as on the chip, with the harness's look for an
accelerator skipped and the arenas cut to 16 MiB."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
SMALL = {"recordcount": 1024, "n_buckets": 1024,
         "cluster": {"capacity_per_blade": 1 << 24}, "frontend": {"cache_bytes": 4096}}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def small_run(cell, seed=20260, seconds=1.0, trace=False, traffic=None, **kw):
    kw.setdefault("log", lambda *a: None)
    return run.run_cell(cell, seed, seconds, trace, require_accelerator=False,
                        config_overrides=SMALL,
                        traffic_overrides={"warmup_s": 0.5, **(traffic or {})}, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal(cell):
    out = small_run(cell)
    keys = list(out)
    assert keys[:5] == RESULT_KEYS and keys[-1] == "checks"
    assert set(keys) <= set(RESULT_KEYS + ["breakdown", "checks"])
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    want = {m["name"] for m in BENCH["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0 and set(m) == {"value", "unit"}
    assert set(out["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert all(set(c) == {"value", "limit"} for c in out["checks"].values())


def test_arena_warm_up_covers_every_wave():
    from repro.core import devmem

    capacity = 1 << 24
    run.warm_arena_programs({"cluster": {"capacity_per_blade": capacity, "num_mirrors": 1}})
    before = devmem.compiled_programs()
    arenas = [devmem.DeviceArena(capacity) for _ in range(2)]
    sizes = np.random.default_rng(7).integers(1, 1 << 20, size=24).tolist()
    for n in sizes + [1, 64, 65, 1 << 19, (1 << 19) + 1]:
        arenas[0].read_runs([(0, n)])
        arenas[0].read_runs([(0, n), (n + 8, 3)])
        arenas[0].write_runs([(16, bytes(n))])
        arenas[0].flush()
        arenas[0].copy_runs(np.array([0]), np.array([n + 8]), np.array([n]),
                            into=arenas[1:])
    assert devmem.compiled_programs() == before


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal_reports_per_layer_metrics(cell):
    out = small_run(cell, trace=True)
    assert out["correct"] is True, out["checks"]
    layer = {m["name"] for m in BENCH["per_layer"] if cell in m["workloads"]}
    # device-trace metrics need a device plane, which a CPU trace lacks
    cpu_readable = {n for n in layer if not n.startswith(("arena_roofline", "device_idle"))}
    assert cpu_readable <= set(out["metrics"]) <= layer


def test_cli_refuses_without_accelerator(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run.BENCH_DIR / "run.py"), "--workload",
                        CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
