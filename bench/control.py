#!/usr/bin/env python3
"""Read the numbers that decide ``correct`` over many seeds, for the store
as it is and for the control.

    python3 bench/control.py --workload <cell> --seeds s1,s2,... --seconds S \
        [--control mirror_lag]

Each seed is one whole run of the cell (``run.run_cell``: set-up, warm-up,
an ``S``-second window at the cell's rate, every check), one after another
in this process.  ``--control mirror_lag`` runs the control: the store's
own asynchronous replication path switched on (every mirror 64 writes
behind), which breaks the synchronous mirror that the configurations
state.  One JSON line per seed with each number compared; the benchmark's
runs never call this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys

import run

MIRROR_LAG_WRITES = 64


def mirror_lag(cluster, cfe, store) -> None:
    for be in cluster.blades.values():
        for m in be.mirrors:
            m.set_lag(MIRROR_LAG_WRITES)


CONTROLS = {"mirror_lag": mirror_lag}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", choices=sorted(CONTROLS))
    args = ap.parse_args(argv)
    tamper = CONTROLS[args.control] if args.control else None
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False, tamper=tamper)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "correct": out["correct"],
                          "failed": out["failed"], "attempted": out["attempted"],
                          "checks": out["checks"]}),
              flush=True)
        gc.collect()  # free the last run's arenas before the next build
    return 0


if __name__ == "__main__":
    sys.exit(main())
