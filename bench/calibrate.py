#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, for one cell, in
one process (set-up is long, so the seeds share it):

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 1,2,3] [--fault NAME --fault-seeds 4,5,6] \\
        [--seconds 15]

For each seed it runs the cell as ``bench/run.py`` does (a short window at
the cell's own load and sizes) and prints one JSON line of the numbers
compared: the program's readings (the lower ends of the limits); with
``--control-seeds`` the control's, a run of the program's own path one
step below the configured precision (int8 serving, bfloat16 master
weights in training: the upper ends); with ``--fault`` the readings of a
planted fault (``bench/harness/faults.py``). The benchmark's own runs
never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".cache" / "jax")
    os.environ.pop("REPRO_TUNE_BLOCKS", None)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench.harness import common, faults, lookup

    cell = lookup.find_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = common.require_chips(cell.chips)
    harness = __import__(f"bench.harness.{cell.kind}", fromlist=["run"])
    table = faults.SERVE if cell.kind == "serve" else faults.TRAIN

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    plan = [(s, None) for s in seeds(args.seeds)]
    plan += [(s, "control") for s in seeds(args.control_seeds)]
    plan += [(s, args.fault) for s in seeds(args.fault_seeds)]
    for seed, kind in plan:
        a = argparse.Namespace(workload=cell.name, seed=seed,
                               seconds=args.seconds, trace=0)
        t = time.perf_counter()
        result, checks = harness.run(
            cell, a, devs, t, control=kind == "control",
            fault=table[kind] if kind not in (None, "control") else None)
        line = {"seed": seed, "kind": kind or "program",
                "correct": result["correct"],
                "checks": {k: v["value"] for k, v in checks.items()},
                "seconds": time.perf_counter() - t}
        if "gap_stats" in result:
            line["gap_stats"] = result["gap_stats"]
        print("CALIB " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
