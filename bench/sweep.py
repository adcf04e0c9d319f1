#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop serving cell sustains: one
process, the cell run at each rate in turn (no reference check):

    python3 bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \\
        [--seconds 40] [--seed 1]

For each rate it prints the TTFT median and 90th percentile, the
requests due and finished, and how long the engine needed after the
window closed to give every request due in it its first token (a queue
that grows shows as a drain that grows with the rate). The cell's rate is
set once from this, at about 0.8 of the highest sustained rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".cache" / "jax")
    os.environ.pop("REPRO_TUNE_BLOCKS", None)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench.harness import common, lookup, serve

    cell = lookup.find_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = common.require_chips(cell.chips)
    for rate in [float(r) for r in args.rates.split(",")]:
        cell.cell["rate_per_s"] = rate
        a = argparse.Namespace(workload=cell.name, seed=args.seed,
                               seconds=args.seconds, trace=0)
        r, _ = serve.run(cell, a, devs, time.perf_counter(), check=False)
        t = 1e3 * np.asarray(r["ttft_s"])
        line = {"rate": rate, "due": r["attempted"], "failed": r["failed"],
                "finished": r["finished"], "drain_s": r["drain_s"],
                "preempted": r["preempted"],
                "ttft_p50_ms": float(np.median(t)) if len(t) else None,
                "ttft_p90_ms": float(np.percentile(t, 90)) if len(t)
                else None}
        print("SWEEP " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
