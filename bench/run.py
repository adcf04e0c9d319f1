#!/usr/bin/env python3
"""Run one benchmark cell on the chip(s) of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration, traffic and limits by the names in
``BENCHMARK.json``, makes weights and inputs from ``--seed``, warms up
every shape the window uses (set-up), measures for ``--seconds``, checks
what the timed path produced against the float32 reference, and prints
one JSON line last on standard output. ``--trace 1`` takes a profiler
trace of part of the window and reports the cell's per-layer metrics in
place of its end-to-end ones.

It needs a TPU: with no TPU, or fewer chips than the cell asks for, it
exits non-zero and prints no result. JAX's compilation cache lives in the
checkout's ``.cache/jax``, whatever the environment says.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from pathlib import Path

T_START = time.perf_counter()
ROOT = Path(__file__).resolve().parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    # fixed, in the checkout: the path is part of the cache's key
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".cache" / "jax")
    # the tile is part of the model: no measured tile re-fit
    os.environ.pop("REPRO_TUNE_BLOCKS", None)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

    from bench.harness import common, lookup

    cell = lookup.find_cell(args.workload)
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      os.environ["JAX_COMPILATION_CACHE_DIR"])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devs = common.require_chips(cell.chips)
    except common.NoChip as e:
        print(str(e), file=sys.stderr, flush=True)
        return 3
    common.log(f"[device] {devs[0].platform} {devs[0].device_kind} x "
               f"{len(devs)}; workload {cell.name}, seed {args.seed}, "
               f"{args.seconds:g} s, trace {args.trace}")
    harness = __import__(f"bench.harness.{cell.kind}",
                         fromlist=["run"])
    result, checks = harness.run(cell, args, devs, T_START)
    common.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
