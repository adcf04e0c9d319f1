"""Device facts and process set-up shared by the entry points.

``on_tpu`` is the one answer to "are we on a TPU?" for backend dispatch.
It does not catch errors: a backend that fails to initialise is a fault
to see, not a reason to run the junctions on XLA.

``init_compile_cache`` turns on JAX's persistent compilation cache. Entry
points call it (``launch.train``, ``launch.serve``, ``chip_smoke.py``);
importing a module never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# repo root: src/repro/device.py -> parents[2]
REPO_ROOT = Path(__file__).resolve().parents[2]

# fixed, gitignored directory inside the checkout for what the program
# caches: the tune cache's default file and, when the environment names no
# other place, JAX's compiled programs (the path is part of the compile
# cache's key, so it must not move)
CACHE_DIR = REPO_ROOT / ".cache"
COMPILE_CACHE_DIR = CACHE_DIR / "jax"


def on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def init_compile_cache() -> str:
    """Keep compiled programs across processes. ``JAX_COMPILATION_CACHE_DIR``
    wins when it is set (JAX reads it itself, so nothing else is set);
    otherwise the cache lives in ``COMPILE_CACHE_DIR``. Returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    COMPILE_CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
