"""Persistent dispatch cache for the empirical autotuner.

One JSON file maps *tuning keys* — ``(op, M-regime, n_in, n_out, rho, E,
dtype/quant, device kind)`` strings — to measured winner configurations
(``{"backend", "dataflow", "block_m", ...timings}``). The cache is the
software analogue of the paper's per-board choice of the parallelism
degree ``z``: measured once per device, reused by every later process.

Contracts (ISSUE 10):

* versioned schema — a file written by a different ``SCHEMA_VERSION`` is
  ignored wholesale (graceful fallback to the static heuristic), never
  partially interpreted;
* atomic writes — ``save()`` writes a sibling temp file and ``os.replace``s
  it, so a concurrent reader sees either the old or the new cache, never a
  torn one;
* env-overridable path — ``REPRO_TUNE_CACHE=<path>`` relocates the file
  (default ``.cache/tune_cache.json`` in the checkout, gitignored: a stale
  file elsewhere on the machine cannot redirect dispatch);
* kill switch — ``REPRO_TUNE_DISABLE=1`` makes every lookup miss, which
  restores today's deterministic ``_resolve`` heuristic exactly;
* corruption tolerance — unreadable / truncated / non-JSON / wrong-schema
  files load as an empty cache (the error is kept on ``load_error`` for
  ``--explain``), they never raise into model code.
"""
from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Optional

SCHEMA_VERSION = 1

ENV_PATH = "REPRO_TUNE_CACHE"
ENV_DISABLE = "REPRO_TUNE_DISABLE"
ENV_BLOCKS = "REPRO_TUNE_BLOCKS"

# M-regime buckets stop here: XLA's large-M lowering is shape-stable well
# before this, so one entry serves everything beyond it.
_M_BUCKET_CAP = 4096


def disabled() -> bool:
    return os.environ.get(ENV_DISABLE, "") not in ("", "0")


def blocks_enabled() -> bool:
    """Tile refit is opt-in: a tuned ``(bL, bR)`` is a *different pattern*
    (different parameters/numerics), unlike the performance-only dispatch
    entries — so it never activates implicitly."""
    return os.environ.get(ENV_BLOCKS, "") not in ("", "0")


def default_path() -> str:
    p = os.environ.get(ENV_PATH)
    if p:
        return p
    from ..device import CACHE_DIR
    return str(CACHE_DIR / "tune_cache.json")


def device_kind() -> str:
    """Cache-key device id: platform plus hardware kind (decisions measured
    on one device class must not leak onto another)."""
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.device_kind}".replace(" ", "_")


def m_bucket(m: int) -> int:
    """Power-of-two M-regime bucket (1, 2, 4, ... cap). Decode batches and
    training batches land in different regimes without a per-M explosion."""
    m = max(1, int(m))
    b = 1
    while b < m and b < _M_BUCKET_CAP:
        b <<= 1
    return b


def _rho_str(rho: float) -> str:
    return f"{float(rho):.4g}"


def junction_key(*, m: int, n_in: int, n_out: int, rho: float, E: int = 0,
                 dtype: str = "float32", quant: bool = False,
                 form: str = "plain", device: Optional[str] = None) -> str:
    """Key for one ``csd_matmul`` dispatch regime. ``form`` is the dispatch
    form (plain/batched/sharded/quant...); sharded callers pass their
    *shard-local* ``n_in``/``n_out``/``rho`` so tuning follows
    ``partition_pattern`` shapes."""
    return (f"csd_spmm|{form}|m{m_bucket(m)}|in{int(n_in)}|out{int(n_out)}"
            f"|rho{_rho_str(rho)}|E{int(E)}|{dtype}|q{int(bool(quant))}"
            f"|{device or device_kind()}")


def decode_key(*, b: int, h_kv: int, groups: int, head_dim: int,
               page_size: int, n_pages: int, pool: int,
               quant: bool = False, dtype: str = "float32",
               device: Optional[str] = None) -> str:
    """Key for one ``paged_decode_attention`` regime (B bucketed like M)."""
    return (f"paged_decode|b{m_bucket(b)}|h{int(h_kv)}|g{int(groups)}"
            f"|d{int(head_dim)}|p{int(page_size)}|np{int(n_pages)}"
            f"|pool{int(pool)}|q{int(bool(quant))}|{dtype}"
            f"|{device or device_kind()}")


def tile_key(*, n_in: int, n_out: int, rho: float, E: int = 0,
             dtype: str = "float32", device: Optional[str] = None) -> str:
    """Key for a measured ``(bL, bR)`` tile refit of one junction family
    (no M axis: ``fit_block_pattern`` runs before any batch exists)."""
    return (f"fit_blocks|in{int(n_in)}|out{int(n_out)}|rho{_rho_str(rho)}"
            f"|E{int(E)}|{dtype}|{device or device_kind()}")


class TuneCache:
    """Dict-of-entries with tolerant load and atomic save."""

    def __init__(self, path: Optional[str] = None):
        self.path = path or default_path()
        self._lock = threading.Lock()
        self.entries: dict = {}
        self.load_error: Optional[str] = None

    def load(self) -> "TuneCache":
        self.entries, self.load_error = {}, None
        try:
            with open(self.path) as fh:
                doc = json.load(fh)
        except FileNotFoundError:
            return self
        except Exception as e:  # corrupt / truncated / unreadable
            self.load_error = f"{type(e).__name__}: {e}"
            return self
        if not isinstance(doc, dict):
            self.load_error = "cache root is not an object"
            return self
        if doc.get("schema") != SCHEMA_VERSION:
            self.load_error = (f"schema {doc.get('schema')!r} != "
                               f"{SCHEMA_VERSION} (ignored)")
            return self
        ent = doc.get("entries")
        if isinstance(ent, dict):
            self.entries = {k: v for k, v in ent.items()
                            if isinstance(v, dict)}
        return self

    def get(self, key: str) -> Optional[dict]:
        return self.entries.get(key)

    def put(self, key: str, value: dict, save: bool = True) -> None:
        with self._lock:
            self.entries[key] = dict(value)
        if save:
            self.save()

    def save(self) -> None:
        doc = {"schema": SCHEMA_VERSION, "entries": self.entries}
        d = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(d, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=d, prefix=".tune_cache.",
                                   suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump(doc, fh, indent=1, sort_keys=True)
            os.replace(tmp, self.path)  # atomic on POSIX
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise

    def __len__(self) -> int:
        return len(self.entries)


_CACHE: Optional[TuneCache] = None


def get_cache(path: Optional[str] = None) -> TuneCache:
    """Process-wide cache singleton. Re-resolves the path on every call so
    tests (and ``REPRO_TUNE_CACHE`` changes) take effect immediately."""
    global _CACHE
    want = path or default_path()
    if _CACHE is None or _CACHE.path != want:
        _CACHE = TuneCache(want).load()
    return _CACHE


def reset_cache() -> None:
    global _CACHE
    _CACHE = None
