"""repro.tune — empirical kernel autotuner with a persistent dispatch cache.

``backend="auto"`` in ``kernels.ops.csd_matmul`` and
``kernels.flash_attention.paged_decode_attention`` consults this module at
trace time: a cache hit dispatches the *measured* winner configuration for
the call's regime, a miss (or ``REPRO_TUNE_DISABLE=1``, or a corrupt /
wrong-schema cache file) falls back to the static heuristic the repo
always had — tuning can only change which legal backend runs, never the
semantics (each backend's output is bit-identical whether it was chosen
explicitly or by the cache; the custom VJP and sharding contracts are
untouched).

Layout: ``cache.py`` (keys + versioned on-disk JSON), ``tuner.py``
(candidate enumeration + measurement), ``certify.py`` (SL101–SL105 gate
on Pallas candidates, pre-bench), ``__main__.py`` (CLI:
``python -m repro.tune`` pre-warms, ``--explain`` dumps decisions).

Misses are recorded (key -> full shape spec) so the CLI can pre-warm
exactly the regimes a traced model actually dispatches:
``jax.eval_shape`` a forward pass, then ``tuner.bench_*`` each pending
spec.
"""
from __future__ import annotations

from typing import Optional

from ..device import on_tpu
from ..obs import metrics as _obs_metrics
from . import cache as _cache
from .cache import (SCHEMA_VERSION, TuneCache, blocks_enabled,  # noqa: F401
                    decode_key, default_path, device_kind, disabled,
                    get_cache, junction_key, m_bucket, reset_cache,
                    tile_key)

# key -> spec dict for every lookup that missed (the CLI's warm worklist)
_PENDING: dict = {}


def pending() -> dict:
    return dict(_PENDING)


def clear_pending() -> None:
    _PENDING.clear()


def _count(op: str, outcome: str) -> None:
    _obs_metrics.get_registry().counter(
        "repro_tune_lookup_total",
        "autotuner cache lookups by op/outcome (counted at trace time)",
    ).inc(op=op, outcome=outcome)


def _count_decision(op: str, entry: dict) -> None:
    _obs_metrics.get_registry().counter(
        "repro_tune_decision_total",
        "tuned dispatch decisions applied, by op/backend/dataflow",
    ).inc(op=op, backend=entry.get("backend", "?"),
          dataflow=entry.get("dataflow", "-"))


def decide_junction(*, m: int, n_in: int, n_out: int, rho: float,
                    E: int = 0, dtype: str = "float32",
                    quant: bool = False, form: str = "plain",
                    block_in: int = 128, block_out: int = 128
                    ) -> Optional[dict]:
    """Measured dispatch decision for one ``csd_matmul`` regime, or
    ``None`` (miss / disabled / illegal entry) — the caller then falls
    back to the static heuristic. Called at trace time only."""
    if _cache.disabled():
        _count("csd_spmm", "disabled")
        return None
    key = _cache.junction_key(m=m, n_in=n_in, n_out=n_out, rho=rho, E=E,
                              dtype=dtype, quant=quant, form=form)
    ent = get_cache().get(key)
    if ent is None:
        _count("csd_spmm", "miss")
        _PENDING.setdefault(key, dict(
            op="csd_spmm", m=int(m), n_in=int(n_in), n_out=int(n_out),
            rho=float(rho), E=int(E), dtype=str(dtype), quant=bool(quant),
            form=str(form), block_in=int(block_in),
            block_out=int(block_out)))
        return None
    allowed = {"pallas", "xla"} if (quant or "sharded" in form) \
        else {"pallas", "xla", "dense"}
    be = ent.get("backend")
    if be not in allowed or (be == "pallas" and not on_tpu()) \
            or ent.get("dataflow", "gather") not in ("gather", "scatter"):
        _count("csd_spmm", "invalid")
        return None
    _count("csd_spmm", "hit")
    _count_decision("csd_spmm", ent)
    return ent


def decide_decode(*, b: int, h_kv: int, groups: int, head_dim: int,
                  page_size: int, n_pages: int, pool: int,
                  quant: bool = False, dtype: str = "float32"
                  ) -> Optional[dict]:
    """Measured backend for one paged-decode regime, or ``None``."""
    if _cache.disabled():
        _count("paged_decode", "disabled")
        return None
    key = _cache.decode_key(b=b, h_kv=h_kv, groups=groups,
                            head_dim=head_dim, page_size=page_size,
                            n_pages=n_pages, pool=pool, quant=quant,
                            dtype=dtype)
    ent = get_cache().get(key)
    if ent is None:
        _count("paged_decode", "miss")
        _PENDING.setdefault(key, dict(
            op="paged_decode", b=int(b), h_kv=int(h_kv),
            groups=int(groups), head_dim=int(head_dim),
            page_size=int(page_size), n_pages=int(n_pages),
            pool=int(pool), quant=bool(quant), dtype=str(dtype)))
        return None
    be = ent.get("backend")
    if be not in ("pallas", "xla") or (be == "pallas" and not on_tpu()):
        _count("paged_decode", "invalid")
        return None
    _count("paged_decode", "hit")
    _count_decision("paged_decode", ent)
    return ent


def decide_tile(*, n_in: int, n_out: int, rho: float, E: int = 0,
                dtype: str = "float32") -> Optional[dict]:
    """Measured ``(bL, bR)`` tile for one junction family. Gated on
    ``REPRO_TUNE_BLOCKS=1`` (a tuned tile is a different pattern — new
    parameters, new numerics — so it never activates implicitly)."""
    if _cache.disabled() or not _cache.blocks_enabled():
        return None
    key = _cache.tile_key(n_in=n_in, n_out=n_out, rho=rho, E=E,
                          dtype=dtype)
    ent = get_cache().get(key)
    if ent is None or "block_in" not in ent or "block_out" not in ent:
        _count("fit_blocks", "miss" if ent is None else "invalid")
        return None
    _count("fit_blocks", "hit")
    return ent
