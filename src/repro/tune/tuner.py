"""Candidate enumeration + measurement for the autotuner.

For one dispatch regime (a cache key), the tuner builds synthetic operands
of the recorded shapes, times every *legal* candidate configuration with
the same ``obs.trace.timed_call`` core the benchmarks use (best-of-k
median with explicit warm-up — tuning and benching cannot disagree about
the clock), and persists the winner.

Candidate axes (the software form of the paper's flexible ``z``):

* backend ∈ {xla, dense, pallas} — ``dense`` is the escape hatch for
  regimes where structured sparsity loses to one cuBLAS/Eigen-style GEMM
  (ρ=0.5 on CPU); it is only legal for the plain/batched unquantized
  junction. Pallas candidates appear on TPU (or under
  ``interpret_pallas=True`` in tests) and must pass the SL101–SL105
  certification gate (``certify.py``) *before* they are benchmarked.
* dataflow ∈ {gather, scatter} for the XLA lowering — scatter gathers
  weights instead of activations, so it is M-independent and wins the
  skinny-M decode regime where gather falls off a cliff.
* block_m for Pallas grids: the forward's derived tiling
  (``csd_spmm.fwd_tiling``, ``block_m`` None) or a fixed row block.

Scoring: skinny-M regimes (M ≤ 32 — decode) score by forward time; larger
regimes (training/prefill) score by a full ``value_and_grad`` step so the
dx/dw sweeps weigh in. Both timings are kept in the entry for
``--explain``.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

from ..device import on_tpu
from ..obs import metrics as _obs_metrics
from ..obs import trace as _obs_trace
from . import cache as _cache
from . import certify as _certify

# M at or below this is the decode regime: score candidates by forward
# time only (no backward runs at decode).
SKINNY_M = 32

# None: the row block and fan-in chunk the forward derives from the shapes
PALLAS_BLOCK_MS = (None, 128, 256)


@dataclasses.dataclass(frozen=True)
class Candidate:
    backend: str
    dataflow: str = "gather"
    block_m: Optional[int] = None

    @property
    def label(self) -> str:
        if self.backend == "pallas":
            return f"pallas/bm{self.block_m or 'derived'}"
        if self.backend == "dense":
            return "dense"
        return f"xla/{self.dataflow}"


def junction_candidates(*, quant: bool = False, sharded: bool = False,
                        interpret_pallas: bool = False) -> List[Candidate]:
    cands = [Candidate("xla", "gather"), Candidate("xla", "scatter")]
    if not quant and not sharded:
        # dense-ref escape hatch: densify the slab (static take) + one
        # GEMM. No sharded/quant form — those contracts are slab-only.
        cands.append(Candidate("dense"))
    if on_tpu() or interpret_pallas:
        for bm in PALLAS_BLOCK_MS:
            cands.append(Candidate("pallas", "gather", bm))
    return cands


def _heuristic_candidate() -> Candidate:
    """What today's static ``_resolve("auto")`` would pick — the baseline
    every tuned decision is compared against."""
    return Candidate("pallas" if on_tpu() else "xla", "gather")


def _reg():
    return _obs_metrics.get_registry()


def _record_win(key: str, entry: dict) -> None:
    reg = _reg()
    reg.counter(
        "repro_tune_benched_total",
        "tuning runs completed, by op").inc(op=key.split("|", 1)[0])
    reg.gauge(
        "repro_tune_speedup",
        "measured winner speedup over the static heuristic, per key",
    ).set(entry.get("speedup_vs_heuristic", 1.0), key=key)


def bench_junction(spec: dict, *, cache: Optional[_cache.TuneCache] = None,
                   iters: int = 3, repeats: int = 2,
                   interpret_pallas: bool = False,
                   save: bool = True) -> dict:
    """Measure all legal candidates for one junction regime; cache and
    return the winning entry.

    ``spec`` fields: ``m, n_in, n_out, rho, E, dtype, quant, form,
    block_in, block_out`` (the exact dict ``decide_junction`` records on a
    miss). Sharded forms are benched on a plain pattern of the shard-local
    dims — same shapes, same density, pallas/xla candidates only — and the
    one decision applies uniformly across shards.
    """
    import jax
    import jax.numpy as jnp

    from ..core.block_pattern import make_block_pattern
    from ..kernels import ops

    m = int(spec["m"])
    n_in, n_out = int(spec["n_in"]), int(spec["n_out"])
    rho = float(spec["rho"])
    E = int(spec.get("E", 0))
    quant = bool(spec.get("quant", False))
    form = str(spec.get("form", "plain"))
    dtype = jnp.dtype(spec.get("dtype", "float32"))
    bi = int(spec.get("block_in", 128))
    bo = int(spec.get("block_out", 128))
    sharded = "sharded" in form

    key = _cache.junction_key(m=m, n_in=n_in, n_out=n_out, rho=rho, E=E,
                              dtype=str(dtype), quant=quant, form=form)
    bp = make_block_pattern(n_in, n_out, bp_rho_cap(rho),
                            block_in=bi, block_out=bo, seed=0)

    lead = (E,) if E > 0 else ()
    kx = jax.random.key(0)
    x = jax.random.normal(kx, lead + (m, n_in)).astype(dtype)
    w = jax.random.normal(
        jax.random.key(1),
        lead + (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out),
    ).astype(dtype) * 0.02
    w_scale = None
    if quant:
        from ..core.quant import quantize_slab
        w, w_scale = quantize_slab(w)

    heuristic = _heuristic_candidate()
    results: dict = {}
    best = None
    score_by = "fwd" if (m <= SKINNY_M or quant) else "step"

    for cand in junction_candidates(quant=quant, sharded=sharded,
                                    interpret_pallas=interpret_pallas):
        info: dict = {}
        results[cand.label] = info
        if cand.backend == "pallas":
            ok, findings = _certify.certify_junction(
                bp, m, cand.block_m, E=E, dtype=dtype)
            if not ok:
                info["rejected"] = sorted({f.code for f in findings})
                _reg().counter(
                    "repro_tune_rejected_total",
                    "pallas candidates rejected by SL101-SL105, by code",
                ).inc(codes=",".join(info["rejected"]))
                continue
        interpret = cand.backend == "pallas" and not on_tpu()
        kw = dict(backend=cand.backend, dataflow=cand.dataflow,
                  block_m=cand.block_m, interpret=interpret)
        try:
            fwd = jax.jit(lambda x, w: ops.csd_matmul(
                x, w, bp, w_scale=w_scale, **kw))
            info["us_fwd"] = round(_obs_trace.timed_call(
                fwd, x, w, iters=iters, warmup=1, repeats=repeats,
                name=f"tune/{key}/{cand.label}/fwd"), 2)
            if score_by == "step":
                def loss(w, x):
                    return jnp.mean(ops.csd_matmul(x, w, bp, **kw) ** 2)
                step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
                info["us_step"] = round(_obs_trace.timed_call(
                    step, w, x, iters=iters, warmup=1, repeats=repeats,
                    name=f"tune/{key}/{cand.label}/step"), 2)
        except Exception as e:  # a candidate that cannot run never wins
            info["error"] = f"{type(e).__name__}: {e}"
            info.pop("us_fwd", None)
            continue
        score = info.get("us_step", info.get("us_fwd"))
        info["score_us"] = score
        if best is None or score < best[0]:
            best = (score, cand)

    if best is None:
        raise RuntimeError(f"no runnable candidate for {key}")
    score, cand = best
    h_info = results.get(heuristic.label, {})
    h_score = h_info.get("score_us", score)
    entry = {
        "backend": cand.backend,
        "dataflow": cand.dataflow,
        "block_m": cand.block_m,
        "block_in": bi,
        "block_out": bo,
        "score_us": score,
        "score_by": score_by,
        "heuristic": heuristic.label,
        "speedup_vs_heuristic": round(h_score / score, 3) if score else 1.0,
        "candidates": results,
    }
    if cache is not None:
        cache.put(key, entry, save=save)
    _record_win(key, entry)
    return entry


def bp_rho_cap(rho: float) -> float:
    """make_block_pattern treats rho as a fan-in fraction; clamp into its
    valid (0, 1] range (recorded densities are already in-range — this
    guards float drift like 1.0000001 from ``d_in_b / n_lb``)."""
    return max(min(rho, 1.0), 1e-6)


def bench_decode(spec: dict, *, cache: Optional[_cache.TuneCache] = None,
                 iters: int = 3, repeats: int = 2,
                 interpret_pallas: bool = False,
                 save: bool = True) -> dict:
    """Measure decode-attention backends for one paged-KV regime.

    The Pallas decode kernel has no tunable grid knobs (its pages per grid
    step come from the shapes, ``decode_pages_per_step``), so the
    candidate axis is backend only; the
    shipped kernel itself is certified by sparselint's CI gate.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..kernels.flash_attention import paged_decode_attention

    b = int(spec["b"])
    h_kv, groups = int(spec["h_kv"]), int(spec["groups"])
    dh = int(spec["head_dim"])
    page, npages = int(spec["page_size"]), int(spec["n_pages"])
    pool = int(spec["pool"])
    quant = bool(spec.get("quant", False))
    dtype = jnp.dtype(spec.get("dtype", "float32"))

    key = _cache.decode_key(b=b, h_kv=h_kv, groups=groups, head_dim=dh,
                            page_size=page, n_pages=npages, pool=pool,
                            quant=quant, dtype=str(dtype))
    q = jax.random.normal(jax.random.key(0), (b, h_kv, groups, dh)
                          ).astype(dtype)
    kp = jax.random.normal(jax.random.key(1), (pool, page, h_kv, dh)
                           ).astype(dtype)
    vp = jax.random.normal(jax.random.key(2), (pool, page, h_kv, dh)
                           ).astype(dtype)
    k_scale = v_scale = None
    if quant:
        amax = jnp.max(jnp.abs(kp), axis=(2, 3))
        k_scale = (amax / 127.0 + 1e-8).astype(jnp.float32)
        v_scale = k_scale
        kp = jnp.clip(jnp.round(kp / k_scale[:, :, None, None]),
                      -127, 127).astype(jnp.int8)
        vp = jnp.clip(jnp.round(vp / v_scale[:, :, None, None]),
                      -127, 127).astype(jnp.int8)
    # half-full rows: pages handed out round-robin from the pool
    used = max(1, npages // 2)
    table = np.full((b, npages), -1, np.int32)
    for r in range(b):
        table[r, :used] = [(r * used + j) % pool for j in range(used)]
    lengths = np.full((b,), used * page - page // 2, np.int32)
    table, lengths = jnp.asarray(table), jnp.asarray(lengths)

    backends = ["xla"] + (["pallas"] if (on_tpu() or interpret_pallas)
                          else [])
    results: dict = {}
    best = None
    for be in backends:
        interpret = be == "pallas" and not on_tpu()
        fn = jax.jit(lambda q, kp, vp, t, ln, be=be, i=interpret:
                     paged_decode_attention(
                         q, kp, vp, t, ln, backend=be, interpret=i,
                         k_scale=k_scale, v_scale=v_scale))
        info: dict = {}
        results[be] = info
        try:
            info["us_fwd"] = round(_obs_trace.timed_call(
                fn, q, kp, vp, table, lengths, iters=iters, warmup=1,
                repeats=repeats, name=f"tune/{key}/{be}"), 2)
        except Exception as e:
            info["error"] = f"{type(e).__name__}: {e}"
            continue
        if best is None or info["us_fwd"] < best[0]:
            best = (info["us_fwd"], be)
    if best is None:
        raise RuntimeError(f"no runnable decode candidate for {key}")
    h = "pallas" if on_tpu() else "xla"
    h_us = results.get(h, {}).get("us_fwd", best[0])
    entry = {
        "backend": best[1],
        "score_us": best[0],
        "score_by": "fwd",
        "heuristic": h,
        "speedup_vs_heuristic": round(h_us / best[0], 3) if best[0] else 1.0,
        "candidates": results,
    }
    if cache is not None:
        cache.put(key, entry, save=save)
    _record_win(key, entry)
    return entry


def bench_tiles(spec: dict, tiles, *,
                cache: Optional[_cache.TuneCache] = None,
                iters: int = 3, repeats: int = 2,
                interpret_pallas: bool = False,
                save: bool = True) -> dict:
    """Re-fit the junction's ``(bL, bR)`` tile shape by measurement.

    Benches the full candidate set at every legal tile (each run also
    populates that tile's dispatch entries) and records the winning tile
    under the M-free ``fit_blocks`` key that ``fit_block_pattern``
    consults behind ``REPRO_TUNE_BLOCKS=1``.
    """
    n_in, n_out = int(spec["n_in"]), int(spec["n_out"])
    rho, E = float(spec["rho"]), int(spec.get("E", 0))
    dtype = str(spec.get("dtype", "float32"))
    min_b = 32
    per_tile: dict = {}
    best = None
    seen = set()
    base = (int(spec.get("block_in", 128)), int(spec.get("block_out", 128)))
    for bi, bo in [base] + [t for t in tiles if tuple(t) != base]:
        bi, bo = int(bi), int(bo)
        if (bi, bo) in seen:
            continue
        seen.add((bi, bo))
        if n_in % bi or n_out % bo or bi < min_b or bo < min_b:
            per_tile[f"{bi}x{bo}"] = {"skipped": "illegal tile"}
            continue
        sub = dict(spec, block_in=bi, block_out=bo)
        try:
            ent = bench_junction(sub, cache=cache, iters=iters,
                                 repeats=repeats,
                                 interpret_pallas=interpret_pallas,
                                 save=save)
        except Exception as e:
            per_tile[f"{bi}x{bo}"] = {"error": f"{type(e).__name__}: {e}"}
            continue
        per_tile[f"{bi}x{bo}"] = {"score_us": ent["score_us"],
                                  "backend": ent["backend"]}
        if best is None or ent["score_us"] < best[0]:
            best = (ent["score_us"], bi, bo)
    if best is None:
        raise RuntimeError(f"no legal tile for {n_in}x{n_out}")
    entry = {"block_in": best[1], "block_out": best[2],
             "score_us": best[0], "per_tile": per_tile}
    key = _cache.tile_key(n_in=n_in, n_out=n_out, rho=rho, E=E, dtype=dtype)
    if cache is not None:
        cache.put(key, entry, save=save)
    return entry
