"""CLI — pre-warm the dispatch cache, explain decisions, self-test.

Pre-warm (measure + persist winners for everything a config dispatches)::

    PYTHONPATH=src python -m repro.tune --configs paper_mlp,qwen2-7b \
        --m 2,256 --cache tune-cache.json

The warm path traces the model's forward with ``jax.eval_shape`` (no
FLOPs, no memory — trace-time dispatch records every cache miss with its
full shape spec), then benchmarks each recorded regime with synthetic
operands of exactly those shapes. Decode regimes are warmed from the
config's attention geometry under the default ``EngineConfig`` paging.

``--explain`` dumps the cache (keys, winners, per-candidate timings,
rejections) without measuring anything. ``--selftest-inject`` presents
sparselint's race-broken kernel as a tuned Pallas candidate and exits
non-zero when the SL101–SL105 gate rejects it — proof the gate has teeth,
wired into CI exactly like ``lint --selftest-inject``.
"""
from __future__ import annotations

import argparse
import json
import sys

from . import (clear_pending, get_cache, pending)
from . import cache as _cache
from . import certify as _certify
from . import tuner as _tuner


def _warm_mlp(m_list, args):
    """paper_mlp: the paper's 4-junction MNIST MLP (Table II row 0)."""
    import jax

    from ..configs.paper_mlp import MNIST_4J, TABLE2_MNIST, rho_from_dout
    from ..nn.mlp import MLPConfig, SparseMLP

    rho = rho_from_dout(MNIST_4J, TABLE2_MNIST[0][0])
    model = SparseMLP(MLPConfig(n_net=MNIST_4J, rho=rho,
                                mode="block_gather"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    for m in m_list:
        x = jax.ShapeDtypeStruct((m, MNIST_4J[0]), "float32")
        y = jax.ShapeDtypeStruct((m,), "int32")
        jax.eval_shape(model.loss, params, x, y)


def _warm_arch(name, m_list, args):
    import jax

    from ..configs import get_config
    from ..nn import build_model

    cfg = get_config(name, smoke=True)
    model = build_model(cfg)
    params = jax.eval_shape(model.init, jax.random.key(0))
    for m in m_list:
        b, s = (1, m) if m > 1 else (1, 1)
        tokens = jax.ShapeDtypeStruct((b, s), "int32")
        batch = {"tokens": tokens, "labels": tokens}
        if cfg.input_mode == "embeddings" or cfg.enc_dec is not None:
            batch["embeds"] = jax.ShapeDtypeStruct(
                (b, s, cfg.frontend_dim), "float32")
        jax.eval_shape(model.loss, params, batch)
    # decode regime: the serving engine's paged-attention geometry under
    # default EngineConfig paging
    heads = getattr(cfg, "n_heads", 0)
    if heads:
        from ..serving.engine import EngineConfig
        ec = EngineConfig()
        hkv = getattr(cfg, "n_kv_heads", heads) or heads
        from . import decide_decode
        decide_decode(b=ec.max_slots, h_kv=hkv, groups=heads // hkv,
                      head_dim=cfg.head_dim, page_size=ec.page_size,
                      n_pages=ec.max_pages_per_seq, pool=ec.total_pages,
                      quant=False, dtype="float32")


def _warm_pending(cache, args) -> int:
    specs = pending()
    n = 0
    for key, spec in specs.items():
        if cache.get(key) is not None:
            continue
        try:
            if spec["op"] == "paged_decode":
                ent = _tuner.bench_decode(
                    spec, cache=cache, iters=args.iters,
                    repeats=args.repeats,
                    interpret_pallas=args.interpret_pallas)
            else:
                ent = _tuner.bench_junction(
                    spec, cache=cache, iters=args.iters,
                    repeats=args.repeats,
                    interpret_pallas=args.interpret_pallas)
                if args.blocks:
                    _tuner.bench_tiles(
                        spec, [(64, 64), (128, 128), (256, 256)],
                        cache=cache, iters=args.iters,
                        repeats=args.repeats,
                        interpret_pallas=args.interpret_pallas)
        except Exception as e:  # noqa: BLE001 — warm what we can
            print(f"  {key}: SKIPPED ({type(e).__name__}: {e})")
            continue
        n += 1
        print(f"  {key}\n    -> {ent['backend']}"
              f"/{ent.get('dataflow', '-')} "
              f"({ent['speedup_vs_heuristic']}x vs heuristic, "
              f"score_by={ent.get('score_by')})")
    return n


def _explain(cache) -> dict:
    doc = {"path": cache.path, "schema": _cache.SCHEMA_VERSION,
           "load_error": cache.load_error, "n_entries": len(cache),
           "device": _cache.device_kind(), "entries": cache.entries}
    for key, ent in sorted(cache.entries.items()):
        extra = ""
        rej = [f"{lbl}:{','.join(i['rejected'])}"
               for lbl, i in ent.get("candidates", {}).items()
               if "rejected" in i]
        if rej:
            extra = f"  [rejected: {'; '.join(rej)}]"
        if "block_in" in ent and "backend" not in ent:
            print(f"{key}\n  -> tiles {ent['block_in']}x{ent['block_out']}"
                  f" ({ent.get('score_us')}us)")
        else:
            print(f"{key}\n  -> {ent.get('backend')}"
                  f"/{ent.get('dataflow', '-')}"
                  f" bm{ent.get('block_m') or 'derived'}"
                  f" ({ent.get('score_us')}us, "
                  f"{ent.get('speedup_vs_heuristic')}x vs "
                  f"{ent.get('heuristic')}){extra}")
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.tune",
        description="pre-warm / inspect the kernel dispatch cache")
    ap.add_argument("--configs", default="paper_mlp",
                    help="comma-separated config names (paper_mlp or any "
                         "registered arch) to pre-warm for")
    ap.add_argument("--m", default="2,256",
                    help="comma-separated M regimes (tokens) to trace")
    ap.add_argument("--cache", default=None,
                    help="cache file (default: REPRO_TUNE_CACHE or "
                         "<checkout>/.cache/tune_cache.json)")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--blocks", action="store_true",
                    help="also re-fit (bL, bR) tile shapes per junction "
                         "(consumed behind REPRO_TUNE_BLOCKS=1)")
    ap.add_argument("--interpret-pallas", action="store_true",
                    help="include Pallas candidates in interpret mode off "
                         "TPU (tests only — interpret timings do not "
                         "transfer to hardware)")
    ap.add_argument("--explain", action="store_true",
                    help="dump cached decisions and exit")
    ap.add_argument("--json", default=None,
                    help="also write the --explain dump to this file")
    ap.add_argument("--selftest-inject", action="store_true",
                    help="certification selftest: an injected race-broken "
                         "Pallas candidate must be REJECTED (exits "
                         "non-zero when the gate fires — has-teeth proof)")
    args = ap.parse_args(argv)

    if args.selftest_inject:
        ok, findings = _certify.certify_injected()
        if ok:
            print("selftest FAILED: injected illegal candidate was "
                  "accepted by the certification gate")
            return 0
        for f in findings:
            print(f"rejected: [{f.code}] {f.subject}: {f.message}")
        print("selftest: injected candidate rejected before benching "
              "(gate has teeth)")
        return 2

    cache = get_cache(args.cache)
    if cache.load_error:
        print(f"note: cache at {cache.path} unusable "
              f"({cache.load_error}); starting empty")

    if args.explain:
        doc = _explain(cache)
        if args.json:
            with open(args.json, "w") as fh:
                json.dump(doc, fh, indent=1, sort_keys=True)
        return 0

    clear_pending()
    for name in [c for c in args.configs.split(",") if c]:
        m_list = [int(v) for v in args.m.split(",") if v]
        print(f"tracing {name} (M regimes: {m_list}) ...")
        if name == "paper_mlp":
            _warm_mlp(m_list, args)
        else:
            _warm_arch(name, m_list, args)
    n_pend = len(pending())
    print(f"{n_pend} unseen regime(s); benchmarking candidates ...")
    n = _warm_pending(cache, args)
    print(f"warmed {n}/{n_pend} regimes -> {cache.path} "
          f"({len(cache)} entries)")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(_explain(cache), fh, indent=1, sort_keys=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
