#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip, through the entry points a
user calls, at published widths with random weights made from a seed.

Phases, in order; any failure ends the script with a non-zero exit:

* device   — require a TPU (no CPU fallback); print its kind and count.
* serve    — qwen2_7b, bf16 parameters, through ``ServingEngine`` with
             ``backend="auto"``: 4 seeded prompts of 128..512 tokens, 32
             greedy tokens each. Every token is in the vocabulary, every
             logit is finite, and one request's paged-decode logits agree
             with ``LM.forward`` on the XLA path within ``LOGIT_BOUND``.
* train    — granite_moe_1b_a400m through ``Trainer.fit``: 3 steps at
             8 x 1024 tokens; loss and gradient norm finite every step.
* dispatch — every junction and the paged decode ran on Pallas (not XLA,
             dense or the interpreter); prints the dispatch and tune
             lookup counters.

Depth is the only thing cut, and only where the compiled step's
``memory_analysis`` says the published depth does not fit
``MEMORY_SHARE`` of the chip's memory; the cut is printed.

    python3 chip_smoke.py              # one chip, the phases above
    python3 chip_smoke.py --four-chip  # four chips: qwen2_7b served on a
                                       # 4-way model axis vs the one-chip
                                       # engine, greedy token parity

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# share of the chip's memory (``bytes_limit``) a compiled step may need:
# the rest is headroom for what lives beside it (the XLA reference
# forward, allocator fragmentation)
MEMORY_SHARE = 0.9
# engine paged-decode logits vs LM.forward on the XLA path, both bf16:
# the two differ in accumulation order and precision (Pallas tiles
# accumulate in f32; XLA junction slots and attention chunks differently),
# and the difference compounds over depth. Bound: max |delta| over one
# request's decode positions <= LOGIT_BOUND * max |reference logit|.
LOGIT_BOUND = 0.1
PROMPT_LENS = (128, 256, 384, 512)
NEW_TOKENS = 32
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 8, 1024, 3


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {msg}")


# ---------------------------------------------------------------------------
# memory: cut depth only to what fits
# ---------------------------------------------------------------------------


def on_device(device, tree):
    """Abstract arrays of ``tree`` placed on ``device``: what a compile
    for that device needs, without allocating anything."""
    import jax
    from jax.sharding import SingleDeviceSharding

    sh = SingleDeviceSharding(device)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree)


def program_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def fit_depth(label: str, published: int, need, budget: int) -> int:
    """Largest depth <= ``published`` whose step needs <= ``budget`` bytes.
    ``need(n)`` compiles the step at depth ``n`` and returns its bytes.
    Two probe depths give the bytes per layer; the pick is compiled again
    and stepped down until the compiler's own analysis says it fits."""
    lo, hi = (2, 4) if published >= 4 else (1, published)
    n_lo, n_hi = need(lo), need(hi)
    per_layer = max((n_hi - n_lo) / max(hi - lo, 1), 1.0)
    depth = min(published, lo + int((budget - n_lo) // per_layer))
    check(depth >= 1, f"{label}: not even one layer fits {budget} B")
    got = need(depth)
    while got > budget:
        depth -= 1
        check(depth >= 1, f"{label}: not even one layer fits {budget} B")
        got = need(depth)
    cut = "no cut" if depth == published else \
        f"CUT from {published} to {depth} layers"
    log(f"[fit] {label}: {depth}/{published} layers ({cut}); step needs "
        f"{got / 2**30:.2f} GiB of a {budget / 2**30:.2f} GiB budget "
        f"({MEMORY_SHARE} of the chip)")
    return depth


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serving_setup(dtype: str):
    from repro.configs import get_config
    from repro.serving import EngineConfig

    cfg = get_config("qwen2_7b").with_(param_dtype="bfloat16", dtype=dtype)
    pages = -(-(max(PROMPT_LENS) + NEW_TOKENS) // 16)
    # total_pages + 1 (the trash page) divides a 4-way model axis, so the
    # one-chip and four-chip engines share one configuration
    ecfg = EngineConfig(max_slots=len(PROMPT_LENS), page_size=16,
                        total_pages=139, max_pages_per_seq=pages,
                        token_budget=512, prefill_chunk=256)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n, dtype=np.int32)
               for n in PROMPT_LENS]
    return cfg, ecfg, prompts


def serve_step_bytes(cfg, ecfg, device) -> int:
    """Bytes the engine's largest step (a full prefill chunk) needs at
    ``cfg``'s depth, from the compiler's memory analysis."""
    import jax
    import jax.numpy as jnp

    from repro.nn import build_model

    model = build_model(cfg)
    params = on_device(device, jax.eval_shape(model.init,
                                              jax.random.key(SEED)))
    cache = on_device(device, jax.eval_shape(
        lambda: model.stack.init_paged_cache(
            ecfg.max_slots, ecfg.total_pages, ecfg.page_size,
            jnp.dtype(cfg.dtype))))
    slots = ecfg.max_slots

    def i32(*shape):
        return on_device(device, jax.ShapeDtypeStruct(shape, jnp.int32))

    def step(params, cache, page_table, tokens, pos, n_new, slot_ids):
        return model.paged_step(params, tokens, pos, n_new, cache,
                                page_table, slot_ids, backend=ecfg.backend)

    compiled = jax.jit(step, donate_argnums=(1,)).lower(
        params, cache, i32(slots, ecfg.max_pages_per_seq),
        i32(slots, ecfg.prefill_chunk), i32(slots), i32(slots),
        i32(slots)).compile()
    return program_bytes(compiled)


def run_engine(model, params, ecfg, prompts, mesh=None, watch_req=None):
    """Serve ``prompts`` greedily; returns (generations, decode records of
    request ``watch_req``: [(position, logits)], all logits finite)."""
    import jax.numpy as jnp

    from repro.serving import ServingEngine

    eng = ServingEngine(model, params, ecfg, mesh=mesh)
    records, finite = [], []
    # the wrapper holds the step and the scheduler, not the engine: no
    # reference cycle keeps the parameters alive after the engine goes
    step, sched = eng._step, eng.sched

    def recording_step(*args):
        logits, cache = step(*args)
        finite.append(jnp.isfinite(logits).all())
        tokens, pos = args[3], args[4]
        if watch_req is not None and tokens.shape[1] == 1:
            for s, seq in enumerate(sched.active):
                if seq is not None and seq.req.req_id == watch_req:
                    records.append((int(pos[s]), np.asarray(
                        logits[s, 0], np.float32)))
        return logits, cache

    eng._step = recording_step
    t0 = time.perf_counter()
    outs = eng.run(prompts, NEW_TOKENS)
    log(f"[serve] {len(prompts)} requests, {sum(map(len, outs))} tokens "
        f"generated in {time.perf_counter() - t0:.1f} s (compiles "
        f"included)")
    return outs, records, bool(jnp.stack(finite).all())


def dispatch_counts() -> dict:
    from repro.obs import get_registry
    snap = get_registry().snapshot()["counters"]
    out = {}
    for name in ("repro_junction_dispatch_total",
                 "repro_decode_dispatch_total", "repro_tune_lookup_total"):
        for s in snap.get(name, {}).get("series", []):
            key = (name,) + tuple(sorted(s["labels"].items()))
            out[key] = s["value"]
    return out


def serve_phase(device, budget: int) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.nn import build_model

    cfg, ecfg, prompts = serving_setup("bfloat16")
    depth = fit_depth(
        "qwen2_7b serve", cfg.n_layers,
        lambda n: serve_step_bytes(cfg.with_(n_layers=n), ecfg, device),
        budget)
    cfg = cfg.with_(n_layers=depth)
    log(f"[serve] qwen2_7b: d_model {cfg.d_model}, {cfg.n_heads} heads "
        f"({cfg.n_kv_heads} kv), d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
        f"{depth} layers, params {cfg.param_dtype}")
    model = build_model(cfg)
    params = jax.jit(model.init)(jax.random.key(SEED))
    n_params = sum(int(x.size) for x in jax.tree.leaves(params))
    log(f"[serve] {n_params / 1e9:.3f} B parameters")

    outs, records, finite = run_engine(model, params, ecfg, prompts,
                                       watch_req=0)
    check(finite, "non-finite logits in an engine step")
    for i, (p, g) in enumerate(zip(prompts, outs)):
        check(len(g) == NEW_TOKENS, f"request {i}: {len(g)} tokens")
        check(((g >= 0) & (g < cfg.vocab_size)).all(),
              f"request {i}: token outside the vocabulary")
        log(f"[serve] request {i}: prompt {len(p)}, first tokens "
            f"{g[:8].tolist()}")

    # the reference: LM.forward over the same tokens with every junction
    # on the XLA path (its own dispatches are kept out of the check)
    before = dispatch_counts()
    ref = build_model(cfg.with_(sparsity=dataclasses.replace(
        cfg.sparsity, backend="xla")))
    seq = np.concatenate([prompts[0], outs[0][:-1]])[None]

    def forward(params, tokens):
        h, _, _ = ref.forward(params, {"tokens": tokens})
        return ref.logits_fn(params, h).astype(jnp.float32)[0]

    ref_logits = np.asarray(jax.jit(forward)(params, jnp.asarray(seq)))
    after = dispatch_counts()
    check(np.isfinite(ref_logits).all(), "non-finite reference logits")
    check(len(records) == NEW_TOKENS - 1,
          f"{len(records)} decode steps recorded for request 0")
    delta = max(float(np.abs(lg - ref_logits[p]).max())
                for p, lg in records)
    scale = max(float(np.abs(ref_logits[p]).max()) for p, _ in records)
    log(f"[serve] paged-decode logits vs LM.forward (XLA), request 0, "
        f"{len(records)} positions: max |delta| {delta:.4g}, max |ref| "
        f"{scale:.4g}, ratio {delta / scale:.4g} (bound {LOGIT_BOUND})")
    check(delta <= LOGIT_BOUND * scale, "logits disagree with the reference")
    return {k: after[k] - before.get(k, 0.0) for k in after}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def trainer_for(cfg):
    from repro.nn import build_model
    from repro.optim import AdamWConfig
    from repro.train import Trainer, TrainerConfig

    return Trainer(build_model(cfg), TrainerConfig(
        opt=AdamWConfig(lr=1e-4, warmup_steps=1, total_steps=TRAIN_STEPS),
        log_every=1))


def train_step_bytes(cfg, device) -> int:
    import jax
    import jax.numpy as jnp

    from repro.optim import adam

    tr = trainer_for(cfg)
    params = on_device(device, jax.eval_shape(tr.model.init,
                                              jax.random.key(SEED)))
    opt = on_device(device, jax.eval_shape(adam.init, params))
    batch = on_device(device, {
        k: jax.ShapeDtypeStruct((TRAIN_BATCH, TRAIN_SEQ), jnp.int32)
        for k in ("tokens", "labels")})
    compiled = tr.step_fn(batch).lower(params, opt, batch).compile()
    return program_bytes(compiled)


def train_phase(device, budget: int) -> None:
    import jax

    from repro.configs import get_config
    from repro.data import BigramLM

    cfg = get_config("granite_moe_1b_a400m")
    depth = fit_depth(
        "granite_moe_1b_a400m train", cfg.n_layers,
        lambda n: train_step_bytes(cfg.with_(n_layers=n), device), budget)
    cfg = cfg.with_(n_layers=depth)
    moe = cfg.moe
    log(f"[train] granite_moe_1b_a400m: d_model {cfg.d_model}, "
        f"{moe.n_routed} experts (d_expert {moe.d_expert}, top-{moe.top_k}),"
        f" vocab {cfg.vocab_size}, {depth} layers, batch {TRAIN_BATCH} x "
        f"{TRAIN_SEQ} tokens")
    tr = trainer_for(cfg)
    params, opt = tr.init_state(jax.random.key(SEED))
    data = BigramLM(vocab_size=cfg.vocab_size, seed=SEED).iterate(
        TRAIN_BATCH, TRAIN_SEQ)
    t0 = time.perf_counter()
    _, _, history = tr.fit(
        data, TRAIN_STEPS, params=params, opt=opt, resume=False,
        on_step=lambda s, m: log(
            f"[train] step {s}: loss {m['loss']:.4f}, grad_norm "
            f"{m['grad_norm']:.4f}"))
    log(f"[train] {TRAIN_STEPS} steps in {time.perf_counter() - t0:.1f} s "
        f"(compile included)")
    check(len(history) == TRAIN_STEPS, f"{len(history)} steps logged")
    for h in history:
        check(np.isfinite(h["loss"]) and np.isfinite(h["grad_norm"]),
              f"step {h['step']}: non-finite loss or gradient norm")


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------


def dispatch_phase(reference: dict) -> None:
    """Print the trace-time counters; every junction and paged decode of
    the main path (the counts less the XLA reference's) ran on Pallas."""
    counts = dispatch_counts()
    bad = []
    for key, v in sorted(counts.items()):
        name, labels = key[0], dict(key[1:])
        main = v - reference.get(key, 0.0)
        log(f"[dispatch] {name} {labels}: {v:g} (XLA reference "
            f"{reference.get(key, 0.0):g})")
        if name != "repro_tune_lookup_total" and main > 0 \
                and labels["backend"] != "pallas":
            bad.append((name, labels, main))
    check(any(k[0] == "repro_decode_dispatch_total" for k in counts),
          "no paged decode was dispatched")
    check(not bad, f"main path ran off Pallas: {bad}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(devices, budget: int) -> None:
    """qwen2_7b through ``ServingEngine(mesh=...)`` on a 4-way model axis
    against the one-chip engine in this process: identical greedy tokens.
    Compute runs in f32 (parameters stay bf16) so the comparison tests
    the sharding, not where bf16 rounding breaks a near-tie."""
    import jax

    from repro.launch.mesh import make_mesh
    from repro.nn import build_model
    from repro.sharding import policy

    check(len(devices) == 4, f"--four-chip needs 4 chips, have "
          f"{len(devices)}")
    cfg, ecfg, prompts = serving_setup("float32")
    depth = fit_depth(
        "qwen2_7b serve (f32 compute)", cfg.n_layers,
        lambda n: serve_step_bytes(cfg.with_(n_layers=n), ecfg, devices[0]),
        budget)
    cfg = cfg.with_(n_layers=depth)
    model = build_model(cfg)
    key = jax.random.key(SEED)

    params = jax.jit(model.init)(key)
    one, _, finite = run_engine(model, params, ecfg, prompts)
    check(finite, "non-finite logits (one chip)")
    del params

    mesh = make_mesh((4,), ("model",), devices=devices)
    rules = policy.rules_for("decode", ecfg.max_slots, mesh, cfg)
    pshape = jax.eval_shape(model.init, key)
    sharding = policy.named(
        mesh, policy.param_pspecs(model.spec(), rules), pshape)
    params = jax.jit(model.init, out_shardings=sharding)(key)
    before = dispatch_counts()
    four, _, finite = run_engine(model, params, ecfg, prompts, mesh=mesh)
    after = dispatch_counts()
    check(finite, "non-finite logits (four chips)")
    forms = {}
    for k, v in after.items():
        if k[0] == "repro_junction_dispatch_total":
            d = v - before.get(k, 0.0)
            if d:
                lab = dict(k[1:])
                forms[(lab["backend"], lab["form"])] = d
    sharded = sum(v for (_, f), v in forms.items() if "sharded" in f)
    fallback = sum(v for (_, f), v in forms.items() if "sharded" not in f)
    log(f"[4chip] junction dispatches on the 4-way mesh: {sharded:g} "
        f"sharded, {fallback:g} single-device fallback {forms}")
    same = [bool(np.array_equal(a, b)) for a, b in zip(one, four)]
    for i, (a, b) in enumerate(zip(one, four)):
        log(f"[4chip] request {i}: one chip {a[:8].tolist()} ... four "
            f"chips {b[:8].tolist()} ... {'match' if same[i] else 'DIFFER'}")
    check(all(same), "greedy tokens differ between one and four chips")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip serving parity path")
    args = ap.parse_args()

    import jax

    from repro.device import init_compile_cache

    cache_dir = init_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX found {dev.platform} ({len(devices)} device(s))")
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)}; "
        f"compile cache {cache_dir}")
    budget = int(MEMORY_SHARE * dev.memory_stats()["bytes_limit"])

    t0 = time.perf_counter()
    if args.four_chip:
        four_chip_phase(devices, budget)
    else:
        reference = serve_phase(dev, budget)
        train_phase(dev, budget)
        dispatch_phase(reference)
    log(f"[done] {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
