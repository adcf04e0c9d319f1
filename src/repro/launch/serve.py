"""Serving driver: continuous-batching engine (default) + legacy loops.

``python -m repro.launch.serve --arch <id> --batch 4 --prompt-len 32
--gen 16`` runs a smoke-scale batched generation. Token-input decoder-only
models route through ``repro.serving.ServingEngine`` (paged KV cache +
chunked prefill); stub-frontend and enc-dec models use the legacy dense
-cache loop. On real hardware the same code path serves the production
mesh with the SERVE sharding rules (TP FFN + context-parallel KV,
DESIGN.md §5).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def _sample_tok(logits, key):
    """Categorical sample from (B, 1, V) logits -> (B, 1) int32."""
    return jax.random.categorical(key, logits[:, 0]).astype(jnp.int32)[:, None]


def generate_cached(model, params, prompt, s_max, steps, *, greedy=True,
                    key=None, extra_batch=None):
    """Legacy batched generation: monolithic prefill + dense-cache decode
    loop. Kept for enc-dec / stub-frontend models and engine A/B tests.
    Returns (tokens, tokens/sec over the decode loop)."""
    batch = {"tokens": prompt}
    if extra_batch:
        batch.update(extra_batch)
    logits, cache = jax.jit(
        lambda p, b: model.prefill(p, b, s_max))(params, batch)
    step = jax.jit(model.decode_step, donate_argnums=(2,))
    if greedy:
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    else:
        # the first token is a draw too — and every draw uses a fresh
        # split, never the raw key
        key, sub = jax.random.split(key)
        tok = _sample_tok(logits, sub)
    out = [tok]
    t0 = time.time()
    for i in range(steps - 1):
        logits, cache = step(params, tok, cache)
        if greedy:
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        else:
            key, sub = jax.random.split(key)
            tok = _sample_tok(logits, sub)
        out.append(tok)
    toks = jnp.concatenate(out, axis=1)
    toks.block_until_ready()
    dt = time.time() - t0
    tps = prompt.shape[0] * max(steps - 1, 1) / max(dt, 1e-9)
    return toks, tps


def generate(model, params, prompt, s_max, steps, *, greedy=True, key=None,
             extra_batch=None, page_size: int = 16):
    """Batched generation; returns (tokens (B, steps), tokens/sec).

    Thin wrapper over the continuous-batching ``ServingEngine`` (paged KV
    cache, chunked prefill, paged-attention decode). Models the engine
    cannot serve (enc-dec, stub-frontend embeddings, MoE with finite
    expert capacity — see the engine's dropless-decode guard) fall back
    to ``generate_cached``. The reported tok/s covers only tokens decoded
    after the prefill drain (compiles + prompt processing excluded).
    """
    moe = getattr(model.cfg, "moe", None)
    if extra_batch or getattr(model.cfg, "enc_dec", None) is not None \
            or model.cfg.input_mode != "tokens" \
            or (moe is not None
                and moe.capacity_factor * moe.top_k < moe.n_routed):
        return generate_cached(model, params, prompt, s_max, steps,
                               greedy=greedy, key=key,
                               extra_batch=extra_batch)
    from ..serving import EngineConfig, ServingEngine

    b, prompt_len = prompt.shape
    pages_per_seq = -(-s_max // page_size)
    eng = ServingEngine(
        model, params,
        EngineConfig(max_slots=b, page_size=page_size,
                     total_pages=b * pages_per_seq,
                     max_pages_per_seq=pages_per_seq,
                     token_budget=b + max(prompt_len, 1),
                     prefill_chunk=64, greedy=greedy),
        key=key)
    for i in range(b):
        eng.add_request(np.asarray(prompt[i]), steps, req_id=i)
    # run prefill (and its jit compiles) before the timer, mirroring the
    # legacy loop's prefill-outside-t0 convention; the tok/s reported is
    # the decode regime, modulo the first decode step's compile
    guard = 0
    while any(s is not None and s.prefilling for s in eng.sched.active) \
            or eng.sched.waiting:
        eng.step()
        guard += 1
        if guard > 10_000:
            raise RuntimeError("prefill failed to drain")
    # tokens decoded during the drain (continuous batching decodes
    # already-prefilled sequences while others prefill) don't count
    # toward the timed rate
    pre = sum(len(o) for o in eng.outputs.values()) \
        + sum(s.n_generated for s in eng.sched.active if s is not None)
    t0 = time.time()
    steps_run = 0
    while eng.sched.has_work():
        eng.step()
        steps_run += 1
        if steps_run > 100_000:
            raise RuntimeError("engine failed to drain")
    dt = time.time() - t0
    toks = jnp.asarray(np.stack([eng.outputs[i] for i in range(b)]))
    tps = max(b * steps - pre, 0) / max(dt, 1e-9)
    return toks, tps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--enc-len", type=int, default=None,
                    help="encoder frames for enc-dec archs "
                         "(default: --prompt-len)")
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()

    from ..configs import get_config
    from ..device import init_compile_cache
    from ..nn import build_model

    init_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    # the decoder prompt (text tokens); for enc-dec archs this seeds the
    # decoder while the frontend embeddings feed the encoder
    prompt = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)

    def frames(length):
        return jnp.asarray(rng.normal(
            size=(args.batch, length, cfg.frontend_dim)), jnp.float32)

    extra = None
    if cfg.enc_dec is not None:
        # enc-dec (seamless-style): stub frontend frames for the encoder,
        # token prompt for the decoder
        extra = {"embeds": frames(args.enc_len or args.prompt_len)}
    elif cfg.input_mode == "embeddings":
        # decoder-only with stub frontend (vlm/audio): the prefill consumes
        # embeddings aligned with the prompt span; decode embeds text tokens
        extra = {"embeds": frames(args.prompt_len)}
    toks, tps = generate(model, params, prompt,
                         args.prompt_len + args.gen, args.gen,
                         extra_batch=extra)
    print(f"generated {toks.shape} tokens at {tps:.1f} tok/s")
    print(toks[0])


if __name__ == "__main__":
    main()
