"""Per-architecture smoke tests: reduced config, forward + train step on
CPU, output shapes + no NaNs; decode consistency against teacher forcing."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config, shapes_for
from repro.nn import build_model
from repro.optim import AdamWConfig
from repro.optim import adam


def _batch(cfg, b=2, s=24, seed=0):
    tokens = jax.random.randint(jax.random.key(seed), (b, s), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.input_mode == "embeddings" or cfg.enc_dec is not None:
        batch["embeds"] = jax.random.normal(
            jax.random.key(seed + 1), (b, s, cfg.frontend_dim))
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.loss)(params, batch)
    assert jnp.isfinite(loss), f"{arch} loss not finite"

    # one full train step (loss + grad + AdamW) — shapes preserved, no NaNs
    opt = adam.init(params)
    g = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    new_params, new_opt, om = adam.update(AdamWConfig(lr=1e-3), g, opt,
                                          params)
    for a, b2 in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)):
        assert a.shape == b2.shape
        assert jnp.isfinite(b2).all()
    assert jnp.isfinite(om["grad_norm"])


@pytest.mark.parametrize("arch", ARCHS)
def test_arch_smoke_prefill_decode(arch):
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)
    s = batch["tokens"].shape[1]
    logits, cache = model.prefill(params, batch, s + 8)
    assert logits.shape[:2] == (2, 1)
    assert jnp.isfinite(logits).all()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):
        logits, cache = model.decode_step(params, tok, cache)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        assert jnp.isfinite(logits).all()


@pytest.mark.parametrize("arch", ["gemma3_4b", "qwen2_7b", "mamba2_130m",
                                  "zamba2_1p2b", "deepseek_moe_16b"])
def test_decode_matches_teacher_forcing(arch):
    """Incremental decode == full forward at the new position."""
    cfg = get_config(arch, smoke=True)
    if cfg.moe is not None:
        import dataclasses
        cfg = cfg.with_(moe=dataclasses.replace(cfg.moe,
                                                capacity_factor=100.0))
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    logits, cache = model.prefill(params, batch, 24)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, _ = model.decode_step(params, tok, cache)
    full = jnp.concatenate([tokens, tok], axis=1)
    h, _, _ = model.forward(params, {"tokens": full})
    ref = model.logits_fn(params, h[:, -1:])
    np.testing.assert_allclose(np.asarray(logits2), np.asarray(ref),
                               atol=2e-4, rtol=2e-4)


def test_assigned_cell_count():
    cells = [(a, s.name) for a in ARCHS for s in shapes_for(a)]
    # 10 archs x 3 universal shapes + 3 long_500k (ssm/hybrid/5:1-window)
    assert len(cells) == 33
    longs = [c for c in cells if c[1] == "long_500k"]
    assert {a for a, _ in longs} == {"mamba2_130m", "zamba2_1p2b",
                                     "gemma3_4b"}


def test_exact_published_dimensions():
    """The full configs carry the exact assigned numbers."""
    want = {
        "gemma3_4b": (34, 2560, 8, 4, 10240, 262144),
        "granite_34b": (88, 6144, 48, 1, 24576, 49152),
        "gemma2_9b": (42, 3584, 16, 8, 14336, 256000),
        "qwen2_7b": (28, 3584, 28, 4, 18944, 152064),
        "llava_next_34b": (60, 7168, 56, 8, 20480, 64000),
    }
    for arch, (L, d, h, kv, ff, v) in want.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), arch
    ds = get_config("deepseek_moe_16b")
    assert (ds.moe.n_routed, ds.moe.top_k, ds.moe.n_shared) == (64, 6, 2)
    gm = get_config("granite_moe_1b_a400m")
    assert (gm.moe.n_routed, gm.moe.top_k) == (32, 8)
    mb = get_config("mamba2_130m")
    assert (mb.n_layers, mb.d_model, mb.ssm.d_state) == (24, 768, 128)
    zb = get_config("zamba2_1p2b")
    assert (zb.n_layers, zb.d_model, zb.ssm.d_state) == (38, 2048, 64)
    sm = get_config("seamless_m4t_medium")
    assert (sm.enc_dec.n_encoder_layers, sm.d_model, sm.vocab_size) == \
        (12, 1024, 256206)


@pytest.mark.parametrize("arch", ["qwen2_7b", "granite_moe_1b_a400m"])
def test_param_dtype_is_the_stored_dtype(arch):
    """``param_dtype`` decides what init stores: bf16 parameters are what
    lets qwen2_7b's published widths fit one 16 GB chip."""
    cfg = get_config(arch, smoke=True).with_(param_dtype="bfloat16")
    params = build_model(cfg).init(jax.random.key(0))
    assert {str(x.dtype) for x in jax.tree.leaves(params)} == {"bfloat16"}


def test_scanned_stack_init_matches_per_layer_init():
    """The stacked (scanned) parameters are each group's own block init
    under its own key — vmapping the init changes nothing."""
    cfg = get_config("qwen2_7b", smoke=True)
    model = build_model(cfg)
    stack = model.stack
    p = stack.init(jax.random.key(3))
    keys = jax.random.split(jax.random.key(3), 4096)[len(stack.prologue):]
    for u, blk in enumerate(stack.unit_blocks):
        for g in range(stack.n_groups):
            want = blk.init(keys[u * stack.n_groups + g])
            got = jax.tree.map(lambda x: x[g], p["scan"][u])
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
                np.testing.assert_array_equal(a, b)
