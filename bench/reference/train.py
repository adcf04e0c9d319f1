"""Plain float32 reference of a training step: the decoder reference's
loss (mean next-token NLL + the MoE auxiliary losses), its gradient by
autodiff, global-norm clipping and AdamW, written from their equations.

AdamW as configured (``opt`` of the cell): ``m = b1 m + (1-b1) g``,
``v = b2 v + (1-b2) g^2``, bias-corrected, ``p -= lr (m^ / (sqrt(v^) +
eps) + wd p)`` with weight decay on matrices only (ndim >= 2), after the
gradient is scaled by ``min(1, clip / (||g|| + 1e-9))``; the learning
rate at step t is ``lr * min(1, (t + 1) / warmup)`` (constant schedule).
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import f32, head_weight


def loss_fn(params, batch, cfg, pats, loss_chunk=1024):
    """Mean NLL over all labels + sum over layers of (0.01 lb + z) / L."""
    B, S = batch["tokens"].shape
    # MoE routes over all tokens of the batch at once
    h, lb, z = _batch_hidden(params, batch["tokens"], cfg, pats)
    w = head_weight(params).astype(f32)
    lab = batch["labels"].reshape(-1)
    tot = 0.0
    for s in range(0, B * S, loss_chunk):
        lg = h[s:s + loss_chunk] @ w
        lz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, lab[s:s + loss_chunk, None], -1)[:, 0]
        tot = tot + jnp.sum(lz - gold)
    nll = tot / (B * S)
    L = cfg["n_layers"]
    return nll + (0.01 * lb + z) / L


def _batch_hidden(params, tokens, cfg, pats):
    """Hidden states of a (B, S) batch: attention per sequence, MoE over
    all B*S tokens together (the program's routing group)."""
    from bench.reference import decoder as D

    B, S = tokens.shape
    pr = D.Prec()
    x = pr.r(params["embed"]["table"].astype(f32)[tokens.reshape(-1)])
    pos = jnp.arange(S)

    def body(carry, p):
        x, lb, z = carry
        xs = x.reshape(B, S, -1)
        # one sequence's attention activations at a time under autodiff
        attn = jax.checkpoint(lambda xb, pa, sc: D.attention(
            pr.r(D.rmsnorm(xb, sc, cfg["rms_eps"])), pa, pos, cfg, pr))
        att = jax.lax.map(
            lambda xb: attn(xb, p["attn"], p["ln_attn"]["scale"]),
            xs).reshape(B * S, -1)
        x = pr.r(x + att)
        h = pr.r(D.rmsnorm(x, p["ln_ffn"]["scale"], cfg["rms_eps"]))
        y, l, zz = D.moe(h, p["ffn"], pats, cfg, pr)
        return (pr.r(x + y), lb + l, z + zz), None

    body = jax.checkpoint(body)
    (x, lb, z), _ = jax.lax.scan(body, (x, 0.0, 0.0),
                                 params["stack"]["scan"][0])
    return pr.r(D.rmsnorm(x, params["ln_f"]["scale"], cfg["rms_eps"])), \
        lb, z


def adamw(opt_cfg, grads, m, v, params, step):
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt_cfg["grad_clip"] / (gn + 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2, eps = opt_cfg["b1"], opt_cfg["b2"], opt_cfg["eps"]
    lr = opt_cfg["lr"] * min(1.0, (step + 1) / max(opt_cfg["warmup_steps"], 1))
    t = step + 1
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)

    def upd(p, m, v):
        u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            u = u + opt_cfg["weight_decay"] * p
        return p - lr * u

    return jax.tree.map(upd, params, m, v), m, v, grads


_FNS = {}


def _grad_fn(cfg, pats):
    key = (json.dumps(cfg, sort_keys=True),
           tuple((k, v.tobytes()) for k, v in sorted(pats.items())))
    if key not in _FNS:
        _FNS[key] = jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, pats)))
    return _FNS[key]


_UPD = {}


def _update_fn(opt_cfg):
    """AdamW as one jitted call that reuses the buffers of the gradient,
    the moments and the parameters (one float32 tree of each is held)."""
    key = json.dumps(opt_cfg, sort_keys=True)
    if key not in _UPD:
        _UPD[key] = jax.jit(
            lambda g, m, v, p, step: adamw(opt_cfg, g, m, v, p, step),
            static_argnums=(4,), donate_argnums=(0, 1, 2, 3))
    return _UPD[key]


def three_steps(params, batches, cfg, pats, opt_cfg):
    """Losses of steps 1-3, the clipped first gradient's per-leaf norms,
    and the parameters after three steps (``p``; the caller takes their
    change from the initial ones made again from the seed, so no second
    float32 tree is held). ``params`` is consumed."""
    pats = {k: np.asarray(v) for k, v in pats.items()}
    p = jax.tree.map(lambda x: x.astype(f32), params)
    del params
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    gfn = _grad_fn(cfg, pats)
    upd = _update_fn(opt_cfg)
    losses, g1 = [], None
    for step, b in enumerate(batches[:3]):
        loss, g = gfn(p, b)
        losses.append(float(loss))
        p, m, v, gc = upd(g, m, v, p, step)
        del g
        if step == 0:
            g1 = np.array([float(jnp.linalg.norm(x.reshape(-1)))
                           for x in jax.tree.leaves(gc)])
        del gc
    del m, v
    return {"losses": losses, "g1": g1, "p": p}
