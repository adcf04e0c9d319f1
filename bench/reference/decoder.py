"""Plain float32 reference of the decoder family the benchmark's
configurations use: GQA attention with optional QKV bias and RoPE,
pre-norm RMSNorm blocks, a SwiGLU FFN or a routed MoE whose junctions are
pre-defined block-sparse, and an LM head (untied or tied).

Written from the architecture's equations in straightforward
``jax.numpy``; it imports nothing of the program. It takes the
benchmark's parameter tree (made from the seed by ``bench``, in the
program's layout) and each sparse junction's gather table ``[rb, f]``
(the left block that feeds fan-in slot ``f`` of right block ``rb``),
built from the configuration by ``bench/reference/pattern.py``. Callers set
``jax.default_matmul_precision("highest")``.

The layer stack runs as a ``lax.scan`` over the stacked layer
parameters, one layer at a time, so only one layer's weights are ever
held in float32.

Conventions followed (the program's definition of the model):

* RMSNorm is ``x / sqrt(mean(x^2) + eps) * (1 + scale)``;
* RoPE rotates the two halves of each head (``[x1, x2] -> [x1 cos -
  x2 sin, x2 cos + x1 sin]``) with frequencies ``theta^(-i / (Dh/2))``;
* MoE routes in float32 over all tokens of the batch: softmax, top-k,
  gates renormalised over the k; expert ``e`` takes its first
  ``capacity`` assignments in token-major order and drops the rest;
  the load-balance and z losses are added to the loss as
  ``(0.01 * lb + zloss) / n_layers`` per layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


# -- pieces -----------------------------------------------------------------


def densify(slab, idx):
    """(n_rb, d_in_b, bL, bR) slab -> dense (n_lb * bL, n_rb * bR)."""
    n_rb, d_in, bl, br = slab.shape
    n_lb = int(np.max(idx)) + 1
    rb = np.broadcast_to(np.arange(n_rb)[:, None], idx.shape)
    dense = jnp.zeros((n_lb, bl, n_rb, br), f32)
    dense = dense.at[jnp.asarray(idx), :, jnp.asarray(rb), :].set(
        slab.astype(f32))
    return dense.reshape(n_lb * bl, n_rb * br)


def rmsnorm(x, scale, eps):
    x = x.astype(f32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale.astype(f32))


def rope(x, pos, theta):
    """x: (T, H, Dh), pos: (T,)."""
    half = x.shape[-1] // 2
    freq = 1.0 / (theta ** (jnp.arange(half, dtype=f32) / half))
    ang = pos.astype(f32)[:, None] * freq
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


class Prec:
    """How the reference computes: float32 throughout, every matmul at
    the precision the caller sets (``highest``)."""

    def r(self, x):
        return x

    def mm(self, x, w):
        return x @ w


def attention(x, p, pos, cfg, pr, q_block=512):
    """Causal GQA over one sequence. x: (T, d) normed input."""
    T = x.shape[0]
    H, K, D = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    def lin(name):
        y = pr.mm(x, p[name]["w"].astype(f32))
        if "b" in p[name]:
            y = pr.r(y + p[name]["b"].astype(f32))
        return y
    q = pr.r(rope(lin("q").reshape(T, H, D), pos, cfg["rope_theta"]))
    k = pr.r(rope(lin("k").reshape(T, K, D), pos, cfg["rope_theta"]))
    v = lin("v").reshape(T, K, D)
    g = H // K
    q = q.reshape(T, K, g, D) * (D ** -0.5)
    qb = min(q_block, T)
    nb = T // qb

    def one(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb)
        lg = jnp.einsum("qkgd,tkd->kgqt", qi, k)
        mask = jnp.arange(T)[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        prob = jax.nn.softmax(jnp.where(mask, lg, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", prob, v).reshape(qb, H * D)

    o = pr.r(jax.lax.map(one, jnp.arange(nb)).reshape(T, H * D))
    return pr.mm(o, p["o"]["w"].astype(f32))


def ffn(x, p, pats, pr, row_block=2048):
    up = densify(p["up"]["w"], pats["up"])
    gate = densify(p["gate"]["w"], pats["gate"])
    down = densify(p["down"]["w"], pats["down"])
    outs = []
    for s in range(0, x.shape[0], row_block):
        xb = x[s:s + row_block]
        h = pr.r(pr.r(jax.nn.silu(pr.mm(xb, gate))) * pr.mm(xb, up))
        outs.append(pr.mm(h, down))
    return jnp.concatenate(outs, 0)


def moe(x, p, pats, cfg, pr):
    """Routed experts over all T tokens. Returns (y, lb, z)."""
    mc = cfg["moe"]
    E, k = mc["n_routed"], mc["top_k"]
    T, d = x.shape
    logits = x @ p["router"].astype(f32)
    probs = jax.nn.softmax(logits, -1)
    gates, ids = jax.lax.top_k(probs, k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    cap = max(int(math.ceil(T * k / E * mc["capacity_factor"])), 1)
    # rank of each (token, slot) assignment within its expert, in
    # token-major order; assignments at rank >= capacity are dropped
    onehot = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.int32)
    rank = (jnp.cumsum(onehot, 0) - onehot)[jnp.arange(T * k),
                                            ids.reshape(-1)]
    keep = (rank < cap).reshape(T, k)
    w_all = jnp.sum(jnp.where(keep[..., None],
                              gates[..., None] * jax.nn.one_hot(ids, E), 0.0),
                    1)                                     # (T, E)

    @jax.checkpoint   # under autodiff: one expert's activations at a time
    def expert(y, xs):
        u, g, dn, w_e = xs
        h = pr.r(pr.r(jax.nn.silu(pr.mm(x, densify(g, pats["gate"]))))
                 * pr.mm(x, densify(u, pats["up"])))
        return y + w_e[:, None] * pr.mm(h, densify(dn, pats["down"])), None

    y, _ = jax.lax.scan(expert, jnp.zeros((T, d), f32),
                        (p["up"], p["gate"], p["down"], w_all.T))
    y = pr.r(y)
    ce = jnp.bincount(ids[:, 0], length=E).astype(f32) / T
    lb = E * jnp.sum(jnp.mean(probs, 0) * ce)
    z = mc["router_zloss"] * jnp.mean(jax.nn.logsumexp(logits, -1) ** 2)
    return y, lb, z


def block(x, p, pos, cfg, pats, pr):
    h = attention(pr.r(rmsnorm(x, p["ln_attn"]["scale"], cfg["rms_eps"])),
                  p["attn"], pos, cfg, pr)
    x = pr.r(x + h)
    h = pr.r(rmsnorm(x, p["ln_ffn"]["scale"], cfg["rms_eps"]))
    if cfg.get("moe"):
        y, lb, z = moe(h, p["ffn"], pats, cfg, pr)
        return pr.r(x + y), lb, z
    return pr.r(x + ffn(h, p["ffn"], pats, pr)), 0.0, 0.0


def hidden(params, tokens, cfg, pats, pr=None, remat=False):
    """Final normed hidden states of one sequence (T,) -> (T, d), plus
    the summed aux losses."""
    pr = pr or Prec()
    x = pr.r(params["embed"]["table"].astype(f32)[tokens])
    pos = jnp.arange(tokens.shape[0])

    def body(carry, p):
        x, lb, z = carry
        x, l, zz = block(x, p, pos, cfg, pats, pr)
        return (x, lb + l, z + zz), None

    if remat:
        body = jax.checkpoint(body)
    (x, lb, z), _ = jax.lax.scan(body, (x, 0.0, 0.0),
                                 params["stack"]["scan"][0])
    return pr.r(rmsnorm(x, params["ln_f"]["scale"], cfg["rms_eps"])), lb, z


def head_logits(h, w, pr, block=32768):
    """h @ w over blocks of the vocabulary (one float32 block of the head
    at a time)."""
    return jnp.concatenate(
        [pr.mm(h, w[:, i:i + block].astype(f32))
         for i in range(0, w.shape[1], block)], -1)


def head_weight(params):
    if "head" in params:
        return params["head"]["w"]
    return params["embed"]["table"].T


# -- serving -------------------------------------------------------------------


def _bucket(n: int) -> int:
    """Powers of two from 1024 to 8192, then multiples of 1024: few
    programs to compile however long the sampled requests are."""
    b = 1024
    while b < n and b < 8192:
        b *= 2
    return b if b >= n else -(-n // 1024) * 1024


def served_logits(params, pats, cfg, prompt, gen):
    """Reference logits at the positions that predicted ``gen``: rows
    P-1 .. P+len(gen)-2 of a forward over prompt + gen[:-1]. The sequence
    is padded at its end to a bucket (``_bucket``; causal: padding cannot
    reach earlier positions) so few programs compile."""
    toks = np.concatenate([prompt, gen[:-1]]).astype(np.int32)
    n = len(toks)
    padded = np.zeros(_bucket(n), np.int32)
    padded[:n] = toks
    rows = np.arange(len(prompt) - 1, n)
    fn = _served_fn(cfg, pats)
    return fn(params, jnp.asarray(padded), jnp.asarray(rows))


_FNS = {}


def _served_fn(cfg, pats):
    """One jitted forward per (configuration, patterns); the gather
    tables are constants of the program."""
    import json
    key = (json.dumps(cfg, sort_keys=True),
           tuple((k, v.tobytes()) for k, v in sorted(pats.items())))
    if key not in _FNS:
        pr = Prec()

        def fn(params, tokens, rows):
            h, _, _ = hidden(params, tokens, cfg, pats, pr)
            return head_logits(h[rows], head_weight(params), pr)

        _FNS[key] = jax.jit(fn)
    return _FNS[key]


def served_gaps(params, pats, cfg, seqs):
    """Per request: reference best logit minus the reference logit of
    each served token."""
    pats = {k: np.asarray(v) for k, v in pats.items()}
    out = []
    for prompt, gen in seqs:
        lg = np.asarray(served_logits(params, pats, cfg, prompt, gen))
        out.append(lg.max(-1) - lg[np.arange(len(gen)), gen])
    return out
