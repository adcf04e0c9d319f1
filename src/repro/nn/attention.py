"""GQA attention: XLA-flash (q-chunk scan) for train/prefill, einsum decode.

Why two paths (and when the Pallas kernel is used):

* **train/prefill** — a ``lax.scan`` over query chunks with full-KV logits
  per chunk: memory is O(chunk x S) instead of O(S^2), every op is a plain
  einsum so GSPMD can partition it (sequence-parallel q, sharded heads, or
  both). Sliding-window layers slice a static window span out of KV per
  chunk — structurally skipping out-of-window keys (gemma3's 5:1 local
  layers do 21x less attention work at 32k than a full-attention layer).
* **decode** — one query token: logits are (B, H, 1, S); a single einsum
  chain that GSPMD partitions over a *sequence-sharded* KV cache (context-
  parallel decode; softmax max/sum become all-reduces over the seq axis).
* On real TPUs the Pallas ``kernels.flash_attention`` replaces the q-chunk
  scan inside a ``shard_map`` (hillclimb path); the XLA scan is the
  portable/partitionable reference and what the dry-run lowers.

GQA is computed in grouped form (B, S, Hkv, G, Dh) — KV is never expanded to
Q heads (a 6x memory blowup for granite-34b's 48:1 MQA).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..serving import kv_cache as paged_kv
from .common import ModelConfig, current_mesh, logical_to_spec, shard
from .layers import Linear, RMSNorm, apply_rope

_NEG_INF = -1e30


def _softcap(logits: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return logits
    return cap * jnp.tanh(logits / cap)


def _attend_block(q_chunk, k_c, v_c, qpos, kpos, *, causal, window,
                  softcap, scale):
    """One (q-block x kv-block) attention with flash-style partials.

    Returns (o_unnormalized_f32, m, l): per-row max, exp-sum, and the
    un-normalized f32 output, so blocks can be merged online.
    """
    logits = jnp.einsum("bqhgd,bkhd->bhgqk",
                        q_chunk.astype(jnp.float32) * scale,
                        k_c.astype(jnp.float32))
    logits = _softcap(logits, softcap)
    mask = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1)                      # (B,H,G,Q)
    p = jnp.exp(logits - jnp.maximum(m, _NEG_INF / 2)[..., None])
    p = jnp.where((m > _NEG_INF / 2)[..., None], p, 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v_c.astype(jnp.float32))
    return o, m, l


def chunked_attention(
    q: jax.Array,  # (B, Sq, Hkv, G, Dh) — grouped query
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,  # (B, Skv, Hkv, Dh)
    *,
    causal: bool,
    window: Optional[int],
    softcap: Optional[float],
    chunk: int,
    q_offset=0,                 # int or traced (shard-local offset)
    scale: float,
    kv_chunk: Optional[int] = None,  # inner flash loop for long KV
) -> jax.Array:
    """Memory-bounded attention: scan over query chunks, and (for long KV)
    an inner online-softmax scan over KV chunks — the XLA form of flash
    attention, O(chunk_q x chunk_k) live logits."""
    b, sq, hkv, g, dh = q.shape
    skv = k.shape[1]
    chunk = min(chunk, sq)
    pad_q = (-sq) % chunk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    sq_p = sq + pad_q
    n_chunks = sq_p // chunk

    kf = k
    use_window_slice = (window is not None and causal
                        and window + chunk < skv)
    span = min(skv, ((window or 0) + chunk + 127) // 128 * 128) \
        if use_window_slice else skv
    use_kv_scan = (kv_chunk is not None and not use_window_slice
                   and skv > 2 * kv_chunk and skv % kv_chunk == 0)

    def body(_, i):
        q_chunk = jax.lax.dynamic_slice_in_dim(q, i * chunk, chunk, axis=1)
        q_start = i * chunk + q_offset
        qpos = q_start + jnp.arange(chunk)
        if use_window_slice:
            # static-size KV span covering [q_start - window + 1, q_end]
            start = jnp.clip(q_start + chunk - span, 0, skv - span)
            k_c = jax.lax.dynamic_slice_in_dim(kf, start, span, axis=1)
            v_c = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
            kpos = start + jnp.arange(span)
        else:
            k_c, v_c, kpos = kf, v, jnp.arange(skv)

        if not use_kv_scan:
            o, m, l = _attend_block(q_chunk, k_c, v_c, qpos, kpos,
                                    causal=causal, window=window,
                                    softcap=softcap, scale=scale)
            safe_l = jnp.where(l == 0.0, 1.0, l)
            out = o / jnp.moveaxis(safe_l, -1, 1)[..., None]
            return None, out.astype(q.dtype)

        # online-softmax merge over KV chunks
        nkv = skv // kv_chunk

        def kv_body(carry, j):
            acc, m_run, l_run = carry
            k_j = jax.lax.dynamic_slice_in_dim(k_c, j * kv_chunk, kv_chunk,
                                               axis=1)
            v_j = jax.lax.dynamic_slice_in_dim(v_c, j * kv_chunk, kv_chunk,
                                               axis=1)
            kpos_j = j * kv_chunk + jnp.arange(kv_chunk)
            o_j, m_j, l_j = _attend_block(q_chunk, k_j, v_j, qpos, kpos_j,
                                          causal=causal, window=window,
                                          softcap=softcap, scale=scale)
            m_new = jnp.maximum(m_run, m_j)
            c_old = jnp.where(m_run > _NEG_INF / 2,
                              jnp.exp(m_run - m_new), 0.0)
            c_new = jnp.where(m_j > _NEG_INF / 2,
                              jnp.exp(m_j - m_new), 0.0)
            l_new = l_run * c_old + l_j * c_new
            acc = acc * jnp.moveaxis(c_old, -1, 1)[..., None] \
                + o_j * jnp.moveaxis(c_new, -1, 1)[..., None]
            return (acc, m_new, l_new), None

        acc0 = jnp.zeros((b, chunk, hkv, g, dh), jnp.float32)
        m0 = jnp.full((b, hkv, g, chunk), _NEG_INF)
        l0 = jnp.zeros((b, hkv, g, chunk))
        # checkpoint: without it the scan stashes per-KV-chunk logits
        # residuals (o_j, m_j) for backward — O(S) memory again
        kv_body_ck = jax.checkpoint(
            kv_body, policy=jax.checkpoint_policies.nothing_saveable)
        (acc, m_run, l_run), _ = jax.lax.scan(
            kv_body_ck, (acc0, m0, l0), jnp.arange(nkv))
        safe_l = jnp.where(l_run == 0.0, 1.0, l_run)
        out = acc / jnp.moveaxis(safe_l, -1, 1)[..., None]
        return None, out.astype(q.dtype)

    if n_chunks == 1:
        _, out = body(None, 0)
    else:
        _, outs = jax.lax.scan(body, None, jnp.arange(n_chunks))
        # (n_chunks, B, chunk, Hkv, G, Dh) -> (B, Sq_p, Hkv, G, Dh)
        out = jnp.moveaxis(outs, 0, 1).reshape(b, sq_p, hkv, g, dh)
    return out[:, :sq] if pad_q else out


def seq_parallel_attention(
    qg: jax.Array,  # (B, Sq, Hkv, G, Dh) — grouped query, seq shardable
    k: jax.Array,   # (B, Skv, Hkv, Dh)
    v: jax.Array,
    **kw,
) -> jax.Array:
    """Sequence-parallel attention via shard_map over the seq mesh axis.

    Each device runs the chunked-flash scan on its local query span against
    the full KV (replicated into the region — GSPMD inserts the all-gather,
    which for GQA KV is small). Without this, the q-chunk scan's
    dynamic-slice on a sharded seq axis forces GSPMD to *replicate* the
    whole attention computation on every model shard (measured 16x compute
    + memory waste at mesh size 16). This wrapper is also exactly where the
    Pallas flash kernel drops in on real TPUs.
    """
    from jax.sharding import PartitionSpec as P

    mesh = current_mesh()
    spec_q = logical_to_spec("batch", "seq", None, None, None)
    seq_ax = spec_q[1]
    if mesh is None or seq_ax is None:
        return chunked_attention(qg, k, v, **kw)
    n_shards = int(np.prod([mesh.shape[a] for a in
                            (seq_ax if isinstance(seq_ax, tuple)
                             else (seq_ax,))]))
    if qg.shape[1] % n_shards or qg.shape[1] // n_shards < 1:
        return chunked_attention(qg, k, v, **kw)
    s_local = qg.shape[1] // n_shards
    spec_kv = logical_to_spec("batch", None, None, None)

    def local(qg_l, k_l, v_l):
        idx = jax.lax.axis_index(seq_ax)
        return chunked_attention(qg_l, k_l, v_l,
                                 q_offset=idx * s_local, **kw)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(spec_q, spec_kv, spec_kv),
        out_specs=spec_q, check_vma=False)
    return fn(qg, k, v)


def decode_attention(
    q: jax.Array,  # (B, 1, Hkv, G, Dh)
    k: jax.Array,  # (B, S, Hkv, Dh) — full cache (seq possibly sharded)
    v: jax.Array,
    *,
    pos: jax.Array,  # current absolute position (q attends to <= pos)
    window: Optional[int],
    softcap: Optional[float],
    scale: float,
) -> jax.Array:
    skv = k.shape[1]
    logits = jnp.einsum("bqhgd,bkhd->bhgqk",
                        q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    logits = _softcap(logits, softcap)
    kpos = jnp.arange(skv)
    mask = kpos <= pos
    if window is not None:
        mask &= kpos > pos - window
    logits = jnp.where(mask[None, None, None, None], logits, _NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


class Attention:
    """GQA self- or cross-attention with rope, optional qk-norm, window,
    logit softcap, and optional pre-defined-sparse projections."""

    def __init__(self, cfg: ModelConfig, *, window: Optional[int] = None,
                 cross: bool = False, seed: int = 0, qk_norm: bool = False,
                 d_in: Optional[int] = None):
        self.cfg = cfg
        self.window = window
        self.cross = cross
        self.qk_norm = qk_norm
        h, kv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
        self.h, self.kv, self.dh = h, kv, dh
        self.groups = h // kv
        d_in = d_in or cfg.d_model
        sp = cfg.sparsity
        rho = sp.rho_attn
        attn_sp = dataclasses.replace(sp, enabled=sp.enabled and rho is not None)
        pd = cfg.param_dtype
        mk = lambda n_in, n_out, s, ax: Linear(
            n_in, n_out, bias=cfg.qkv_bias and not cross,
            rho=rho if rho is not None else 1.0, sp=attn_sp, seed=seed + s,
            dtype=pd, logical_axes=ax)
        self.wq = mk(d_in, h * dh, 1, ("embed", "qheads"))
        self.wk = mk(d_in, kv * dh, 2, ("embed", "kvheads"))
        self.wv = mk(d_in, kv * dh, 3, ("embed", "kvheads"))
        self.wo = Linear(h * dh, cfg.d_model, bias=False,
                         rho=rho if rho is not None else 1.0, sp=attn_sp,
                         seed=seed + 4, dtype=pd,
                         logical_axes=("qheads", "embed"))
        if qk_norm:
            self.qnorm = RMSNorm(dh, cfg.rms_eps, pd)
            self.knorm = RMSNorm(dh, cfg.rms_eps, pd)

    def init(self, key: jax.Array) -> dict:
        ks = jax.random.split(key, 4)
        p = {"q": self.wq.init(ks[0]), "k": self.wk.init(ks[1]),
             "v": self.wv.init(ks[2]), "o": self.wo.init(ks[3])}
        if self.qk_norm:
            p["qnorm"] = self.qnorm.init()
            p["knorm"] = self.knorm.init()
        return p

    def spec(self) -> dict:
        s = {"q": self.wq.spec(), "k": self.wk.spec(), "v": self.wv.spec(),
             "o": self.wo.spec()}
        if self.qk_norm:
            s["qnorm"] = self.qnorm.spec()
            s["knorm"] = self.knorm.spec()
        return s

    # -- qkv ----------------------------------------------------------------

    @jax.named_scope("attn/proj")
    def _qkv(self, params, x, x_kv, positions):
        cfg = self.cfg
        b = x.shape[0]
        q = self.wq(params["q"], x).reshape(b, -1, self.h, self.dh)
        src = x if x_kv is None else x_kv
        k = self.wk(params["k"], src).reshape(b, -1, self.kv, self.dh)
        v = self.wv(params["v"], src).reshape(b, -1, self.kv, self.dh)
        if self.qk_norm:
            q = self.qnorm(params["qnorm"], q)
            k = self.knorm(params["knorm"], k)
        if not self.cross:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    @jax.named_scope("attn/proj")
    def _out(self, params, o):
        return self.wo(params["o"], o)

    # -- full-sequence (train / prefill) -------------------------------------

    def __call__(self, params: dict, x: jax.Array, positions: jax.Array,
                 *, x_kv: Optional[jax.Array] = None,
                 causal: bool = True) -> Tuple[jax.Array, dict]:
        """Returns (output, kv) where kv = {'k','v'} for cache seeding."""
        cfg = self.cfg
        b, sq, _ = x.shape
        q, k, v = self._qkv(params, x, x_kv, positions)
        q = shard(q, "batch", "seq", None, None)
        k = shard(k, "batch", None, None, None)
        v = shard(v, "batch", None, None, None)
        qg = q.reshape(b, sq, self.kv, self.groups, self.dh)
        causal = causal and not self.cross
        with jax.named_scope("attn/core"):
            o = seq_parallel_attention(
                qg, k, v, causal=causal, window=self.window,
                softcap=cfg.logit_softcap, chunk=cfg.attn_chunk,
                kv_chunk=cfg.attn_kv_chunk, scale=self.dh ** -0.5)
        o = o.reshape(b, sq, self.h * self.dh)
        o = shard(o, "batch", "seq", None)
        return self._out(params, o), {"k": k, "v": v}

    # -- single-token decode --------------------------------------------------

    def decode(self, params: dict, x: jax.Array, pos: jax.Array,
               cache: dict) -> Tuple[jax.Array, dict]:
        """x: (B, 1, d); cache: {'k','v'}: (B, S_max, Hkv, Dh) seq-sharded.

        Returns (out, updated_cache). For cross-attention the cache holds the
        (static) encoder KV and is not updated.
        """
        b = x.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        q, k_new, v_new = self._qkv(params, x, None if not self.cross else x,
                                    positions)
        if self.cross:
            k, v = cache["k"], cache["v"]
        else:
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), pos, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), pos, axis=1)
            cache = {"k": k, "v": v}
        qg = q.reshape(b, 1, self.kv, self.groups, self.dh)
        with jax.named_scope("attn/core"):
            o = decode_attention(
                qg, k.astype(q.dtype), v.astype(q.dtype),
                pos=pos if not self.cross else k.shape[1] - 1,
                window=self.window if not self.cross else None,
                softcap=self.cfg.logit_softcap, scale=self.dh ** -0.5)
        o = o.reshape(b, 1, self.h * self.dh)
        return self._out(params, o), cache

    # -- paged serving step (decode or chunked prefill) -----------------------

    def paged_step(self, params: dict, x: jax.Array, pos: jax.Array,
                   n_new: jax.Array, cache: dict, page_table: jax.Array,
                   *, backend: str = "auto", interpret: bool = False
                   ) -> Tuple[jax.Array, dict]:
        """One serving step against a paged KV cache.

        x: (B, C, d) — C == 1 is a decode step (per-row positions, routed
        through the paged-attention kernel); C > 1 is one chunk of prefill
        (causal within the chunk, attending to previously-cached pages via
        gather). pos: (B,) tokens already cached per row; n_new: (B,) valid
        tokens in this chunk (0 = inactive row: its KV writes land on the
        discard page and its output is garbage the engine ignores).
        cache: {'k_pages','v_pages'}: (P+1, page, Hkv, Dh), shared page
        pool addressed through ``page_table`` (B, max_pages). Returns
        (out (B, C, d), updated cache).

        Int8 cache: when the cache carries ``k_scale``/``v_scale``
        ((P+1, page) f32, see ``serving.kv_cache``), pages are int8 —
        writes quantize at append, reads dequantize in kernel/post-gather.
        """
        if self.cross:
            raise NotImplementedError("paged serving: no cross-attention")
        cfg = self.cfg
        b, c = x.shape[:2]
        k_pages, v_pages = cache["k_pages"], cache["v_pages"]
        quant = "k_scale" in cache
        page_size = k_pages.shape[1]
        trash = k_pages.shape[0] - 1
        positions = pos[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        valid = jnp.arange(c)[None] < n_new[:, None]

        q, k_new, v_new = self._qkv(params, x, None, positions)
        phys, off = paged_kv.physical_addresses(
            page_table, positions, valid, page_size, trash)
        if quant:
            k_scale, v_scale = cache["k_scale"], cache["v_scale"]
            k_pages, v_pages, k_scale, v_scale = paged_kv.write_kv_quant(
                k_pages, v_pages, k_scale, v_scale, k_new, v_new, phys, off)
        else:
            k_scale = v_scale = None
            k_pages, v_pages = paged_kv.write_kv(
                k_pages, v_pages, k_new, v_new, phys, off)
        lengths = pos + n_new
        scale = self.dh ** -0.5

        if c == 1:
            from ..kernels.flash_attention import paged_decode_attention
            qg = q.reshape(b, self.kv, self.groups, self.dh)
            with jax.named_scope("attn/decode"):
                o = paged_decode_attention(
                    qg, k_pages, v_pages, page_table, lengths,
                    window=self.window, softcap=cfg.logit_softcap,
                    scale=scale, backend=backend, interpret=interpret,
                    k_scale=k_scale, v_scale=v_scale, mesh=current_mesh())
            o = o.reshape(b, 1, self.h * self.dh).astype(x.dtype)
        else:
            # chunk prefill: gather this batch row's logical KV view and
            # run masked grouped attention (causal against everything
            # already in the pages, including this just-written chunk)
            with jax.named_scope("attn/core"):
                k = paged_kv.gather_kv(k_pages, page_table)
                v = paged_kv.gather_kv(v_pages, page_table)
                if quant:
                    ks = paged_kv.gather_scales(k_scale, page_table)
                    vs = paged_kv.gather_scales(v_scale, page_table)
                    k = k.astype(jnp.float32) * ks[:, :, None, None]
                    v = v.astype(jnp.float32) * vs[:, :, None, None]
                k = k.astype(q.dtype)
                v = v.astype(q.dtype)
                qg = q.reshape(b, c, self.kv, self.groups, self.dh)
                logits = jnp.einsum("bqhgd,bkhd->bhgqk",
                                    qg.astype(jnp.float32) * scale,
                                    k.astype(jnp.float32))
                logits = _softcap(logits, cfg.logit_softcap)
                kpos = jnp.arange(k.shape[1])
                mask = kpos[None, None] <= positions[:, :, None]   # (B, C, S)
                if self.window is not None:
                    mask &= kpos[None, None] > positions[:, :, None] \
                        - self.window
                mask &= valid[..., None]
                logits = jnp.where(mask[:, None, None], logits, _NEG_INF)
                m = jnp.max(logits, axis=-1, keepdims=True)
                p = jnp.exp(logits - jnp.maximum(m, _NEG_INF / 2))
                p = jnp.where(m > _NEG_INF / 2, p, 0.0)
                l = jnp.sum(p, axis=-1, keepdims=True)
                p = p / jnp.where(l == 0.0, 1.0, l)
                o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
            o = o.reshape(b, c, self.h * self.dh).astype(x.dtype)
        out = self._out(params, o)
        new_cache = {"k_pages": k_pages, "v_pages": v_pages}
        if quant:
            new_cache["k_scale"] = k_scale
            new_cache["v_scale"] = v_scale
        return out, new_cache
