"""Model assembly: scanned layer stacks, LM / encoder-decoder wrappers,
KV/SSM caches, and the chunked cross-entropy loss.

The stack splits layers into (prologue, scanned pattern units, epilogue):

* ``prologue``  — unscanned leading layers (deepseek-moe's dense layer 0);
* ``scan``      — ``n_groups`` repetitions of the architecture's repeating
                  unit (gemma3: LLLLLG, gemma2: LG, zamba2: 6 mamba + one
                  shared-attention application), parameters stacked on a
                  leading group axis and applied under ``lax.scan``;
* ``epilogue``  — unscanned remainder (34 = 5x6 + 4 for gemma3).

Pattern-sharing note: scanned groups share each unit-slot's pre-defined
sparsity pattern (the pattern is compile-time static, so it cannot vary
along the scan axis). Prologue/epilogue/unit-slots each get distinct seeds.
This mirrors the FPGA reusing one address generator per pipeline stage.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .common import ModelConfig, dtype_of, shard
from .layers import Embedding, Linear, RMSNorm, activation
from .transformer import MambaLayer, SharedAttnBlock, TransformerBlock


def _detect_unit(kinds: Tuple[str, ...]) -> int:
    n = len(kinds)
    for u in range(1, n + 1):
        groups = n // u
        if groups == 0:
            continue
        ok = all(kinds[i] == kinds[i % u] for i in range(groups * u))
        if ok and (groups > 1 or u == n):
            return u
    return n


def _make_block(cfg: ModelConfig, kind: str, seed: int, cross: bool,
                layer_idx: int):
    if kind == "mamba":
        return MambaLayer(cfg, seed=seed)
    return TransformerBlock(cfg, kind, seed=seed, cross=cross,
                            layer_idx=layer_idx)


class Stack:
    """A stack of blocks executed as prologue + scan(groups) + epilogue."""

    def __init__(self, cfg: ModelConfig, kinds: Tuple[str, ...],
                 cross: bool = False, seed: int = 0, causal: bool = True):
        self.cfg = cfg
        self.causal = causal
        self.cross = cross
        n = len(kinds)
        self.n_layers = n
        pro_n = 1 if (cfg.moe is not None and cfg.moe.first_layer_dense
                      and not cross) else 0
        self.prologue = [
            _make_block(cfg, kinds[i], seed + 1000 * i, cross, i)
            for i in range(pro_n)]
        rest = kinds[pro_n:]
        self.hybrid = cfg.hybrid is not None and "mamba" in kinds
        if self.hybrid:
            unit = cfg.hybrid.period
        else:
            unit = _detect_unit(rest) if rest else 1
        self.unit_len = unit
        self.n_groups = len(rest) // unit if unit else 0
        scanned = self.n_groups * unit
        self.unit_blocks = [
            _make_block(cfg, rest[u], seed + 10 * u + 1, cross, pro_n + u)
            for u in range(unit)] if self.n_groups else []
        self.epilogue = [
            _make_block(cfg, rest[scanned + i],
                        seed + 2000 + 10 * i, cross, pro_n + scanned + i)
            for i in range(len(rest) - scanned)]
        self.shared = SharedAttnBlock(cfg, seed=seed + 501) \
            if self.hybrid else None

    # -- params --------------------------------------------------------------

    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        keys = iter(jax.random.split(key, 4096))
        p: dict = {}
        p["prologue"] = [b.init(next(keys)) for b in self.prologue]
        if self.n_groups:
            # one vmapped init per unit slot: the same per-group keys and
            # values as a loop, but one traced layer instead of n_groups
            # (a 28-layer init otherwise takes minutes to compile)
            p["scan"] = [
                jax.vmap(blk.init)(jnp.stack(
                    [next(keys) for _ in range(self.n_groups)]))
                for blk in self.unit_blocks]
        else:
            p["scan"] = []
        p["epilogue"] = [b.init(next(keys)) for b in self.epilogue]
        if self.shared is not None:
            p["shared"] = self.shared.init(next(keys))
        return p

    def spec(self) -> dict:
        s: dict = {}
        s["prologue"] = [b.spec() for b in self.prologue]
        if self.n_groups:
            # scanned params get a leading 'layers' (stacked) axis
            s["scan"] = [
                jax.tree.map(lambda ax: ("layers",) + tuple(ax),
                             blk.spec(),
                             is_leaf=lambda x: isinstance(x, tuple))
                for blk in self.unit_blocks]
        else:
            s["scan"] = []
        s["epilogue"] = [b.spec() for b in self.epilogue]
        if self.shared is not None:
            s["shared"] = self.shared.spec()
        return s

    # -- helpers ---------------------------------------------------------------

    def _apply_block(self, blk, p, x, positions, enc_out, emb, collect):
        if isinstance(blk, MambaLayer):
            x, state, aux = blk(p, x, positions)
            kv = state if collect else None
        else:
            x, kv_raw, aux = blk(p, x, positions, enc_out=enc_out,
                                 causal=self.causal)
            kv = {"self": kv_raw} if collect else None
        return x, kv, aux

    # -- forward ----------------------------------------------------------------

    def __call__(self, params: dict, x: jax.Array, positions: jax.Array,
                 *, enc_out: Optional[jax.Array] = None,
                 emb: Optional[jax.Array] = None,
                 collect_cache: bool = False
                 ) -> Tuple[jax.Array, dict, dict]:
        cfg = self.cfg
        aux_tot: dict = {}
        cache: dict = {"prologue": [], "epilogue": [], "scan": None,
                       "shared": None}

        def add_aux(aux):
            for k, v in aux.items():
                aux_tot[k] = aux_tot.get(k, 0.0) + v

        for blk, p in zip(self.prologue, params["prologue"]):
            x, kv, aux = self._apply_block(blk, p, x, positions, enc_out,
                                           emb, collect_cache)
            add_aux(aux)
            cache["prologue"].append(kv)

        if self.n_groups:
            shared_p = params.get("shared")

            def body(carry, p_unit):
                xc, aux_c = carry
                kvs = []
                aux_g: dict = {}
                for u, blk in enumerate(self.unit_blocks):
                    xc, kv, aux = self._apply_block(
                        blk, p_unit[u], xc, positions, enc_out, emb,
                        collect_cache)
                    kvs.append(kv)
                    for k, v in aux.items():
                        aux_g[k] = aux_g.get(k, 0.0) + v
                kv_sh = None
                if self.shared is not None:
                    xc, kv_sh_raw = self.shared(shared_p, xc, emb, positions)
                    kv_sh = kv_sh_raw if collect_cache else None
                aux_c = {k: aux_c.get(k, 0.0) + aux_g.get(k, 0.0)
                         for k in set(aux_c) | set(aux_g)}
                ys = (kvs, kv_sh) if collect_cache else None
                return (xc, aux_c), ys

            if cfg.remat:
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.nothing_saveable)
            aux0 = {}
            if any(getattr(b, "is_moe", False) for b in self.unit_blocks):
                aux0 = {"moe_lb": 0.0, "moe_z": 0.0}
            (x, aux_s), ys = jax.lax.scan(body, (x, aux0),
                                          tuple(params["scan"]))
            add_aux(aux_s)
            if collect_cache:
                cache["scan"], cache["shared"] = ys

        for blk, p in zip(self.epilogue, params["epilogue"]):
            x, kv, aux = self._apply_block(blk, p, x, positions, enc_out,
                                           emb, collect_cache)
            add_aux(aux)
            cache["epilogue"].append(kv)

        return x, cache, aux_tot

    # -- decode -------------------------------------------------------------------

    def decode(self, params: dict, x: jax.Array, pos: jax.Array,
               cache: dict, emb: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, dict]:
        new_cache: dict = {"prologue": [], "epilogue": [],
                           "scan": None, "shared": None}
        for blk, p, c in zip(self.prologue, params["prologue"],
                             cache["prologue"]):
            x, nc = blk.decode(p, x, pos, c)
            new_cache["prologue"].append(nc)

        if self.n_groups:
            shared_p = params.get("shared")

            def body(xc, xs):
                p_unit, c_unit, c_sh = xs
                ncs = []
                for u, blk in enumerate(self.unit_blocks):
                    xc, nc = blk.decode(p_unit[u], xc, pos, c_unit[u])
                    ncs.append(nc)
                nc_sh = None
                if self.shared is not None:
                    xc, nc_sh = self.shared.decode(shared_p, xc, emb, pos,
                                                   c_sh)
                return xc, (ncs, nc_sh)

            x, (ncs, nc_sh) = jax.lax.scan(
                body, x, (tuple(params["scan"]), cache["scan"],
                          cache["shared"]))
            new_cache["scan"], new_cache["shared"] = ncs, nc_sh

        for blk, p, c in zip(self.epilogue, params["epilogue"],
                             cache["epilogue"]):
            x, nc = blk.decode(p, x, pos, c)
            new_cache["epilogue"].append(nc)
        return x, new_cache

    # -- paged serving step ---------------------------------------------------

    def paged_step(self, params: dict, x: jax.Array, pos: jax.Array,
                   n_new: jax.Array, cache: dict, page_table: jax.Array,
                   slot_ids: jax.Array, emb: Optional[jax.Array] = None,
                   *, backend: str = "auto", interpret: bool = False
                   ) -> Tuple[jax.Array, dict]:
        """One serving step (decode C==1 or a prefill chunk C>1) against the
        paged cache built by ``init_paged_cache``.

        Attention layers address the shared page pool through
        ``page_table`` (B, max_pages); SSM layers carry per-slot recurrent
        state through the same interface — their state rows are gathered by
        ``slot_ids`` (B,), stepped, and scattered back, so a B=1 prefill
        chunk touches only its own slot's state.
        """
        def apply(blk, p, xc, c):
            if isinstance(blk, MambaLayer):
                rows = jax.tree.map(lambda l: l[slot_ids], c)
                xc, new_rows = blk.paged_step(
                    p, xc, pos, n_new, rows, page_table,
                    backend=backend, interpret=interpret)
                nc = jax.tree.map(
                    lambda l, r: l.at[slot_ids].set(r.astype(l.dtype)),
                    c, new_rows)
                return xc, nc
            return blk.paged_step(p, xc, pos, n_new, c, page_table,
                                  backend=backend, interpret=interpret)

        new_cache: dict = {"prologue": [], "epilogue": [],
                           "scan": None, "shared": None}
        for blk, p, c in zip(self.prologue, params["prologue"],
                             cache["prologue"]):
            x, nc = apply(blk, p, x, c)
            new_cache["prologue"].append(nc)

        if self.n_groups:
            shared_p = params.get("shared")

            def body(xc, xs):
                p_unit, c_unit, c_sh = xs
                ncs = []
                for u, blk in enumerate(self.unit_blocks):
                    xc, nc = apply(blk, p_unit[u], xc, c_unit[u])
                    ncs.append(nc)
                nc_sh = None
                if self.shared is not None:
                    xc, nc_sh = self.shared.paged_step(
                        shared_p, xc, emb, pos, n_new, c_sh, page_table,
                        backend=backend, interpret=interpret)
                return xc, (ncs, nc_sh)

            x, (ncs, nc_sh) = jax.lax.scan(
                body, x, (tuple(params["scan"]), cache["scan"],
                          cache["shared"]))
            new_cache["scan"], new_cache["shared"] = ncs, nc_sh

        for blk, p, c in zip(self.epilogue, params["epilogue"],
                             cache["epilogue"]):
            x, nc = apply(blk, p, x, c)
            new_cache["epilogue"].append(nc)
        return x, new_cache

    # -- cache allocation ------------------------------------------------------------

    def _blk_cache(self, blk, batch: int, s_max: int, dtype,
                   enc_len: int = 0) -> dict:
        cfg = self.cfg
        if isinstance(blk, MambaLayer):
            return blk.mixer.init_state(batch, jnp.float32)
        kvshape = (batch, s_max, cfg.n_kv_heads, cfg.head_dim)
        c = {"self": {
            "k": shard(jnp.zeros(kvshape, dtype),
                       "batch", "kv_seq", None, None),
            "v": shard(jnp.zeros(kvshape, dtype),
                       "batch", "kv_seq", None, None)}}
        if blk.cross_attn is not None:
            xshape = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
            c["cross"] = {"k": jnp.zeros(xshape, dtype),
                          "v": jnp.zeros(xshape, dtype)}
        return c

    def init_cache(self, batch: int, s_max: int, dtype=jnp.bfloat16,
                   enc_len: int = 0) -> dict:
        cache: dict = {
            "prologue": [self._blk_cache(b, batch, s_max, dtype, enc_len)
                         for b in self.prologue],
            "epilogue": [self._blk_cache(b, batch, s_max, dtype, enc_len)
                         for b in self.epilogue],
            "scan": None, "shared": None,
        }
        if self.n_groups:
            def rep(tree):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x, (self.n_groups,) + x.shape), tree)
            cache["scan"] = [
                rep(self._blk_cache(b, batch, s_max, dtype, enc_len))
                for b in self.unit_blocks]
            if self.shared is not None:
                kvshape = (self.n_groups, batch, s_max,
                           self.cfg.n_kv_heads, self.cfg.head_dim)
                cache["shared"] = {
                    "k": shard(jnp.zeros(kvshape, dtype),
                               None, "batch", "kv_seq", None, None),
                    "v": shard(jnp.zeros(kvshape, dtype),
                               None, "batch", "kv_seq", None, None)}
        return cache

    def reset_slot_state(self, cache: dict, slot: int) -> dict:
        """Zero one slot's recurrent (SSM) state rows in a paged cache —
        called when a freed slot is re-admitted. Attention page buffers
        need no reset (stale KV is masked by sequence length), but Mamba
        state is carried unmasked as the chunk's initial state, so a new
        occupant must not inherit the previous sequence's state."""
        def zero(tree, scanned):
            # scanned mamba state leaves are (G, slots, ...) — slot is
            # axis 1; unscanned are (slots, ...)
            return jax.tree.map(
                lambda l: l.at[:, slot].set(0.0) if scanned
                else l.at[slot].set(0.0), tree)

        new = dict(cache)
        new["prologue"] = [
            zero(c, False) if isinstance(b, MambaLayer) else c
            for b, c in zip(self.prologue, cache["prologue"])]
        new["epilogue"] = [
            zero(c, False) if isinstance(b, MambaLayer) else c
            for b, c in zip(self.epilogue, cache["epilogue"])]
        if self.n_groups:
            new["scan"] = [
                zero(c, True) if isinstance(b, MambaLayer) else c
                for b, c in zip(self.unit_blocks, cache["scan"])]
        return new

    def _blk_paged_cache(self, blk, slots: int, total_pages: int,
                         page_size: int, dtype, quant_kv: bool) -> dict:
        cfg = self.cfg
        if isinstance(blk, MambaLayer):
            return blk.mixer.init_state(slots, jnp.float32)
        if blk.cross_attn is not None:
            raise NotImplementedError(
                "paged serving: cross-attention stacks not supported")
        shape = (total_pages + 1, page_size, cfg.n_kv_heads, cfg.head_dim)
        if quant_kv:
            # int8 pages + per-token f32 scales (see serving.kv_cache);
            # the attention paged_step keys the quantized path off the
            # presence of "k_scale" in its cache dict
            return {"self": {
                "k_pages": jnp.zeros(shape, jnp.int8),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "k_scale": jnp.zeros(shape[:2], jnp.float32),
                "v_scale": jnp.zeros(shape[:2], jnp.float32)}}
        return {"self": {"k_pages": jnp.zeros(shape, dtype),
                         "v_pages": jnp.zeros(shape, dtype)}}

    def init_paged_cache(self, slots: int, total_pages: int,
                         page_size: int, dtype=jnp.bfloat16,
                         quant_kv: bool = False) -> dict:
        """Per-layer page pools (+1 write-discard page each) and per-slot
        SSM state, shaped to mirror ``init_cache``'s tree so the scan
        traversal is identical. ``quant_kv`` makes the per-layer pools
        int8 with per-token scale buffers riding alongside (the shared
        cross-group pool stays full-width: it is written once per step
        and G-replicated reads dominate, so its bandwidth win is
        marginal next to the per-layer pools)."""
        cache: dict = {
            "prologue": [self._blk_paged_cache(b, slots, total_pages,
                                               page_size, dtype, quant_kv)
                         for b in self.prologue],
            "epilogue": [self._blk_paged_cache(b, slots, total_pages,
                                               page_size, dtype, quant_kv)
                         for b in self.epilogue],
            "scan": None, "shared": None,
        }
        if self.n_groups:
            def rep(tree):
                return jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x, (self.n_groups,) + x.shape).copy(), tree)
            cache["scan"] = [
                rep(self._blk_paged_cache(b, slots, total_pages,
                                          page_size, dtype, quant_kv))
                for b in self.unit_blocks]
            if self.shared is not None:
                shape = (self.n_groups, total_pages + 1, page_size,
                         self.cfg.n_kv_heads, self.cfg.head_dim)
                cache["shared"] = {"k_pages": jnp.zeros(shape, dtype),
                                   "v_pages": jnp.zeros(shape, dtype)}
        return cache


# ---------------------------------------------------------------------------
# Language model
# ---------------------------------------------------------------------------


class LM:
    """Decoder-only LM (tokens or stub-frontend embeddings in)."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.embed = Embedding(cfg.vocab_size, cfg.d_model, cfg.param_dtype)
        self.stack = Stack(cfg, cfg.layer_kinds)
        self.ln_f = RMSNorm(cfg.d_model, cfg.rms_eps, cfg.param_dtype)
        if cfg.input_mode == "embeddings":
            # 2-layer MLP projector (llava-style); also used for audio stubs
            self.proj_in = Linear(cfg.frontend_dim, cfg.d_model,
                                  dtype=cfg.param_dtype, bias=True,
                                  logical_axes=(None, "embed"))
            self.proj_mid = Linear(cfg.d_model, cfg.d_model,
                                   dtype=cfg.param_dtype, bias=True,
                                   logical_axes=("embed", None))
        self.head = None
        if not cfg.tie_embeddings:
            self.head = Linear(cfg.d_model, cfg.vocab_size,
                               dtype=cfg.param_dtype,
                               logical_axes=("embed", "vocab"))

    def init(self, key: jax.Array) -> dict:
        ks = jax.random.split(key, 5)
        p = {"embed": self.embed.init(ks[0]),
             "stack": self.stack.init(ks[1]),
             "ln_f": self.ln_f.init()}
        if self.cfg.input_mode == "embeddings":
            p["proj_in"] = self.proj_in.init(ks[2])
            p["proj_mid"] = self.proj_mid.init(ks[3])
        if self.head is not None:
            p["head"] = self.head.init(ks[4])
        return p

    def spec(self) -> dict:
        s = {"embed": self.embed.spec(), "stack": self.stack.spec(),
             "ln_f": self.ln_f.spec()}
        if self.cfg.input_mode == "embeddings":
            s["proj_in"] = self.proj_in.spec()
            s["proj_mid"] = self.proj_mid.spec()
        if self.head is not None:
            s["head"] = self.head.spec()
        return s

    # -- embedding in / logits out -------------------------------------------

    def embed_in(self, params: dict, batch: dict) -> jax.Array:
        cfg = self.cfg
        cdt = dtype_of(cfg)
        if cfg.input_mode == "embeddings":
            x = batch["embeds"].astype(cdt)
            x = self.proj_in(params["proj_in"], x)
            x = jax.nn.gelu(x)
            x = self.proj_mid(params["proj_mid"], x)
        else:
            x = self.embed(params["embed"], batch["tokens"], dtype=cdt)
        if cfg.scale_embed:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), cdt)
        return shard(x, "batch", "seq", None)

    @jax.named_scope("lm_head")
    def logits_fn(self, params: dict, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        if self.head is not None:
            logits = self.head(params["head"], h)
        else:
            logits = self.embed.attend(params["embed"], h)
        if cfg.final_softcap is not None:
            logits = cfg.final_softcap * jnp.tanh(
                logits / cfg.final_softcap)
        return logits

    # -- forward / loss ---------------------------------------------------------

    def forward(self, params: dict, batch: dict,
                collect_cache: bool = False):
        cfg = self.cfg
        x = self.embed_in(params, batch)
        b, s = x.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        emb = x if self.stack.shared is not None else None
        h, cache, aux = self.stack(params["stack"], x, positions, emb=emb,
                                   collect_cache=collect_cache)
        h = self.ln_f(params["ln_f"], h)
        return h, cache, aux

    def loss(self, params: dict, batch: dict) -> Tuple[jax.Array, dict]:
        """Next-token cross entropy, chunked over the sequence."""
        h, _, aux = self.forward(params, batch)
        return self._loss(params, h, batch["labels"], aux)

    @jax.named_scope("loss")
    def _loss(self, params: dict, h: jax.Array, labels: jax.Array,
              aux: dict) -> Tuple[jax.Array, dict]:
        cfg = self.cfg
        # gather the (seq-sharded) hidden once, in bf16, before chunking —
        # otherwise every chunk's dynamic_slice re-gathers it
        h = shard(h, "batch", None, None)
        b, s = labels.shape
        chunk = min(cfg.loss_chunk, s)
        n_chunks = s // chunk

        def chunk_loss(i):
            hc = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, 1)
            lc = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, 1)
            logits = self.logits_fn(params, hc).astype(jnp.float32)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, lc[..., None],
                                       axis=-1)[..., 0]
            valid = (lc >= 0).astype(jnp.float32)
            nll = (logz - gold) * valid
            return jnp.sum(nll), jnp.sum(valid)

        if cfg.remat:
            # without this the loss scan stashes full-vocab logits per
            # chunk for backward — gigabytes per device at 256k vocab
            chunk_loss = jax.checkpoint(
                chunk_loss, policy=jax.checkpoint_policies.nothing_saveable)

        if n_chunks == 1:
            tot, cnt = chunk_loss(0)
        else:
            def body(carry, i):
                t, c = chunk_loss(i)
                return (carry[0] + t, carry[1] + c), None
            (tot, cnt), _ = jax.lax.scan(
                body, (jnp.zeros(()), jnp.zeros(())),
                jnp.arange(n_chunks))
        loss = tot / jnp.maximum(cnt, 1.0)
        metrics = {"loss": loss, "tokens": cnt}
        aux_scale = {"moe_lb": 0.01, "moe_z": 1.0}
        for k, v in aux.items():
            loss = loss + aux_scale.get(k, 1.0) * jnp.asarray(
                v, jnp.float32) / self.stack.n_layers
            metrics[k] = v
        return loss, metrics

    # -- serving ------------------------------------------------------------------

    def prefill(self, params: dict, batch: dict, s_max: int
                ) -> Tuple[jax.Array, dict]:
        """Run the prompt, build a cache of capacity ``s_max``; returns
        (last-token logits, cache)."""
        cfg = self.cfg
        h, kv_new, _ = self.forward(params, batch, collect_cache=True)
        b, s = h.shape[:2]
        cache = self.stack.init_cache(b, s_max, dtype_of(cfg))
        cache = _write_prefill(cache, kv_new, s)
        logits = self.logits_fn(params, h[:, -1:])
        return logits, {"layers": cache, "pos": jnp.asarray(s, jnp.int32)}

    def decode_step(self, params: dict, token: jax.Array, cache: dict
                    ) -> Tuple[jax.Array, dict]:
        """token: (B, 1) int32 (or (B,1,F) embeds). One step of decoding.

        For stub-frontend models (vlm/audio) decode always embeds *text*
        tokens through the embedding table — the frontend only feeds the
        prefix at prefill time (llava: anyres patches, seamless: frames).
        """
        cfg = self.cfg
        pos = cache["pos"]
        if token.ndim == 2:  # token ids
            cdt = dtype_of(cfg)
            x = self.embed(params["embed"], token, dtype=cdt)
            if cfg.scale_embed:
                x = x * jnp.asarray(np.sqrt(cfg.d_model), cdt)
        else:
            x = self.embed_in(params, {"embeds": token})
        emb = x if self.stack.shared is not None else None
        x, new_layers = self.stack.decode(params["stack"], x, pos,
                                          cache["layers"], emb=emb)
        x = self.ln_f(params["ln_f"], x)
        logits = self.logits_fn(params, x)
        return logits, {"layers": new_layers, "pos": pos + 1}

    # -- paged serving (continuous batching engine) ---------------------------

    def paged_step(self, params: dict, tokens: jax.Array, pos: jax.Array,
                   n_new: jax.Array, cache: dict, page_table: jax.Array,
                   slot_ids: jax.Array, *, backend: str = "auto",
                   interpret: bool = False, all_logits: bool = False
                   ) -> Tuple[jax.Array, dict]:
        """One engine step: tokens (B, C) int32, per-row start positions
        ``pos`` (B,) and valid counts ``n_new`` (B,). C == 1 is a batched
        decode step; C > 1 one prefill chunk or a speculative verify
        chunk (pending token + drafts). Returns (last-valid-token logits
        (B, 1, V), updated paged cache) — or, with ``all_logits=True``
        (static), logits at EVERY chunk position (B, C, V): the verify
        path needs the greedy continuation after each draft to accept the
        longest matching prefix host-side.

        Only token-input decoder-only models serve through this path;
        frontends (embeddings) and enc-dec go through the legacy loop.
        """
        cfg = self.cfg
        if cfg.input_mode != "tokens":
            raise NotImplementedError(
                "paged serving expects token inputs (stub frontends feed "
                "the legacy prefill path)")
        cdt = dtype_of(cfg)
        x = self.embed(params["embed"], tokens, dtype=cdt)
        if cfg.scale_embed:
            x = x * jnp.asarray(np.sqrt(cfg.d_model), cdt)
        emb = x if self.stack.shared is not None else None
        x, new_cache = self.stack.paged_step(
            params["stack"], x, pos, n_new, cache, page_table, slot_ids,
            emb=emb, backend=backend, interpret=interpret)
        x = self.ln_f(params["ln_f"], x)
        if all_logits:
            return self.logits_fn(params, x), new_cache
        idx = jnp.clip(n_new - 1, 0, x.shape[1] - 1)
        h_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        logits = self.logits_fn(params, h_last)
        return logits, new_cache


def _write_prefill(cache: dict, kv_new: dict, s: int) -> dict:
    """Write prefill-collected KV (length s) into zero-initialized caches."""
    def write(c, kv):
        if kv is None:
            return c
        if "ssd" in c:  # mamba state: prefill state replaces directly
            return kv
        out = dict(c)
        if "self" in kv and kv["self"] is not None:
            out["self"] = {
                n: jax.lax.dynamic_update_slice_in_dim(
                    c["self"][n], kv["self"][n].astype(c["self"][n].dtype),
                    0, axis=1)
                for n in ("k", "v")}
        return out

    new = dict(cache)
    new["prologue"] = [write(c, kv) for c, kv in
                       zip(cache["prologue"], kv_new["prologue"])]
    new["epilogue"] = [write(c, kv) for c, kv in
                       zip(cache["epilogue"], kv_new["epilogue"])]
    if cache["scan"] is not None and kv_new["scan"] is not None:
        new_scan = []
        for c, kv in zip(cache["scan"], kv_new["scan"]):
            if kv is None:
                new_scan.append(c)
            elif "ssd" in c:
                new_scan.append(kv)
            else:
                out = dict(c)  # keep e.g. zero-initialized 'cross' slots
                out["self"] = {
                    n: jax.lax.dynamic_update_slice_in_dim(
                        c["self"][n],
                        kv["self"][n].astype(c["self"][n].dtype),
                        0, axis=2)  # (G, B, S, H, D)
                    for n in ("k", "v")}
                new_scan.append(out)
        new["scan"] = new_scan
    if cache["shared"] is not None and kv_new["shared"] is not None:
        new["shared"] = {
            n: jax.lax.dynamic_update_slice_in_dim(
                cache["shared"][n],
                kv_new["shared"][n].astype(cache["shared"][n].dtype),
                0, axis=2)
            for n in ("k", "v")}
    return new


# ---------------------------------------------------------------------------
# Encoder-decoder (seamless-m4t backbone)
# ---------------------------------------------------------------------------


class EncDec:
    """Enc-dec transformer; encoder consumes stub frontend embeddings,
    decoder is a causal token LM with cross-attention."""

    def __init__(self, cfg: ModelConfig):
        assert cfg.enc_dec is not None
        self.cfg = cfg
        ed = cfg.enc_dec
        self.embed = Embedding(cfg.vocab_size, cfg.d_model, cfg.param_dtype)
        self.adapter = Linear(cfg.frontend_dim or cfg.d_model, cfg.d_model,
                              bias=True, dtype=cfg.param_dtype,
                              logical_axes=(None, "embed"))
        self.encoder = Stack(cfg, ("global",) * ed.n_encoder_layers,
                             seed=7000, causal=False)
        self.decoder = Stack(cfg, ("global",) * ed.n_decoder_layers,
                             cross=True, seed=9000)
        self.ln_enc = RMSNorm(cfg.d_model, cfg.rms_eps, cfg.param_dtype)
        self.ln_f = RMSNorm(cfg.d_model, cfg.rms_eps, cfg.param_dtype)

    def init(self, key: jax.Array) -> dict:
        ks = jax.random.split(key, 4)
        return {"embed": self.embed.init(ks[0]),
                "adapter": self.adapter.init(ks[1]),
                "encoder": self.encoder.init(ks[2]),
                "decoder": self.decoder.init(ks[3]),
                "ln_enc": self.ln_enc.init(), "ln_f": self.ln_f.init()}

    def spec(self) -> dict:
        return {"embed": self.embed.spec(), "adapter": self.adapter.spec(),
                "encoder": self.encoder.spec(),
                "decoder": self.decoder.spec(),
                "ln_enc": self.ln_enc.spec(), "ln_f": self.ln_f.spec()}

    def encode(self, params: dict, embeds: jax.Array) -> jax.Array:
        cdt = dtype_of(self.cfg)
        x = self.adapter(params["adapter"], embeds.astype(cdt))
        x = shard(x, "batch", "seq", None)
        b, s = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _, _ = self.encoder(params["encoder"], x, pos)
        return self.ln_enc(params["ln_enc"], h)

    def forward(self, params: dict, batch: dict,
                collect_cache: bool = False):
        cfg = self.cfg
        enc_out = self.encode(params, batch["embeds"])
        tokens = batch["tokens"]
        cdt = dtype_of(cfg)
        x = self.embed(params["embed"], tokens, dtype=cdt)
        x = shard(x, "batch", "seq", None)
        b, s = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, cache, aux = self.decoder(params["decoder"], x, pos,
                                     enc_out=enc_out,
                                     collect_cache=collect_cache)
        h = self.ln_f(params["ln_f"], h)
        return h, cache, aux, enc_out

    def loss(self, params: dict, batch: dict) -> Tuple[jax.Array, dict]:
        h, _, aux, _ = self.forward(params, batch)
        labels = batch["labels"]
        logits = self.embed.attend(params["embed"], h).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        valid = (labels >= 0).astype(jnp.float32)
        loss = jnp.sum((logz - gold) * valid) / jnp.maximum(
            jnp.sum(valid), 1.0)
        return loss, {"loss": loss}

    def prefill(self, params: dict, batch: dict, s_max: int):
        cfg = self.cfg
        h, kv_new, _, enc_out = self.forward(params, batch,
                                             collect_cache=True)
        b, s = h.shape[:2]
        cache = self.decoder.init_cache(b, s_max, dtype_of(cfg),
                                        enc_len=enc_out.shape[1])
        cache = _write_prefill(cache, kv_new, s)
        cache = self._fill_cross(params, cache, enc_out)
        logits = self.embed.attend(params["embed"], h[:, -1:])
        return logits, {"layers": cache, "pos": jnp.asarray(s, jnp.int32)}

    def _fill_cross(self, params: dict, cache: dict,
                    enc_out: jax.Array) -> dict:
        """Precompute cross-attention KV from encoder output once."""
        b, se = enc_out.shape[:2]
        cdt = dtype_of(self.cfg)

        def cross_kv(blk, p):
            att = blk.cross_attn
            k = att.wk(p["cross"]["k"], enc_out).reshape(
                b, se, att.kv, att.dh)
            v = att.wv(p["cross"]["v"], enc_out).reshape(
                b, se, att.kv, att.dh)
            return {"k": k.astype(cdt), "v": v.astype(cdt)}

        dparams = params["decoder"]
        for i, blk in enumerate(self.decoder.prologue):
            cache["prologue"][i] = dict(cache["prologue"][i],
                                        cross=cross_kv(blk,
                                                       dparams["prologue"][i]))
        for i, blk in enumerate(self.decoder.epilogue):
            cache["epilogue"][i] = dict(cache["epilogue"][i],
                                        cross=cross_kv(blk,
                                                       dparams["epilogue"][i]))
        if self.decoder.n_groups:
            new_scan = []
            for u, blk in enumerate(self.decoder.unit_blocks):
                kv = jax.vmap(lambda pg: cross_kv(blk, pg))(
                    dparams["scan"][u])  # (G, B, se, kv, dh)
                new_scan.append(dict(cache["scan"][u], cross=kv))
            cache = dict(cache, scan=new_scan)
        return cache

    def decode_step(self, params: dict, token: jax.Array, cache: dict):
        cfg = self.cfg
        pos = cache["pos"]
        x = self.embed(params["embed"], token, dtype=dtype_of(cfg))
        x, new_layers = self.decoder.decode(params["decoder"], x, pos,
                                            cache["layers"])
        x = self.ln_f(params["ln_f"], x)
        logits = self.embed.attend(params["embed"], x)
        return logits, {"layers": new_layers, "pos": pos + 1}


def build_model(cfg: ModelConfig):
    if cfg.enc_dec is not None:
        return EncDec(cfg)
    return LM(cfg)
