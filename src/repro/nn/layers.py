"""Primitive layers: Linear (dense or pre-defined-sparse), norms, embeddings,
rotary position embeddings. Functional modules: ``init(key) -> params`` and
``__call__(params, x)``; parameters are plain nested dicts (pjit-friendly).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.block_pattern import BlockPattern, fit_block_pattern
from ..kernels import ops as kops
from .common import ModelConfig, SparsityConfig, shard


# ---------------------------------------------------------------------------
# Linear — every weight junction in the framework goes through here, so
# pre-defined sparsity is a first-class option for all of them.
# ---------------------------------------------------------------------------


class Linear:
    """A junction. Dense by default; pre-defined block-sparse when ``rho<1``.

    ``logical_axes`` name the (in, out) sharding axes for the dense weight;
    the block-sparse weight inherits the output-dim axis on its right-block
    dimension and keeps fan-in dims replicated (the pattern is tiny).
    """

    def __init__(self, n_in: int, n_out: int, *, bias: bool = False,
                 rho: float = 1.0, sp: Optional[SparsityConfig] = None,
                 seed: int = 0, dtype: str = "float32",
                 logical_axes: Tuple[Optional[str], Optional[str]] = (None, None),
                 name: str = "linear"):
        self.n_in, self.n_out, self.bias = n_in, n_out, bias
        self.dtype = jnp.dtype(dtype)
        self.logical_axes = logical_axes
        self.name = name
        self.pattern: Optional[BlockPattern] = None
        self.backend = "xla"
        if sp is not None:
            # fit_block_pattern applies the shared block-size adaptation +
            # micro-block guard; None -> this junction stays dense.
            self.pattern = fit_block_pattern(n_in, n_out, rho, sp,
                                             seed=seed,
                                             weight_dtype=self.dtype)
            if self.pattern is not None:
                self.backend = sp.backend

    @property
    def is_sparse(self) -> bool:
        return self.pattern is not None

    @property
    def n_params(self) -> int:
        n = self.pattern.n_weight_elems if self.is_sparse else self.n_in * self.n_out
        return n + (self.n_out if self.bias else 0)

    def init(self, key: jax.Array) -> dict:
        if self.is_sparse:
            bp = self.pattern
            fan_in = bp.d_in_b * bp.block_in
            w = jax.random.normal(
                key, (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out),
                self.dtype) * float(np.sqrt(1.0 / fan_in))
        else:
            w = jax.random.normal(key, (self.n_in, self.n_out),
                                  self.dtype) * float(np.sqrt(1 / self.n_in))
        p = {"w": w}
        if self.bias:
            p["b"] = jnp.zeros((self.n_out,), self.dtype)
        return p

    def spec(self) -> dict:
        """Logical sharding axes per parameter (consumed by sharding.policy)."""
        if self.is_sparse:
            # (n_rb, d_in_b, bL, bR): the block-row dim carries the "slab"
            # logical axis — the SAME rule that drives the shard_map
            # partition of the junction compute, so the weight chunks a
            # NamedSharding produces are exactly the per-device slabs the
            # sharded csd_matmul expects (no resharding at entry)
            s = {"w": ("slab", None, None, None)}
        else:
            s = {"w": self.logical_axes}
        if self.bias:
            s["b"] = (None,)
        return s

    def __call__(self, params: dict, x: jax.Array,
                 activation: Optional[str] = None) -> jax.Array:
        """``activation(x @ W + b)``. For sparse junctions the bias and
        activation ride the fused ``csd_matmul`` epilogue (one kernel, no
        HBM round-trip of the pre-activation); dense junctions apply them
        inline. ``activation`` is ``None | "relu" | "gelu"``.

        Under a mesh whose rules resolve the ``"slab"`` axis (TRAIN and
        SERVE both map it to ``model``), a partitionable sparse junction
        transparently runs model-parallel: pattern + slab split across the
        axis, FF column-parallel, BP psum'd, UP shard-local (see
        ``kernels.ops``)."""
        w = params["w"]
        cdt = x.dtype
        if self.is_sparse:
            from .common import junction_shard_kwargs, logical_to_spec
            b = params["b"].astype(cdt) if self.bias else None
            kw = junction_shard_kwargs(self.pattern)
            if kw:
                # leading dims keep their batch sharding through the
                # shard_map; the seq dim replicates over the slab axis
                # (the Megatron-style all-gather at junction entry)
                kw["lead_spec"] = tuple(logical_to_spec(
                    *(("batch",) + (None,) * (x.ndim - 2))))
            if "w_scale" in params:
                # quantize_tree left an int8 slab + per-block scales: the
                # slab must enter csd_matmul uncast (SL206)
                return kops.csd_matmul(x, w, self.pattern, bias=b,
                                       activation=activation,
                                       backend=self.backend,
                                       w_scale=params["w_scale"], **kw)
            return kops.csd_matmul(x, w.astype(cdt), self.pattern,
                                   bias=b, activation=activation,
                                   backend=self.backend, **kw)
        y = x @ w.astype(cdt)
        if self.bias:
            y = y + params["b"].astype(cdt)
        return kops.apply_activation(y, activation)


class RMSNorm:
    def __init__(self, dim: int, eps: float = 1e-6, dtype: str = "float32",
                 zero_centered: bool = True):
        self.dim, self.eps = dim, eps
        self.dtype = jnp.dtype(dtype)
        self.zero_centered = zero_centered  # gemma-style (1 + scale)

    def init(self, key=None) -> dict:
        return {"scale": jnp.zeros((self.dim,), self.dtype)
                if self.zero_centered else jnp.ones((self.dim,), self.dtype)}

    def spec(self) -> dict:
        return {"scale": (None,)}

    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        dt = x.dtype
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        xf = xf * jax.lax.rsqrt(var + self.eps)
        scale = params["scale"].astype(jnp.float32)
        if self.zero_centered:
            scale = 1.0 + scale
        return (xf * scale).astype(dt)


class Embedding:
    def __init__(self, vocab: int, dim: int, dtype: str = "float32"):
        self.vocab, self.dim = vocab, dim
        self.dtype = jnp.dtype(dtype)

    def init(self, key: jax.Array) -> dict:
        w = jax.random.normal(key, (self.vocab, self.dim), self.dtype)
        return {"table": w * float(1.0 / np.sqrt(self.dim))}

    def spec(self) -> dict:
        # NOTE: the table's model dim gets its own logical name — sharding
        # it like weight matrices' "embed" (over data) makes every lookup /
        # tied-head matmul reshard through a global-batch intermediate
        # (measured ~4 GB of f32 scatter-adds per step at gemma3 scale).
        # vocab->model + embed-dim replicated keeps both the gather and
        # h @ table.T local with one small all-reduce.
        return {"table": ("vocab", "embed_table")}

    def __call__(self, params: dict, tokens: jax.Array,
                 dtype=None) -> jax.Array:
        t = params["table"]
        if dtype is not None:
            t = t.astype(dtype)  # gather + psum in compute dtype
        out = self._lookup(t, tokens)
        return out.astype(dtype or t.dtype)

    def _lookup(self, t: jax.Array, tokens: jax.Array) -> jax.Array:
        """Vocab-shard-local lookup via shard_map (mask + psum).

        GSPMD's default gather strategy for a vocab-sharded table
        materializes global-batch intermediates (measured GBs of f32
        scatter-adds in the backward). The mask+psum form keeps everything
        local: each shard serves the token rows it owns, zeros elsewhere,
        and one small psum over the vocab axis assembles the rows.
        """
        from jax.sharding import PartitionSpec as P

        from .common import current_mesh, logical_to_spec

        mesh = current_mesh()
        spec_t = logical_to_spec("vocab", "embed_table")
        vax = spec_t[0]
        if mesh is None or vax is None:
            return jnp.take(t, tokens, axis=0)
        n_shards = int(np.prod([mesh.shape[a] for a in
                                (vax if isinstance(vax, tuple)
                                 else (vax,))]))
        if self.vocab % n_shards:
            return jnp.take(t, tokens, axis=0)
        vshard = self.vocab // n_shards
        spec_i = logical_to_spec("batch", None)

        def local(tbl, tok):
            rel = tok - jax.lax.axis_index(vax) * vshard
            ok = (rel >= 0) & (rel < vshard)
            g = jnp.take(tbl, jnp.clip(rel, 0, vshard - 1), axis=0)
            g = jnp.where(ok[..., None], g, jnp.zeros((), g.dtype))
            return jax.lax.psum(g, vax)

        fn = jax.shard_map(
            local, mesh=mesh, in_specs=(spec_t, spec_i),
            out_specs=P(spec_i[0], None, None), check_vma=False)
        return fn(t, tokens)

    def attend(self, params: dict, h: jax.Array) -> jax.Array:
        """Tied output head: h @ table^T -> logits."""
        return h @ params["table"].astype(h.dtype).T


# ---------------------------------------------------------------------------
# Rotary position embedding
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    dh = x.shape[-1]
    freqs = rope_frequencies(dh, theta)  # (Dh/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu,
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True)}[name]
