"""FFN and Mixture-of-Experts blocks.

The FFN junctions (up/gate/down) are where the paper's pre-defined sparsity
attaches in every assigned architecture: they hold the dominant share of
parameters (DESIGN.md §4), mirroring the paper's observation that the big
early junctions tolerate the most sparsity. Per-junction densities follow
the paper's trend 3 (later junctions denser): ``rho_ffn = (rho_up, rho_down)``.

MoE has two interchangeable implementations:

* ``gshard``   — one-hot dispatch/combine einsums. Pure GSPMD data flow; the
                 partitioner shards E over 'model'. Simple and robust, but
                 the dispatch einsum costs O(T*E*C*d) — often more FLOPs than
                 the experts themselves (this shows up in the §Roofline
                 useful-flops ratio and is a hillclimb target).
* ``shardmap`` — explicit expert parallelism: local top-k routing, capacity-
                 bucketed dispatch buffers, ``lax.all_to_all`` over the
                 'model' axis to the expert owners, batched expert FFN,
                 reverse all-to-all, local combine. This is the production
                 path (the all-to-all is visible in the compiled HLO and in
                 the collective roofline term).

Both are differentiable and agree numerically (tests/test_moe.py).

Expert junctions can be pre-defined sparse too
(``SparsityConfig.moe_sparsity``): each expert's up/gate/down weight
becomes a stacked block-sparse slab ``(E, n_rb, d_in_b, bL, bR)`` over ONE
shared ``BlockPattern`` per junction, and ``_expert_ffn`` — the expert
compute of BOTH dispatch modes — executes through the batched
``kernels.ops.csd_matmul`` path (expert-major Pallas grid on TPU, vmapped
slot-sweeps on XLA). The dense stacked einsums live on as the oracle
``kernels.ref.moe_expert_ffn_ref``.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.block_pattern import fit_block_pattern
from ..kernels import ops as kops
from .common import (ModelConfig, MoEConfig, current_mesh,
                     junction_shard_kwargs, shard)
from .layers import Linear, activation

# activation names the fused csd_matmul epilogue understands (the registry
# binds gelu and gelu_tanh to the same tanh-approx function); shared by the
# dense-FFN and MoE expert junction paths.
_FUSABLE = {"relu": "relu", "gelu": "gelu", "gelu_tanh": "gelu"}


class FFN:
    """(Gated) feed-forward junction pair, optionally pre-defined sparse."""

    def __init__(self, cfg: ModelConfig, d_ff: Optional[int] = None,
                 seed: int = 0, d_in: Optional[int] = None):
        self.cfg = cfg
        d_ff = d_ff or cfg.d_ff
        d_in = d_in or cfg.d_model
        sp = cfg.sparsity
        rho_up, rho_down = sp.rho_ffn if sp.enabled else (1.0, 1.0)
        pd = cfg.param_dtype
        self.up = Linear(d_in, d_ff, rho=rho_up, sp=sp, seed=seed + 11,
                         dtype=pd, logical_axes=("embed", "mlp"))
        self.gate = Linear(d_in, d_ff, rho=rho_up, sp=sp, seed=seed + 12,
                           dtype=pd, logical_axes=("embed", "mlp")) \
            if cfg.ffn_gated else None
        self.down = Linear(d_ff, cfg.d_model, rho=rho_down, sp=sp,
                           seed=seed + 13, dtype=pd,
                           logical_axes=("mlp", "embed"))
        self.act = activation(cfg.act)

    def init(self, key: jax.Array) -> dict:
        ks = jax.random.split(key, 3)
        p = {"up": self.up.init(ks[0]), "down": self.down.init(ks[1])}
        if self.gate is not None:
            p["gate"] = self.gate.init(ks[2])
        return p

    def spec(self) -> dict:
        s = {"up": self.up.spec(), "down": self.down.spec()}
        if self.gate is not None:
            s["gate"] = self.gate.spec()
        return s

    @jax.named_scope("ffn")
    def __call__(self, params: dict, x: jax.Array) -> jax.Array:
        fused = _FUSABLE.get(self.cfg.act)
        if self.gate is not None:
            h = self.up(params["up"], x)
            # the activation fuses into the *gate* junction's epilogue
            g = self.gate(params["gate"], x, activation=fused)
            if fused is None:
                g = self.act(g)
            h = g * h
        else:
            h = self.up(params["up"], x, activation=fused)
            if fused is None:
                h = self.act(h)
        h = shard(h, "batch", "seq", "mlp_act")
        return self.down(params["down"], h)


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


class MoE:
    """Routed experts (+ optional always-on shared experts)."""

    def __init__(self, cfg: ModelConfig, seed: int = 0,
                 impl: str = "shardmap"):
        assert cfg.moe is not None
        self.cfg = cfg
        self.mc = cfg.moe
        self.impl = impl
        self.d = cfg.d_model
        self.d_e = self.mc.d_expert
        self.act = activation(cfg.act)
        pd = cfg.param_dtype
        self.pd = jnp.dtype(pd)
        self.seed = seed
        # Pre-defined sparse expert junctions: one pattern per junction
        # family, shared by every expert (the batched csd_matmul layout).
        sp = cfg.sparsity
        self.backend = sp.backend
        self.up_pat = self.gate_pat = self.down_pat = None
        if sp.enabled and sp.moe_sparsity:
            rho_up, rho_down = sp.rho_ffn
            self.up_pat = fit_block_pattern(self.d, self.d_e, rho_up, sp,
                                            seed=seed + 31,
                                            weight_dtype=self.pd)
            self.gate_pat = fit_block_pattern(self.d, self.d_e, rho_up, sp,
                                              seed=seed + 32,
                                              weight_dtype=self.pd)
            self.down_pat = fit_block_pattern(self.d_e, self.d, rho_down,
                                              sp, seed=seed + 33,
                                              weight_dtype=self.pd)
        if self.mc.n_shared:
            self.shared = FFN(cfg, d_ff=self.mc.n_shared * self.d_e,
                              seed=seed + 29)
        else:
            self.shared = None

    def _expert_w(self, key, pat, n_in, n_out, E):
        """One stacked expert weight: block-sparse slab when the junction
        has a pattern, dense (E, n_in, n_out) otherwise."""
        if pat is not None:
            fan_in = pat.d_in_b * pat.block_in
            return jax.random.normal(
                key, (E, pat.n_rb, pat.d_in_b, pat.block_in, pat.block_out),
                self.pd) * float(np.sqrt(1.0 / fan_in))
        return jax.random.normal(key, (E, n_in, n_out), self.pd) \
            * float(np.sqrt(1.0 / n_in))

    # expert weights are stored stacked: (E, d, d_e) / (E, d_e, d) dense,
    # (E, n_rb, d_in_b, bL, bR) when the junction is pre-defined sparse
    def init(self, key: jax.Array) -> dict:
        mc, d, d_e = self.mc, self.d, self.d_e
        ks = jax.random.split(key, 5)
        E = mc.n_routed
        p = {
            "router": jax.random.normal(ks[0], (d, E), self.pd)
            * float(np.sqrt(1.0 / d)),
            "up": self._expert_w(ks[1], self.up_pat, d, d_e, E),
            "gate": self._expert_w(ks[2], self.gate_pat, d, d_e, E),
            "down": self._expert_w(ks[3], self.down_pat, d_e, d, E),
        }
        if self.shared is not None:
            p["shared"] = self.shared.init(ks[4])
        return p

    def spec(self) -> dict:
        def wspec(pat, dense_axes):
            # sparse slab (E, n_rb, d_in_b, bL, bR). The sharded dim must
            # match the dispatch mode's compute partition, or every step
            # pays a reshard at shard_map entry: shardmap dispatch shards
            # experts over the model axis ("expert"); local dispatch runs
            # the model-parallel junction path, which chunks the
            # block-row dim ("slab"). Both rules resolve to the same
            # axis, so they cannot be annotated together.
            if pat is None:
                return dense_axes
            return ("expert", None, None, None, None) \
                if self.impl == "shardmap" \
                else (None, "slab", None, None, None)
        s = {"router": (None, None),
             "up": wspec(self.up_pat, ("expert", "embed", None)),
             "gate": wspec(self.gate_pat, ("expert", "embed", None)),
             "down": wspec(self.down_pat, ("expert", None, "embed"))}
        if self.shared is not None:
            s["shared"] = self.shared.spec()
        return s

    def capacity(self, t_local: int) -> int:
        mc = self.mc
        c = int(np.ceil(t_local * mc.top_k / mc.n_routed
                        * mc.capacity_factor))
        return max(c, 1)

    # -- routing (shared by both impls) -------------------------------------

    @jax.named_scope("moe/router")
    def _route(self, params, x2d):
        """x2d: (T, d) -> gates (T,k), ids (T,k), aux losses."""
        mc = self.mc
        logits = (x2d.astype(jnp.float32)
                  @ params["router"].astype(jnp.float32))  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, ids = jax.lax.top_k(probs, mc.top_k)
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        # Switch-style load balance + router z-loss. ce = fraction of
        # tokens whose top-1 lands on each expert: a bincount (segment
        # count), not a (T, E) one-hot materialization — ids carry no
        # gradient either way, so only the intermediate changes
        ce = jnp.bincount(ids[:, 0], length=mc.n_routed).astype(
            jnp.float32) / ids.shape[0]
        me = jnp.mean(probs, axis=0)
        lb_loss = mc.n_routed * jnp.sum(me * ce)
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
        aux = {"moe_lb": lb_loss, "moe_z": mc.router_zloss * z_loss}
        return gates, ids, aux

    def _junction(self, xe, w, pat, activation=None, sharded=False,
                  w_scale=None):
        """One stacked expert junction: batched csd_matmul when pre-defined
        sparse, stacked einsum (the kernels.ref oracle form) when dense.
        ``sharded`` opts into the model-parallel junction path (per-expert
        slabs partitioned over the slab axis) when the installed rules and
        this junction's pattern allow it. ``w_scale`` selects the int8
        slab path (inference only — the slab enters uncast)."""
        cdt = xe.dtype
        if pat is not None:
            kw = junction_shard_kwargs(pat) if sharded else {}
            if w_scale is not None:
                return kops.csd_matmul(xe, w, pat, activation=activation,
                                       backend=self.backend,
                                       w_scale=w_scale, **kw)
            return kops.csd_matmul(xe, w.astype(cdt), pat,
                                   activation=activation,
                                   backend=self.backend, **kw)
        y = jnp.einsum("ecd,edf->ecf", xe, w.astype(cdt))
        return kops.apply_activation(y, activation)

    @jax.named_scope("moe/experts")
    def _expert_ffn(self, up, gate, down, xe, sharded=False,
                    scales=(None, None, None)):
        """xe: (E_loc, C, d) -> (E_loc, C, d), batched over experts — the
        expert compute of BOTH dispatch modes (gshard-style local and
        shard_map expert-parallel). Each junction routes through the
        batched block-sparse csd_matmul path when it carries a pattern;
        a fusable activation rides the gate junction's epilogue.

        ``sharded=True`` (local dispatch mode only — the shard_map mode
        already spends the model axis on expert parallelism) partitions
        every expert's slab over the slab axis: the 5-D batched kernels
        run shard-local with the expert index still the leading grid dim.

        ``scales`` = (up_scale, gate_scale, down_scale): per-block f32
        scales of int8 expert slabs (from ``quantize_tree``).
        """
        s_up, s_gate, s_down = scales
        fused = _FUSABLE.get(self.cfg.act) if self.gate_pat is not None \
            else None
        h = self._junction(xe, up, self.up_pat, sharded=sharded,
                           w_scale=s_up)
        g = self._junction(xe, gate, self.gate_pat, activation=fused,
                           sharded=sharded, w_scale=s_gate)
        if fused is None:
            g = self.act(g)
        return self._junction(g * h, down, self.down_pat, sharded=sharded,
                              w_scale=s_down)

    # -- local (single-shard) sort-based dispatch ----------------------------

    @jax.named_scope("moe/dispatch")
    def _dispatch_local(self, x2d, gates, ids, capacity):
        """Build (E, C) token-index and gate buffers from local routing,
        and gather each expert's (C, d) input rows; returns (xe, buf_tok,
        buf_gate).

        Gather form: after the stable sort by expert id, expert ``e``'s
        assignments occupy sorted rows ``[starts[e], starts[e]+counts[e])``
        — buffer cell ``(e, c)`` is a ``jnp.take`` at ``starts[e]+c``
        (over-capacity tails fall off the end of the window). This
        replaces the old scatter build (``.at[sid, pos].set``), whose
        (T*k -> E*(C+1)) scatter dominated dispatch cost at low expert
        density; same buffers, same drop policy.
        """
        mc = self.mc
        T = x2d.shape[0]
        k, E, C = mc.top_k, mc.n_routed, capacity
        flat_ids = ids.reshape(-1)
        order = jnp.argsort(flat_ids, stable=True)
        stok = (order // k).astype(jnp.int32)
        sgate = gates.reshape(-1)[order]
        counts = jnp.bincount(flat_ids, length=E)
        starts = jnp.cumsum(counts) - counts
        gidx = starts[:, None] + jnp.arange(C)[None]       # (E, C)
        valid = jnp.arange(C)[None] < counts[:, None]
        gidx = jnp.clip(gidx, 0, T * k - 1)
        buf_tok = jnp.where(valid, jnp.take(stok, gidx),
                            jnp.int32(T))
        buf_gate = jnp.where(valid, jnp.take(sgate, gidx), 0.0)
        xp = jnp.concatenate([x2d, jnp.zeros((1, x2d.shape[1]), x2d.dtype)],
                             axis=0)
        return xp[buf_tok], buf_tok, buf_gate  # xe: (E, C, d)

    @jax.named_scope("moe/combine")
    def _combine_local(self, ye, buf_tok, buf_gate, T):
        """Weight expert outputs by their gates and segment-sum them back
        onto token rows (row T is the dispatch-padding sink)."""
        d = ye.shape[-1]
        yw = ye * buf_gate[..., None].astype(ye.dtype)
        y = jax.ops.segment_sum(yw.reshape(-1, d), buf_tok.reshape(-1),
                                num_segments=T + 1)
        return y[:T]

    def _moe_local(self, params, x2d, capacity):
        gates, ids, aux = self._route(params, x2d)
        xe, buf_tok, buf_gate = self._dispatch_local(x2d, gates, ids,
                                                     capacity)
        ye = self._expert_ffn(params["up"], params["gate"], params["down"],
                              xe, sharded=True,
                              scales=(params.get("up_scale"),
                                      params.get("gate_scale"),
                                      params.get("down_scale")))
        return self._combine_local(ye, buf_tok, buf_gate, x2d.shape[0]), aux

    # -- expert-parallel shard_map implementation ----------------------------

    def _moe_shardmap(self, params, x2d_shape_hint, x, mesh, ep_axis):
        """x: (B, S, d). Experts sharded over ``ep_axis``; tokens keep their
        (batch, seq) sharding. all_to_all moves capacity buffers to expert
        owners and back within each data row."""
        from jax.sharding import PartitionSpec as P
        from .common import logical_to_spec

        mc = self.mc
        n_ep = mesh.shape[ep_axis]
        E, k = mc.n_routed, mc.top_k
        e_loc = E // n_ep
        x_spec = logical_to_spec("batch", "seq", None)

        def w_spec(pat):
            # expert dim sharded over ep_axis; dense (E, n, n) weights have
            # 2 trailing dims, sparse slabs (E, n_rb, d_in_b, bL, bR) have 4
            return P(ep_axis, *([None] * (2 if pat is None else 4)))
        r_spec = P(None, None)
        all_axes = tuple(mesh.axis_names)
        quant = "up_scale" in params

        def local_fn(router, up, gate, down, xl, *sc):
            scales = sc if quant else (None, None, None)
            b, s, d = xl.shape
            t_loc = b * s
            x2d = xl.reshape(t_loc, d)
            gates, ids, aux = self._route({"router": router}, x2d)
            c_src = self.capacity(t_loc)
            xe, buf_tok, buf_gate = self._dispatch_local(x2d, gates, ids,
                                                         c_src)
            with jax.named_scope("moe/dispatch"):
                # ship capacity buffers to expert owners: E = n_ep * e_loc
                xr = jax.lax.all_to_all(
                    xe.reshape(n_ep, e_loc, c_src, d), ep_axis, 0, 0,
                    tiled=False)  # (n_ep, e_loc, C_src, d): sources stacked
                xr = jnp.moveaxis(xr, 0, 1).reshape(e_loc, n_ep * c_src, d)
            ye = self._expert_ffn(up, gate, down, xr, scales=scales)
            with jax.named_scope("moe/combine"):
                ye = jnp.moveaxis(ye.reshape(e_loc, n_ep, c_src, d), 1, 0)
                yb = jax.lax.all_to_all(ye, ep_axis, 0, 0, tiled=False)
                yb = yb.reshape(E, c_src, d)  # back at the source
            y = self._combine_local(yb, buf_tok, buf_gate, t_loc)
            aux = {n: jax.lax.pmean(v, all_axes) for n, v in aux.items()}
            return y.reshape(b, s, d), aux

        in_specs = (r_spec, w_spec(self.up_pat), w_spec(self.gate_pat),
                    w_spec(self.down_pat), x_spec)
        operands = [params["router"], params["up"], params["gate"],
                    params["down"], x]
        if quant:
            # (E, n_rb, d_in_b) scales ride the expert sharding of their slab
            in_specs = in_specs + (P(ep_axis, None, None),) * 3
            operands += [params["up_scale"], params["gate_scale"],
                         params["down_scale"]]
        fn = jax.shard_map(
            local_fn, mesh=mesh, in_specs=in_specs,
            out_specs=(x_spec, {n: P() for n in ("moe_lb", "moe_z")}),
            check_vma=False)
        return fn(*operands)

    # -- public --------------------------------------------------------------

    def __call__(self, params: dict, x: jax.Array) -> Tuple[jax.Array, dict]:
        """x: (B, S, d) -> (y, aux_losses)."""
        cfg, mc = self.cfg, self.mc
        b, s, d = x.shape
        mesh = current_mesh()
        use_sm = (self.impl == "shardmap" and mesh is not None
                  and "model" in mesh.axis_names
                  and mc.n_routed % mesh.shape["model"] == 0)
        if use_sm:
            y, aux = self._moe_shardmap(params, None, x, mesh, "model")
        else:
            x2d = x.reshape(b * s, d)
            y2d, aux = self._moe_local(params, x2d,
                                       self.capacity(b * s))
            y = y2d.reshape(b, s, d)
        if self.shared is not None:
            y = y + self.shared(params["shared"], x)
        return y.astype(x.dtype), aux
