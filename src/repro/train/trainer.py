"""The training engine: jitted sharded step, grad accumulation, mixed
precision, DiLoCo-style cross-pod sync with compressed deltas, and
checkpoint-resume.

Distributed-optimization tricks implemented here (DESIGN.md §5):

* grad-accum microbatches via ``lax.scan`` — XLA overlaps microbatch k+1's
  compute with microbatch k's gradient reduce-scatter;
* fused optimizer (no separate update dispatch — the paper's FF/BP/UP
  operational parallelism, realized by the XLA scheduler);
* DiLoCo outer loop (``diloco_period``): pods run local AdamW and exchange
  int8 error-feedback-compressed parameter deltas every K steps — cutting
  inter-pod (DCN) traffic by ~4x/K vs per-step gradient all-reduce;
* donated buffers: params/opt-state update in place;
* sharded sparse junctions: the TRAIN rules map the ``"slab"`` logical
  axis to ``model``, so ``param_pspecs`` chunks every block-sparse weight
  slab (and its mirrored Adam state) on the block-row dim, and the jitted
  step — traced under ``mesh_context`` — runs those junctions through the
  model-parallel ``csd_matmul`` shard_map. UP (dw/db) is shard-local
  there, so the sharded optimizer state updates without any gradient
  collectives on the slab weights (ZeRO-style for free).
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..nn.common import mesh_context
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..optim import adam
from ..optim.compression import psum_compressed_tree
from ..sharding import policy
from .checkpoint import CheckpointManager


def _batch_tokens(batch: dict) -> int:
    """Tokens a batch feeds the model (batch x seq), for throughput."""
    for k in ("labels", "tokens"):
        if k in batch:
            return int(np.prod(batch[k].shape))
    leaf = next(iter(batch.values()))
    return int(np.prod(leaf.shape[:2]))


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    opt: adam.AdamWConfig = dataclasses.field(default_factory=adam.AdamWConfig)
    grad_accum: int = 1
    diloco_period: int = 0       # 0 = synchronous data parallel
    diloco_outer_lr: float = 0.7
    diloco_outer_momentum: float = 0.9
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 100
    checkpoint_keep: int = 3
    log_every: int = 10
    # observability: ``metrics`` routes per-step timing/loss/grad-norm
    # through the process obs registry (recording is host-side only — the
    # jitted step is identical either way). ``profile_dir`` captures a
    # jax.profiler trace of the whole fit() into that directory.
    metrics: bool = True
    profile_dir: Optional[str] = None


class Trainer:
    def __init__(self, model, cfg: TrainerConfig,
                 mesh: Optional[Mesh] = None,
                 rules: Optional[dict] = None,
                 registry: Optional[obs_metrics.Registry] = None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.obs = obs_metrics.resolve(registry, enabled=cfg.metrics)
        self._m_steps = self.obs.counter(
            "train_steps_total", "optimizer steps taken")
        self._m_tokens = self.obs.counter(
            "train_tokens_total", "tokens consumed (batch * seq)")
        self._m_step_s = self.obs.histogram(
            "train_step_seconds",
            "per-step wall clock (first step includes compile)")
        self._m_loss = self.obs.gauge("train_loss", "last logged loss")
        self._m_gnorm = self.obs.gauge(
            "train_grad_norm", "last logged global gradient norm")
        self._m_tps = self.obs.gauge(
            "train_tokens_per_s",
            "throughput over the last log window")
        self._m_micro = self.obs.gauge(
            "train_microbatches", "grad-accum microbatches per step")
        self.rules = rules or (
            policy.rules_for("train", 0, mesh,
                             getattr(model, "cfg", None)) if mesh else {})
        self.ckpt = CheckpointManager(cfg.checkpoint_dir,
                                      cfg.checkpoint_keep) \
            if cfg.checkpoint_dir else None
        self._step_fn = None
        if mesh is not None:
            import jax as _jax
            pstruct = _jax.eval_shape(model.init, _jax.random.key(0))
            pspec = policy.param_pspecs(model.spec(), self.rules)
            self.param_sharding = policy.named(mesh, pspec, pstruct)
            self.opt_sharding = policy.named(
                mesh, policy.opt_pspecs(pspec),
                _jax.eval_shape(__import__("repro.optim.adam", fromlist=["init"]).init, pstruct))
        else:
            self.param_sharding = None
            self.opt_sharding = None

    # -- state ------------------------------------------------------------------

    def init_state(self, key: jax.Array) -> Tuple[Any, Any]:
        if self.mesh is not None:
            with self.mesh, mesh_context(self.mesh, self.rules):
                params = jax.jit(
                    self.model.init,
                    out_shardings=self.param_sharding)(key)
                opt = jax.jit(adam.init,
                              out_shardings=self.opt_sharding)(params)
        else:
            params = self.model.init(key)
            opt = adam.init(params)
        return params, opt

    # -- the step ----------------------------------------------------------------

    def _loss_fn(self, params, batch):
        return self.model.loss(params, batch)

    def _make_step(self, batch_example: dict):
        cfg = self.cfg
        accum = cfg.grad_accum

        def step(params, opt, batch):
            if accum > 1:
                def micro(carry, mb):
                    g_acc, l_acc = carry
                    (loss, metrics), g = jax.value_and_grad(
                        self._loss_fn, has_aux=True)(params, mb)
                    g_acc = jax.tree.map(jnp.add, g_acc, g)
                    return (g_acc, l_acc + loss), metrics

                mbs = jax.tree.map(
                    lambda x: x.reshape((accum, x.shape[0] // accum)
                                        + x.shape[1:]), batch)
                zeros = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (g, loss_sum), metrics = jax.lax.scan(
                    micro, (zeros, 0.0), mbs)
                g = jax.tree.map(lambda x: x / accum, g)
                loss = loss_sum / accum
                metrics = jax.tree.map(lambda m: m[-1], metrics)
            else:
                (loss, metrics), g = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(params, batch)
            params, opt, opt_metrics = adam.update(cfg.opt, g, opt, params)
            metrics = dict(metrics, **opt_metrics, loss=loss)
            return params, opt, metrics

        if self.mesh is None:
            return jax.jit(step, donate_argnums=(0, 1))
        batch_spec = policy.named(
            self.mesh, policy.batch_pspecs(batch_example, self.rules))
        return jax.jit(
            step,
            in_shardings=(self.param_sharding, self.opt_sharding,
                          batch_spec),
            out_shardings=(self.param_sharding, self.opt_sharding, None),
            donate_argnums=(0, 1))

    def step_fn(self, batch_example: dict):
        if self._step_fn is None:
            self._step_fn = self._make_step(batch_example)
        return self._step_fn

    # -- DiLoCo outer sync ----------------------------------------------------------

    def make_diloco_state(self, params):
        # explicit copies: params are donated by the step fn, and astype on
        # an already-f32 array would alias the donated buffer
        return {"anchor": jax.tree.map(
                    lambda p: jnp.array(p, jnp.float32, copy=True), params),
                "outer_m": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params),
                "err": jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)}

    def diloco_sync(self, params, dstate, axis_name: Optional[str] = None):
        """Outer step: compressed cross-pod mean of (anchor - params) deltas
        + Nesterov-style outer momentum; returns (params, dstate)."""
        cfg = self.cfg

        def inner(params, anchor, outer_m, err):
            delta = jax.tree.map(
                lambda a, p: a - p.astype(jnp.float32), anchor, params)
            mean_delta, new_err = psum_compressed_tree(delta, err, axis_name)
            new_m = jax.tree.map(
                lambda m, d: cfg.diloco_outer_momentum * m + d,
                outer_m, mean_delta)
            new_anchor = jax.tree.map(
                lambda a, m: a - cfg.diloco_outer_lr * m, anchor, new_m)
            # explicit copy: params are donated by the next step; they must
            # not alias the anchor (f32->f32 astype is a no-op)
            new_params = jax.tree.map(
                lambda p, a: jnp.array(a, p.dtype, copy=True),
                params, new_anchor)
            return new_params, new_anchor, new_m, new_err

        if axis_name is None or self.mesh is None \
                or axis_name not in self.mesh.axis_names:
            p, a, m, e = inner(params, dstate["anchor"], dstate["outer_m"],
                               dstate["err"])
        else:
            mesh = self.mesh
            spec = jax.tree.map(lambda _: P(), params)
            fn = jax.shard_map(
                inner, mesh=mesh,
                in_specs=(spec, spec, spec, spec),
                out_specs=(spec, spec, spec, spec), check_vma=False)
            p, a, m, e = fn(params, dstate["anchor"], dstate["outer_m"],
                            dstate["err"])
        return p, {"anchor": a, "outer_m": m, "err": e}

    # -- the loop -----------------------------------------------------------------

    def fit(self, data_iter: Iterator[dict], steps: int,
            key: Optional[jax.Array] = None, resume: bool = True,
            params=None, opt=None,
            on_step: Optional[Callable[[int, dict], None]] = None):
        cfg = self.cfg
        start = 0
        if params is None:
            params, opt = self.init_state(key or jax.random.key(0))
        if resume and self.ckpt is not None and self.ckpt.latest_step():
            start = self.ckpt.latest_step()
            (params, opt), _ = self.ckpt.restore(
                start, (params, opt),
                (self.param_sharding, self.opt_sharding)
                if self.mesh else None)
        dstate = self.make_diloco_state(params) \
            if cfg.diloco_period else None
        history = []
        self._m_micro.set(cfg.grad_accum)
        win_t0 = time.perf_counter()
        win_tokens = 0
        ctx = mesh_context(self.mesh, self.rules) if self.mesh else None
        if ctx:
            ctx.__enter__()
        try:
            with obs_trace.profile_trace(cfg.profile_dir):
                for step in range(start, steps):
                    batch = next(data_iter)
                    batch = {k: jnp.asarray(v) for k, v in batch.items()}
                    fn = self.step_fn(batch)
                    t0 = time.perf_counter()
                    with obs_trace.span("train/step", registry=self.obs):
                        params, opt, metrics = fn(params, opt, batch)
                    # dispatch wall-clock: under async dispatch this
                    # converges to true step time once the queue fills
                    self._m_step_s.observe(time.perf_counter() - t0)
                    n_tok = _batch_tokens(batch)
                    win_tokens += n_tok
                    self._m_steps.inc()
                    self._m_tokens.inc(n_tok)
                    if cfg.diloco_period \
                            and (step + 1) % cfg.diloco_period == 0:
                        params, dstate = self.diloco_sync(
                            params, dstate,
                            "pod" if (self.mesh and "pod" in
                                      self.mesh.axis_names) else None)
                    if (step + 1) % cfg.log_every == 0 \
                            or step == steps - 1:
                        m = {k: float(v) for k, v in metrics.items()}
                        # float() above synced the device, so the window
                        # clock now covers real compute, not just dispatch
                        now = time.perf_counter()
                        tps = win_tokens / max(now - win_t0, 1e-9)
                        win_t0, win_tokens = now, 0
                        m["tokens_per_s"] = tps
                        self._m_loss.set(m.get("loss", float("nan")))
                        if "grad_norm" in m:
                            self._m_gnorm.set(m["grad_norm"])
                        self._m_tps.set(tps)
                        history.append({"step": step + 1, **m})
                        if on_step:
                            on_step(step + 1, m)
                        else:
                            print(f"step {step + 1:>6d}  "
                                  f"loss {m.get('loss', float('nan')):.4f}  "
                                  f"tok/s {tps:,.0f}  "
                                  f"grad_norm "
                                  f"{m.get('grad_norm', float('nan')):.3f}")
                    if self.ckpt \
                            and (step + 1) % cfg.checkpoint_every == 0:
                        self.ckpt.save(step + 1, (params, opt),
                                       async_=True)
            if self.ckpt:
                self.ckpt.save(steps, (params, opt))
                self.ckpt.wait()
        finally:
            if ctx:
                ctx.__exit__(None, None, None)
        return params, opt, history
