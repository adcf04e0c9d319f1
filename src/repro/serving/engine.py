"""ServingEngine: continuous-batching inference over the paged cache.

One ``step()`` executes a scheduler plan: chunked prefill for sequences
still consuming their prompt (through the same fused csd_matmul junctions
as training; attention over previously-cached pages by gather) interleaved
with batched decode for every running sequence (through the
paged-attention kernel — Pallas on TPU, gather-XLA elsewhere). Fixed
accelerator memory (the page pool) serves any number / length of requests
by time-multiplexing the per-step token budget — the serving analog of the
paper's flexible-``z`` junction hardware.

Two throughput multipliers keep that budget (the ``z`` lanes) busy when
decode dominates:

* **speculative decode** (``spec_k > 0``): a model-free prompt-lookup
  drafter proposes up to ``k`` continuation tokens per slot; the engine
  verifies pending + drafts in ONE multi-token ``paged_step`` (the chunk
  path prefill already uses) and accepts the longest greedily-matching
  prefix, rolling rejected KV back via ``kv_cache.truncate``. Greedy
  acceptance keeps the output token-identical to plain decode.
* **batched prefill**: the scheduler packs equal-length power-of-two
  chunks from different sequences into one B>1 call, collapsing
  O(slots) sequential chunk launches into O(log prefill_chunk) batched
  ones.

The jitted step function has one signature for both phases; distinct chunk
lengths trace separate executables (the scheduler emits power-of-two
chunks, so there are O(log prefill_chunk) of them, plus at most one
verify shape at ``1 + spec_k``). Prompt chunks are exact — rows are
either fully valid or fully inactive, never partially padded — so SSM
recurrent state advances over real tokens only and stays bit-identical
to a full-sequence prefill.

Sharded decode (``mesh=...``): the engine jits ``LM.paged_step`` once
under the SERVE mesh rules — params placed by ``policy.param_pspecs``
(block-sparse slabs row-sharded on the ``slab`` axis so every junction
runs the model-parallel ``csd_matmul`` shard_map), the paged KV pools
partitioned on the same axis (``policy.paged_cache_pspecs``: pages are
the cache's sequence axis -> context-parallel KV; pick ``total_pages ≡ -1
mod axis_size`` so the +1 trash page divides). Scheduling stays on the
host and is byte-identical to the single-device engine, so sharded decode
is token-parity testable against it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.quant import QuantConfig, quantize_tree
from ..nn.common import dtype_of, mesh_context
from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from .scheduler import Request, Scheduler, StepPlan
from .spec import PromptLookupDrafter


@jax.jit
def sample_greedy(logits: jax.Array, row=None) -> jax.Array:
    """Greedy tokens of a step's logits (B, C, V): the argmax over the
    vocabulary at each position of every row, (B, C), or of ``row``, (C,)."""
    return jnp.argmax(logits if row is None else logits[row], axis=-1)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine knobs. ``token_budget`` is the per-step work quantum (the
    paper's degree-of-parallelism ``z``); ``page_size`` the KV allocation
    granularity; ``max_slots`` the number of resident sequences."""
    max_slots: int = 8
    page_size: int = 16
    total_pages: int = 128
    max_pages_per_seq: int = 32
    token_budget: int = 64
    prefill_chunk: int = 32
    backend: str = "auto"       # auto | xla | pallas (paged decode kernel)
    interpret: bool = False     # Pallas interpret mode (CPU tests)
    greedy: bool = True
    temperature: float = 1.0
    # speculative decode: up to spec_k prompt-lookup draft tokens per
    # decode slot, verified in one multi-token step (0 = off). Greedy
    # only, and auto-disabled for stacks with recurrent (mamba) layers:
    # KV pages can be truncated after a rejected draft, a recurrence
    # that already stepped over it cannot.
    spec_k: int = 0
    spec_ngram: int = 3         # longest suffix n-gram the drafter matches
    # observability: ``metrics`` routes the engine's host-side counters/
    # gauges/histograms through the process obs registry (False = no-op
    # registry; the jitted step functions are identical either way —
    # recording never enters a traced program). ``metrics_port`` serves
    # the registry at http://127.0.0.1:<port>/metrics (0 = ephemeral).
    metrics: bool = True
    metrics_port: Optional[int] = None
    # int8 inference (core.quant.QuantConfig): quantize the checkpoint's
    # block-sparse slabs per-block at load (weights=True) and/or store KV
    # pages as int8 with per-token scales (kv=True). None falls back to
    # the model's SparsityConfig.quant, so a model built with the knob
    # serves quantized without any engine-side flag.
    quant: Optional["QuantConfig"] = None


class ServingEngine:
    """Continuous-batching engine: add requests any time, call ``step()``
    (or ``run()``) and collect finished generations."""

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, key: Optional[jax.Array] = None, mesh=None, rules=None,
                 registry: Optional[obs_metrics.Registry] = None,
                 **overrides):
        cfg = config or EngineConfig(**overrides)
        if overrides and config is not None:
            raise ValueError("pass EngineConfig or overrides, not both")
        mc = model.cfg
        if getattr(mc, "enc_dec", None) is not None:
            raise NotImplementedError(
                "paged serving supports decoder-only models (enc-dec "
                "serves through the legacy loop)")
        if mc.input_mode != "tokens":
            raise NotImplementedError(
                "paged serving expects token inputs")
        moe = getattr(mc, "moe", None)
        if moe is not None and moe.capacity_factor * moe.top_k \
                < moe.n_routed:
            # the batched decode step runs garbage rows for inactive
            # slots; with finite expert capacity those rows would compete
            # with (and can evict) real tokens from their expert buckets,
            # silently corrupting active requests. Serving MoE requires
            # dropless decode: capacity_factor >= n_routed / top_k.
            raise NotImplementedError(
                f"paged serving with capacity-constrained MoE "
                f"(capacity_factor={moe.capacity_factor}): rebuild the "
                f"model with capacity_factor >= n_routed/top_k = "
                f"{moe.n_routed / moe.top_k:.1f} (dropless decode) or "
                f"use the legacy dense-cache loop")
        self.model = model
        self.config = cfg
        # -- int8 inference: quantize once at load, serve quantized ------
        # Training stays full width; the engine is the one place the
        # QuantConfig is applied. quantize_tree rewrites every block-sparse
        # slab to int8 + per-block scales and extends the sharding spec in
        # lock-step, so the mesh path below places the scale leaves with
        # the same rules as their slabs.
        qc = cfg.quant if cfg.quant is not None \
            else getattr(getattr(mc, "sparsity", None), "quant", None)
        self.quant = qc
        spec = model.spec()
        if qc is not None and qc.weights:
            params, spec = quantize_tree(params, spec)
        self._spec = spec
        self.params = params
        self.key = key if key is not None else jax.random.key(0)
        # speculative decode is greedy-only (acceptance compares argmax
        # continuations) and needs rollback: paged KV truncates, mamba
        # recurrent state does not — clamp k to 0 for recurrent stacks
        self.spec_k = cfg.spec_k if cfg.greedy \
            and "mamba" not in mc.layer_kinds else 0
        drafter = PromptLookupDrafter(cfg.spec_ngram) if self.spec_k \
            else None
        # -- observability: all recording is host-side, around (never
        # inside) the jitted step — with metrics off the same executables
        # compile byte-identically (tests/test_obs.py proves it on HLO)
        self.obs = obs_metrics.resolve(registry, enabled=cfg.metrics)
        self._m_req = self.obs.counter(
            "serving_requests_total",
            "request lifecycle events (added/finished/rejected)")
        self._m_tok = self.obs.counter(
            "serving_tokens_total",
            "tokens processed per phase (prefill/decode/spec_draft)")
        self._m_emit = self.obs.counter(
            "serving_emitted_tokens_total", "generated tokens emitted")
        self._m_spec = self.obs.counter(
            "serving_spec_tokens_total",
            "speculative draft tokens by outcome "
            "(proposed/accepted/rolled_back)")
        self._m_ttft = self.obs.histogram(
            "serving_ttft_seconds", "time from add_request to first token")
        self._m_itl = self.obs.histogram(
            "serving_itl_seconds",
            "inter-token latency per slot (consecutive emitted tokens)")
        self._m_queue = self.obs.gauge(
            "serving_queue_depth", "requests waiting for admission")
        self._m_slots = self.obs.gauge(
            "serving_active_slots", "resident sequences")
        self._m_occ = self.obs.gauge(
            "serving_page_occupancy", "fraction of the KV page pool in use")
        self._m_pages_hw = self.obs.gauge(
            "serving_pages_highwater", "max pages ever in use at once")
        self.sched = Scheduler(
            slots=cfg.max_slots, total_pages=cfg.total_pages,
            page_size=cfg.page_size,
            max_pages_per_seq=cfg.max_pages_per_seq,
            token_budget=cfg.token_budget,
            prefill_chunk=cfg.prefill_chunk,
            window=self._reclaim_window(mc),
            spec_k=self.spec_k, drafter=drafter, obs=self.obs)
        self._http = obs_metrics.serve_http(self.obs, cfg.metrics_port) \
            if cfg.metrics_port is not None else None
        # -- measured decode dispatch (PR 10): surface which decode
        # kernel this engine's regime will run. The authoritative consult
        # happens at trace time inside paged_decode_attention (so it sees
        # the actual q dtype); this lookup records the decision as an obs
        # counter so ``repro.obs.dump`` shows tuned vs heuristic serving.
        heads = getattr(mc, "n_heads", 0)
        if cfg.backend == "auto" and heads:
            from .. import tune
            hkv = getattr(mc, "n_kv_heads", heads) or heads
            ent = tune.decide_decode(
                b=cfg.max_slots, h_kv=hkv, groups=heads // hkv,
                head_dim=mc.head_dim, page_size=cfg.page_size,
                n_pages=cfg.max_pages_per_seq, pool=cfg.total_pages,
                quant=bool(qc is not None and qc.kv),
                dtype=str(dtype_of(mc)))
            self.obs.counter(
                "repro_tune_engine_decode_total",
                "engine decode-kernel selection (tuned=cache hit)",
            ).inc(backend=ent["backend"] if ent else "heuristic",
                  tuned=str(ent is not None).lower())
        self.cache = model.stack.init_paged_cache(
            cfg.max_slots, cfg.total_pages, cfg.page_size, dtype_of(mc),
            quant_kv=bool(qc is not None and qc.kv))
        self._next_id = 0
        self.outputs: Dict[int, np.ndarray] = {}
        # per-request admission timestamps, pruned at first token (TTFT
        # recorded) and again at finish — bounded by in-flight requests.
        # TTFT/ITL themselves live in the obs histograms (label-free, so
        # state cannot grow with request count — the PR-7 ``ttft`` dict
        # grew forever).
        self._t_added: Dict[int, float] = {}
        self._last_tok: List[Optional[float]] = [None] * cfg.max_slots

        self.mesh = mesh
        self.rules = rules
        if mesh is not None:
            from ..sharding import policy
            if rules is None:
                self.rules = policy.rules_for("decode", cfg.max_slots,
                                              mesh, mc)
            pspec = policy.param_pspecs(self._spec, self.rules)
            self._param_sh = policy.named(mesh, pspec, params)
            cspec = policy.paged_cache_pspecs(self.cache, self.rules)
            self._cache_sh = policy.named(mesh, cspec, self.cache)
            self.params = jax.device_put(params, self._param_sh)
            self.cache = jax.device_put(self.cache, self._cache_sh)

        def raw_step(params, cache, page_table, tokens, pos, n_new,
                     slot_ids):
            return model.paged_step(
                params, tokens, pos, n_new, cache, page_table, slot_ids,
                backend=cfg.backend, interpret=cfg.interpret)

        def raw_verify(params, cache, page_table, tokens, pos, n_new,
                       slot_ids):
            # speculative verify: logits at EVERY chunk position, so the
            # host can accept the longest greedily-matching draft prefix
            return model.paged_step(
                params, tokens, pos, n_new, cache, page_table, slot_ids,
                backend=cfg.backend, interpret=cfg.interpret,
                all_logits=True)

        if mesh is not None:
            # one executable per phase under the SERVE mesh: params and the
            # paged pools keep their placement across steps, logits come
            # back replicated for host-side sampling
            jit_kw = dict(
                donate_argnums=(1,),
                in_shardings=(self._param_sh, self._cache_sh, None, None,
                              None, None, None),
                out_shardings=(None, self._cache_sh))
            self._step = jax.jit(raw_step, **jit_kw)
            self._verify = jax.jit(raw_verify, **jit_kw)
        else:
            self._step = jax.jit(raw_step, donate_argnums=(1,))
            self._verify = jax.jit(raw_verify, donate_argnums=(1,))

    @staticmethod
    def _reclaim_window(mc) -> Optional[int]:
        """Sliding-window page reclamation is sound only when EVERY
        attention layer is windowed (all page pools share one page table,
        so a page may be freed only when no layer can still read it);
        mamba layers carry no pages and don't constrain it."""
        kinds = set(mc.layer_kinds)
        if mc.attn_window is not None and kinds <= {"local", "mamba"} \
                and "local" in kinds and mc.hybrid is None:
            return int(mc.attn_window)
        return None

    def _in_ctx(self):
        return mesh_context(self.mesh, self.rules) if self.mesh is not None \
            else contextlib.nullcontext()

    # -- request intake ----------------------------------------------------

    def _reject(self, reason: str, msg: str) -> ValueError:
        """Admission rejection: count it, return the error to raise."""
        self._m_req.inc(event="rejected", reason=reason)
        return ValueError(msg)

    def add_request(self, prompt, max_new_tokens: int,
                    req_id: Optional[int] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0:
            raise self._reject("empty_prompt", "empty prompt")
        if max_new_tokens < 1:
            raise self._reject("bad_budget",
                               "max_new_tokens must be >= 1")
        need = len(prompt) + max_new_tokens
        cap = min(self.config.max_pages_per_seq,
                  self.config.total_pages) * self.config.page_size
        if need > cap:
            raise self._reject(
                "too_long",
                f"request needs {need} tokens but a sequence can hold at "
                f"most {cap} (min(max_pages_per_seq, total_pages) * "
                f"page_size)")
        if req_id is None:
            req_id = self._next_id
        elif any(r.req_id == req_id for r in self.sched.waiting) or any(
                s is not None and s.req.req_id == req_id
                for s in self.sched.active):
            # a duplicate would silently cross-wire outputs/_t_added
            # between the two requests (dict keys collide)
            raise self._reject(
                "duplicate_id",
                f"req_id {req_id} is already queued or in flight")
        self._next_id = max(self._next_id, req_id) + 1
        self.sched.add(Request(req_id=req_id, prompt=prompt,
                               max_new_tokens=max_new_tokens))
        self._t_added[req_id] = time.perf_counter()
        self._m_req.inc(event="added")
        return req_id

    # -- sampling ----------------------------------------------------------

    def _sample(self, logits: jax.Array, slot: int) -> int:
        """The token after ``slot``'s row of a step's logits (B, 1, V)."""
        if self.config.greedy:
            return int(sample_greedy(logits, slot)[0])
        logits = logits[slot, 0]
        seq = self.sched.active[slot]
        # per-request stream, folded by absolute position: a preempted and
        # recomputed sequence re-draws identical tokens
        k = jax.random.fold_in(self.key, seq.req.req_id)
        k = jax.random.fold_in(k, len(seq.tokens))
        return int(jax.random.categorical(
            k, logits.astype(jnp.float32) / self.config.temperature))

    def _emit(self, slot: int) -> None:
        seq = self.sched.active[slot]
        now = time.perf_counter()
        if seq.n_generated == 1:
            # first token of this request: record TTFT and drop the
            # admission timestamp (pop = the leak fix; after a preemption
            # recompute n_generated > 1, so nothing double-records)
            t0 = self._t_added.pop(seq.req.req_id, None)
            if t0 is not None:
                self._m_ttft.observe(now - t0)
        prev = self._last_tok[slot]
        if prev is not None:
            self._m_itl.observe(now - prev)
        self._last_tok[slot] = now
        self._m_emit.inc()

    # -- the step ----------------------------------------------------------

    def step(self) -> Tuple[StepPlan, List[Tuple[int, np.ndarray]]]:
        """Run one engine step; returns (plan, finished) where finished is
        a list of (req_id, generated token ids).

        The host phases of a step are spans inside ``engine/step``:
        ``engine/schedule`` (the plan, slot resets); per prefill group
        ``engine/prefill`` (input assembly, copies, the call) then
        ``engine/commit`` (per-slot bookkeeping, where the sample of a
        prompt that ends is an ``engine/sync``); ``engine/decode`` or
        ``engine/verify``, then ``engine/sync`` (the argmax pull) and
        ``engine/commit``; ``engine/finish`` (finished requests, gauges).
        """
        with self._in_ctx(), self._span("engine/step"):
            return self._step_impl()

    def _span(self, name: str, **attrs):
        return obs_trace.span(name, registry=self.obs, **attrs)

    def _step_impl(self) -> Tuple[StepPlan, List[Tuple[int, np.ndarray]]]:
        cfg = self.config
        slots = cfg.max_slots
        with self._span("engine/schedule"):
            plan = self.sched.schedule()
            # a re-admitted slot may have hosted another sequence: clear
            # its recurrent (SSM) state before the first prefill chunk
            # touches it
            for slot in plan.admitted:
                self.cache = self.model.stack.reset_slot_state(self.cache,
                                                               slot)
                self._last_tok[slot] = None
            if plan.prefill_groups:
                n_pf = sum(len(toks) for group in plan.prefill_groups
                           for _, _, toks in group)
                self._m_tok.inc(n_pf, phase="prefill")
            if plan.decode_slots:
                self._m_tok.inc(len(plan.decode_slots), phase="decode")

        for group in plan.prefill_groups:
            # equal-length chunks from different sequences packed into
            # ONE batched call (rows are slot-indexed; slots without a
            # chunk this step ride along inactive with n_new == 0, so
            # there are O(log prefill_chunk) compiled shapes, not
            # O(slots) sequential launches)
            c = len(group[0][2])
            with self._span("engine/prefill", chunk=c, rows=len(group)):
                tokens = np.zeros((slots, c), np.int32)
                pos = np.zeros((slots,), np.int32)
                n_new = np.zeros((slots,), np.int32)
                for slot, start, toks in group:
                    tokens[slot, :len(toks)] = toks
                    pos[slot] = start
                    n_new[slot] = len(toks)
                logits, self.cache = self._step(
                    self.params, self.cache, self.sched.state.page_table,
                    jnp.asarray(tokens), jnp.asarray(pos),
                    jnp.asarray(n_new),
                    jnp.arange(slots, dtype=jnp.int32))
            with self._span("engine/commit"):
                for slot, start, toks in group:
                    self.sched.advance_prefill(slot, len(toks))
                    seq = self.sched.active[slot]
                    if not seq.prefilling \
                            and len(seq.tokens) == seq.n_prefilled:
                        # prompt fully cached and no pending token yet
                        # (also true right after a preemption recompute):
                        # sample it
                        with self._span("engine/sync"):
                            tok = self._sample(logits, slot)
                        self.sched.append_token(slot, tok)
                        self._emit(slot)

        kmax = max((len(plan.drafts.get(s, ()))
                    for s in plan.decode_slots), default=0)
        if plan.decode_slots and kmax == 0:
            # plain decode (C == 1): the PR-3 baseline path, bit-for-bit
            with self._span("engine/decode", rows=len(plan.decode_slots)):
                tokens = np.zeros((slots, 1), np.int32)
                n_new = np.zeros((slots,), np.int32)
                for s in plan.decode_slots:
                    tokens[s, 0] = self.sched.active[s].pending_token
                    n_new[s] = 1
                logits, self.cache = self._step(
                    self.params, self.cache, self.sched.state.page_table,
                    jnp.asarray(tokens), self.sched.state.seq_lens,
                    jnp.asarray(n_new),
                    jnp.arange(slots, dtype=jnp.int32))
            if cfg.greedy:
                with self._span("engine/sync"):
                    greedy_toks = np.asarray(sample_greedy(logits))[:, 0]
            with self._span("engine/commit"):
                for s in plan.decode_slots:
                    self.sched.note_decoded(s)
                    tok = int(greedy_toks[s]) if cfg.greedy \
                        else self._sample(logits, s)
                    self.sched.append_token(s, tok)
                    self._emit(s)
        elif plan.decode_slots:
            self._verify_decode(plan)

        with self._span("engine/finish"):
            finished = []
            for s in range(cfg.max_slots):
                seq = self.sched.active[s]
                if seq is not None and seq.done:
                    req, gen = self.sched.finish(s)
                    self.outputs[req.req_id] = gen
                    self._t_added.pop(req.req_id, None)
                    self._last_tok[s] = None
                    self._m_req.inc(event="finished")
                    finished.append((req.req_id, gen))
            self._m_queue.set(len(self.sched.waiting))
            self._m_slots.set(sum(s is not None for s in self.sched.active))
            total = cfg.total_pages
            used = total - self.sched._free
            self._m_occ.set(used / total)
            self._m_pages_hw.set_max(used)
        return plan, finished

    def _verify_decode(self, plan: StepPlan) -> None:
        """Speculative decode: verify pending + draft tokens for every
        decode slot in ONE multi-token ``paged_step`` (``n_new`` = 1 +
        drafts per row, chunk padded to ``1 + spec_k`` so exactly one
        extra executable is ever compiled). Greedy verification accepts
        the longest prefix of drafts matching the model's own argmax
        continuations — so accepted tokens are exactly what plain decode
        would have produced — and rejected tail KV rolls back through
        ``kv_cache.truncate``."""
        slots = self.config.max_slots
        c = 1 + self.spec_k
        with self._span("engine/verify", rows=len(plan.decode_slots),
                        chunk=c):
            tokens = np.zeros((slots, c), np.int32)
            n_new = np.zeros((slots,), np.int32)
            n_prop = 0
            for s in plan.decode_slots:
                row = [self.sched.active[s].pending_token] \
                    + plan.drafts.get(s, [])
                tokens[s, :len(row)] = row
                n_new[s] = len(row)
                n_prop += len(row) - 1
            if n_prop:
                self._m_spec.inc(n_prop, result="proposed")
                self._m_tok.inc(n_prop, phase="spec_draft")
            logits, self.cache = self._verify(
                self.params, self.cache, self.sched.state.page_table,
                jnp.asarray(tokens), self.sched.state.seq_lens,
                jnp.asarray(n_new), jnp.arange(slots, dtype=jnp.int32))
        with self._span("engine/sync"):
            greedy = np.asarray(sample_greedy(logits))    # (slots, C)
        with self._span("engine/commit"):
            for s in plan.decode_slots:
                drafts = plan.drafts.get(s, [])
                g = greedy[s]
                m = 0
                while m < len(drafts) and drafts[m] == int(g[m]):
                    m += 1
                if m:
                    self._m_spec.inc(m, result="accepted")
                if len(drafts) - m:
                    self._m_spec.inc(len(drafts) - m, result="rolled_back")
                # committed: the pending token + m accepted drafts;
                # emitted: their greedy continuations g[0..m] (g[m] is the
                # bonus token from the last accepted position — it becomes
                # the new pending token, exactly as in plain decode)
                self.sched.note_verified(s, n_written=1 + len(drafts),
                                         n_accepted=1 + m)
                for i in range(m + 1):
                    self.sched.append_token(s, int(g[i]))
                    self._emit(s)

    # -- drain loop --------------------------------------------------------

    def run(self, prompts: Sequence, max_new_tokens,
            max_steps: int = 100_000) -> List[np.ndarray]:
        """Submit ``prompts`` (list of 1-D int arrays) and step until all
        finish; returns generated ids per prompt, in submission order.
        ``max_new_tokens`` is an int or a per-prompt list."""
        if isinstance(max_new_tokens, int):
            max_new_tokens = [max_new_tokens] * len(prompts)
        ids = [self.add_request(p, n)
               for p, n in zip(prompts, max_new_tokens)]
        steps = 0
        while self.sched.has_work():
            plan, _ = self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError("engine failed to drain (stuck plan?)")
            if plan.n_tokens == 0 and not plan.admitted \
                    and not plan.preempted:
                # a preempt-only plan is NOT stuck: preemption just freed
                # pages (after the allocations that triggered it failed),
                # so the next step can admit/prefill into them
                raise RuntimeError(
                    "scheduler produced an empty plan with work pending — "
                    "page pool too small for any resident sequence")
        # pop: a long-lived engine must not hold every generation forever
        # (latency telemetry lives in the obs registry histograms, which
        # are fixed-size — nothing here grows with request count)
        return [self.outputs.pop(i) for i in ids]
