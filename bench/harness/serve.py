"""Serving cells: the program's ``ServingEngine`` under a seeded mix.

Set-up: weights from the seed on the device, the engine, warm-up
requests that run every step shape the window will use (all power-of-two
prefill chunks up to the cell's ``prefill_chunk`` and the decode step, on
every slot), and, for a backlog mix, the fill: serving until ``max_slots``
requests have completed, so that completions are staggered when the
window opens.

Window (``--seconds``): the harness drives ``add_request`` and ``step``
itself and times every token at the return of the step that delivered
it.

* open loop (``arrival: poisson``): requests are due on a schedule over
  the window; TTFT runs from when a request was *due* to its first token,
  and after the window closes the engine keeps stepping (no new arrivals)
  until every request due in it has its first token;
* backlog: the queue always holds more than ``max_slots`` requests; the
  window closes with the first step that ends after ``--seconds``, and
  the rate is every token delivered over that time.

``correct``: once the window has closed and the engine's state is freed,
a seeded sample of finished requests (the longest among them) is run
through the float32 reference over prompt + served tokens; the widest
gap by which a served token's reference logit lies below the reference's
best must stay under the cell's limit.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time

import numpy as np

from ..reference import pattern
from . import common, lookup, traffic, weights
from .common import clock, log


def _ann(name):
    """A named host span in the profiler's trace (idle-gap attribution)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class ReqState:
    req: traffic.Req
    due_abs: float = 0.0
    in_window: bool = False
    first: float = None
    last: float = None
    n: int = 0


def junction_patterns(model) -> dict:
    """The block patterns of the scanned layer's FFN junctions (the model's
    definition: which left block feeds each slot), as numpy tables."""
    blk = model.stack.unit_blocks[0]
    f = blk.ffn
    out = {}
    for j in ("up", "gate", "down"):
        pat = getattr(f, j + "_pat", None) if hasattr(f, "up_pat") \
            else getattr(f, j).pattern
        out[j] = pat
    return out


def record_junctions(model, cfgfile: dict) -> dict:
    """Print each junction's (n_lb, n_rb, rho) and the parameter counts,
    so that a change to the tile or the rounding shows."""
    import jax

    pats = junction_patterns(model)
    rec = {}
    for j, p in pats.items():
        rec[j] = (p.n_lb, p.n_rb, p.d_in_b, p.density)
        log(f"[setup] junction {j}: n_lb {p.n_lb}, n_rb {p.n_rb}, d_in_b "
            f"{p.d_in_b}, rho {p.density:g}, tile {p.block_in}x"
            f"{p.block_out}")
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    nb = sum(int(np.prod(s.shape)) * s.dtype.itemsize
             for s in jax.tree.leaves(shapes))
    dense = cfgfile.get("record", {}).get("params_dense")
    log(f"[setup] parameters at rho: {n} ({nb} bytes on the device); "
        f"dense: {dense}")
    return rec


def table_mismatch(model, cfgfile: dict) -> int:
    """Entries in which the program's junction tables differ from the
    ones the configuration states, built by ``bench/reference/pattern.py``
    (a table of another shape counts whole)."""
    ref = pattern.tables(cfgfile)
    n = 0
    for j, p in junction_patterns(model).items():
        a = np.asarray(p.block_idx)
        n += int(np.sum(a != ref[j])) if a.shape == ref[j].shape \
            else int(ref[j].size)
    return n


def structure_checks(model, cfgfile: dict) -> dict:
    """The exact checks of the junctions: density and tables."""
    return {
        "junction_density_gap": {"value": density_gap(model, cfgfile),
                                 "limit": 0.0, "rule": "<="},
        "junction_table_mismatch": {"value": table_mismatch(model, cfgfile),
                                    "limit": 0, "rule": "<="},
    }


def density_gap(model, cfgfile: dict) -> float:
    """Largest |rho - configured rho| over the junctions (exact: 0)."""
    rho_up, rho_down = cfgfile["model"]["sparsity"]["rho_ffn"]
    want = {"up": rho_up, "gate": rho_up, "down": rho_down}
    return max(abs(p.density - want[j])
               for j, p in junction_patterns(model).items())


def geometry(model):
    from .. import work

    cfg = model.cfg
    pats = junction_patterns(model)
    moe = cfg.moe
    js = tuple(work.Junction(p.n_in, p.n_out, p.n_rb, p.d_in_b, p.block_in,
                             p.block_out) for p in pats.values())
    return work.Geometry(
        d_model=cfg.d_model, n_layers=cfg.n_layers, n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads, head_dim=cfg.head_dim,
        vocab=cfg.vocab_size, junctions=js,
        top_k=moe.top_k if moe else 0, n_experts=moe.n_routed if moe else 0)


class Driver:
    """Steps the engine and times every token it delivers."""

    def __init__(self, eng, reqs: dict):
        self.eng = eng
        self.state = reqs               # rid -> ReqState
        self.itl = []                   # gaps of window requests
        self.finished = {}              # rid -> generated tokens
        self.finished_counting = set()  # finished while counting
        self.served_counting = set()    # given a token while counting
        self.tokens = 0                 # tokens delivered while counting
        self.processed = 0              # prompt + decode tokens computed
        self.counting = False
        self.steps = []                 # step records while recording
        self.recording = False
        self.preempted = 0

    def add(self, rs: ReqState):
        self.state[rs.req.rid] = rs
        with _ann("bench/add_request"):
            self.eng.add_request(rs.req.prompt, rs.req.max_new,
                                 req_id=rs.req.rid)

    def step(self):
        eng = self.eng
        sched = eng.sched
        before = {s: (q.req.req_id, q.n_prefilled)
                  for s, q in enumerate(sched.active) if q is not None}
        t0 = clock()
        plan, finished = eng.step()
        t = clock()
        with _ann("bench/record"):
            self._note(plan, finished, before, t0, t)
        return plan

    def _note(self, plan, finished, before, t0, t):
        sched = self.eng.sched
        self.preempted += len(plan.preempted)
        if self.counting:
            self.processed += len(plan.decode_slots) + sum(
                len(toks) for group in plan.prefill_groups
                for _, _, toks in group)
        counts = {}
        for q in sched.active:
            if q is not None:
                counts[q.req.req_id] = q.n_generated
        for rid, gen in finished:
            counts[rid] = len(gen)
            self.finished[rid] = gen
            if self.counting:
                self.finished_counting.add(rid)
        for rid, n in counts.items():
            rs = self.state[rid]
            new = n - rs.n
            if new <= 0:
                continue
            if self.counting:
                self.tokens += new
                self.served_counting.add(rid)
            timed = rs.in_window or self.counting
            if rs.first is None:
                rs.first = t
            elif timed:
                self.itl.append(t - rs.last)
            if timed:
                self.itl.extend([0.0] * (new - 1))
            rs.last = t
            rs.n = n
        if self.recording:
            self.steps.append(self._record(plan, before, t0, t))

    def _record(self, plan, before, t0, t1) -> dict:
        """What each device call of this step computed: per prefill group
        the (start, n) runs and how many rows' logits were used; the
        decode rows' context lengths."""
        sched = self.eng.sched
        calls = []
        for group in plan.prefill_groups:
            runs, logit_rows = [], 0
            for slot, start, toks in group:
                runs.append((int(start), len(toks)))
                q = sched.active[slot]
                if q is None or not q.prefilling:
                    logit_rows += 1
            calls.append({"kind": "prefill", "chunk": len(group[0][2]),
                          "runs": runs, "logit_rows": logit_rows})
        if plan.decode_slots:
            ctx = [before[s][1] + 1 for s in plan.decode_slots]
            calls.append({"kind": "decode", "chunk": 1,
                          "runs": [(c - 1, 1) for c in ctx],
                          "logit_rows": len(ctx), "lengths": ctx})
        return {"t0": t0, "t1": t1, "calls": calls}


def warm_up(eng, ecfg, vocab: int, seed: int) -> None:
    """One slot runs a prompt of 2 * prefill_chunk - 1 tokens (every
    power-of-two chunk), every other slot a one-token prompt, and each
    decodes twice: every step shape, and the host paths that sample at
    the end of a prompt on every slot, run before the window."""
    rng = np.random.default_rng([seed, 99])
    prompts = [rng.integers(0, vocab, 2 * ecfg.prefill_chunk - 1,
                            dtype=np.int32)]
    prompts += [rng.integers(0, vocab, 1, dtype=np.int32)
                for _ in range(ecfg.max_slots - 1)]
    eng.run(prompts, 3)


def gap_stats(gaps) -> dict:
    """Summaries of the served-token gaps (calibration log): the widest,
    the mean, and the share of tokens that are not the reference's best."""
    g = np.concatenate([np.asarray(x, float) for x in gaps]) if gaps \
        else np.zeros(0)
    if not g.size:
        return {}
    return {"max": float(g.max()), "mean": float(g.mean()),
            "p99": float(np.percentile(g, 99)),
            "share_not_best": float(np.mean(g > 0)), "tokens": int(g.size)}


def sample_finished(fin: dict, reqs: dict, seed: int, min_tokens: int,
                    max_reqs: int) -> list:
    """Seeded sample of finished requests, the longest (prompt + served)
    first, until ``min_tokens`` served tokens or ``max_reqs`` requests."""
    if not fin:
        return []
    rids = sorted(fin)
    longest = max(rids, key=lambda r: len(reqs[r].prompt) + len(fin[r]))
    rng = np.random.default_rng([seed, 5])
    order = [longest] + [r for r in rng.permutation(rids) if r != longest]
    out, served = [], 0
    for r in order:
        out.append(int(r))
        served += len(fin[r])
        if served >= min_tokens or len(out) >= max_reqs:
            break
    return out


def run(cell, args, devs, t_start: float, fault=None,
        control: bool = False, check: bool = True) -> tuple:
    """One run of a serving cell. ``fault`` (tests only) wraps the engine
    to break the timed path; ``control`` (calibration only) runs the
    control in the program's place: the program's own int8 path (int8
    weights and KV pages), one step below the configuration's bfloat16;
    ``check=False`` (the knee sweep) skips the reference. Returns
    (result, checks)."""
    import jax

    from repro.nn import build_model
    from repro.serving import EngineConfig, ServingEngine

    setup = collections.OrderedDict()
    compiles = common.CompileCounter()
    compiles.active = True
    t = clock()
    cfg = lookup.model_config(cell.config["model"])
    model = build_model(cfg)
    record_junctions(model, cell.config)
    setup["build_s"] = clock() - t

    t = clock()
    params = weights.make_params(model, args.seed)
    jax.block_until_ready(params)
    setup["weights_s"] = clock() - t

    t = clock()
    ecfg = EngineConfig(**cell.cell["engine"])
    if control:
        from repro.core.quant import QuantConfig

        ecfg = dataclasses.replace(ecfg, quant=QuantConfig(weights=True,
                                                           kv=True))
        log("[control] the program with int8 weights and KV pages")
    eng = ServingEngine(model, params, ecfg)
    if fault is not None:
        fault(eng)
    setup["engine_s"] = clock() - t

    t = clock()
    warm_up(eng, ecfg, cfg.vocab_size, args.seed)
    setup["warmup_s"] = clock() - t

    mix = cell.traffic
    gen = traffic.Generator(mix, args.seed, cfg.vocab_size)
    drv = Driver(eng, {})
    backlog = mix["arrival"] == "backlog"
    queue = collections.deque()

    def top_up():
        while len(eng.sched.waiting) <= ecfg.max_slots:
            if not queue:
                queue.extend(gen.block())
            drv.add(ReqState(queue.popleft()))

    if backlog:
        # the fill: serve until max_slots requests have completed
        t = clock()
        while len(drv.finished) < ecfg.max_slots:
            top_up()
            drv.step()
        setup["fill_s"] = clock() - t
    setup_s = clock() - t_start
    log("[setup] " + ", ".join(f"{k} {v:.3f}" for k, v in setup.items())
        + f"; setup_s {setup_s:.3f}")
    compiles.report_and_reset("setup")
    tracer = common.Trace() if args.trace else None
    seconds = float(args.seconds)
    tr_len = min(float(cell.cell.get("trace_seconds", 6.0)), seconds)
    tr_at = max(0.0, (seconds - tr_len) / 2)
    late = []

    def trace_tick(now):
        """Trace [tr_at, tr_at + tr_len) of the window, recording the
        steps that run in it."""
        if tracer is None:
            return
        if not tracer.on and tracer.t0 is None and now >= tr_at:
            tracer.start()
            drv.recording = True
        elif tracer.on and now >= tr_at + tr_len:
            drv.recording = False
            tracer.stop()

    compiles.active = True
    t0 = clock()
    if backlog:
        drv.counting = True
        while True:
            now = clock() - t0
            trace_tick(now)
            if now >= seconds:
                break
            top_up()
            drv.step()
        window_s = clock() - t0
        drv.counting = False
        attempted = len(drv.served_counting)
        failed = 0
    else:
        rate = float(cell.cell["rate_per_s"])
        pending = collections.deque(gen.schedule(rate, seconds))
        window_reqs = []
        deadline = seconds + float(cell.cell.get("drain_cap_s", 120.0))
        while True:
            now = clock() - t0
            trace_tick(now)
            while pending and pending[0].due <= now:
                r = pending.popleft()
                rs = ReqState(r, due_abs=t0 + r.due, in_window=True)
                late.append(now - r.due)
                drv.add(rs)
                window_reqs.append(rs)
            if not pending and all(rs.first is not None
                                   for rs in window_reqs):
                break
            if now > deadline:
                break
            if not eng.sched.has_work():
                if pending:
                    with _ann("bench/wait_arrival"):
                        time.sleep(max(0.0, min(pending[0].due - now,
                                                0.002)))
                continue
            drv.step()
        window_s = clock() - t0
        attempted = len(window_reqs)
        failed = sum(rs.first is None for rs in window_reqs)
    compiles.active = False
    if tracer is not None and tracer.on:
        drv.recording = False
        tracer.stop()
    if backlog:
        # answers of the window: requests it served; those still running
        # at the close finish here (no new work is added), until enough
        # of them have for the check
        t = clock()
        need = int(cell.cell["correct"].get("min_requests", 1))
        cap = float(cell.cell.get("drain_cap_s", 120.0))
        while eng.sched.has_work() and clock() - t < cap and len(
                drv.served_counting & set(drv.finished)) < need:
            drv.step()
        log(f"[window] after the close: {clock() - t:.3f} s until "
            f"{len(drv.served_counting & set(drv.finished))} requests "
            f"served in the window had finished")

    info = common.device_info(devs)
    log(f"[window] {window_s:.3f} s, {len(drv.state)} requests added, "
        f"{len(drv.finished)} finished ({len(drv.finished_counting)} in "
        f"the window), {drv.preempted} preempted, "
        f"{compiles.n} compiles in the window {compiles.names[:8]}")
    if late:
        log(f"[window] generator lateness: median "
            f"{1e3 * float(np.median(late)):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms")

    metrics = {}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        names = {m["name"] for m in cell.end_to_end}
        if "ttft_p90_ms" in names:
            ttft = [1e3 * (rs.first - rs.due_abs) for rs in window_reqs
                    if rs.first is not None]
            metrics["ttft_p90_ms"] = {
                "value": float(np.percentile(ttft, 90)), "unit": "ms"}
            log(f"[metrics] ttft: {len(ttft)} requests, median "
                f"{float(np.median(ttft)):.3f} ms")
        itl = 1e3 * np.asarray(drv.itl)
        if len(itl):
            log(f"[metrics] itl: {len(itl)} gaps, median "
                f"{float(np.median(itl)):.3f} ms, p95 "
                f"{float(np.percentile(itl, 95)):.3f} ms")
        if "itl_p95_ms" in names:
            metrics["itl_p95_ms"] = {
                "value": float(np.percentile(itl, 95)), "unit": "ms"}
        if backlog:
            log(f"[metrics] {drv.tokens} tokens delivered, {drv.processed} "
                f"prompt and decode tokens computed, in {window_s:.3f} s")
        if "gen_tokens_per_s" in names:
            metrics["gen_tokens_per_s"] = {
                "value": drv.tokens / window_s, "unit": "tokens/s"}
        if "processed_tokens_per_s" in names:
            metrics["processed_tokens_per_s"] = {
                "value": drv.processed / window_s, "unit": "tokens/s"}

    # free the engine's state before the reference runs
    reqs = {rid: rs.req for rid, rs in drv.state.items()}
    # the answers of the window: requests it served (backlog), or the
    # window's own requests (open loop)
    finished = {r: g for r, g in drv.finished.items()
                if (r in drv.served_counting if backlog
                    else drv.state[r].in_window)}
    preempted = drv.preempted
    steps = drv.steps
    del eng, drv
    gc.collect()

    breakdown = None
    if tracer is not None:
        from .. import trace_reduce
        from ..peaks import peaks_for

        red = trace_reduce.reduce(tracer.file())
        tracer.remove()
        log(f"[trace] kernel families: seconds {red['families']}, events "
            f"{red['family_events']}")
        info["busy_s"] = red["busy_s"]
        info["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        ctx = {"trace": red, "steps": steps, "geometry": geometry(model),
               "peaks": peaks_for(info["kind"]), "engine": cell.cell["engine"],
               "dtype_bytes": 2, "cell": cell}
        metrics.update(common.per_layer_metrics(cell, ctx))

    if not check:
        ttft = [rs.first - rs.due_abs for rs in window_reqs
                if rs.first is not None] if not backlog else []
        return {"attempted": attempted, "failed": failed,
                "metrics": metrics, "device": info,
                "drain_s": window_s - seconds,
                "finished": len(finished), "ttft_s": ttft,
                "preempted": preempted}, {}

    # correctness: the reference over a seeded sample of finished requests
    t = clock()
    lim = cell.cell["correct"]
    sample = sample_finished(finished, reqs, args.seed,
                             int(lim.get("sample_tokens", 256)),
                             int(lim.get("sample_requests", 8)))
    ref = cell.reference()
    pats = pattern.tables(cell.config)
    seqs = [(reqs[r].prompt, finished[r]) for r in sample]
    with common.highest_precision():
        gaps = ref.served_gaps(params, pats, cell.config["model"], seqs)
    gap = max((float(np.max(g)) for g in gaps), default=float("inf"))
    served = sum(len(s[1]) for s in seqs)
    log(f"[correct] gaps {gap_stats(gaps)}")
    log(f"[correct] {len(sample)} requests, {served} served tokens, "
        f"longest {max((len(p) + len(g) for p, g in seqs), default=0)} "
        f"positions; reference {clock() - t:.3f} s")
    checks = {"served_logit_gap": {"value": gap,
                                   "limit": float(lim["served_logit_gap"]),
                                   "rule": "<="},
              **structure_checks(model, cell.config)}
    correct = bool(served > 0 and common.judge(checks))
    result = {"correct": correct, "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["gap_stats"] = gap_stats(gaps)
    return result, checks
