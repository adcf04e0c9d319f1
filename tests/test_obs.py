"""Certification for repro.obs: registry semantics, replayable JSONL,
FLOP accounting parity with hand counts, and — the load-bearing contract —
jit purity: instrumentation must never change a traced program.

Layers:

* **registry**   — counter/gauge/histogram semantics, label-cardinality
  budget, Prometheus text golden, snapshot shapes;
* **stream**     — JSONL events replayed by ``repro.obs.dump`` in a fresh
  registry reconstruct identical state (the CI-artifact contract);
* **flops**      — per-junction gauges match MAC/storage counts derived
  independently from the pattern's dense mask (the paper's rho and
  complexity-reduction factor);
* **purity**     — the engine's jitted paged step and the trainer's step
  lower to byte-identical HLO with metrics on vs off, and sparselint's
  SL201 pass finds no host-sync primitive in either;
* **surfaces**   — the ``/metrics`` HTTP endpoint and the dump CLI;
* **names**      — the model's scopes reach the lowered programs' op_name
  metadata, and an engine step records its documented host spans.
"""
import json
import os
import re
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_block_pattern
from repro.obs import dump, flops, metrics, trace
from repro.obs.metrics import CardinalityError, Registry


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_and_gauge_basics():
    reg = Registry()
    c = reg.counter("c_total", "help text")
    c.inc()
    c.inc(2.5, phase="prefill")
    assert c.value() == 1.0
    assert c.value(phase="prefill") == 2.5
    with pytest.raises(ValueError):
        c.inc(-1.0)
    g = reg.gauge("g")
    g.set(3.0)
    g.set_max(1.0)          # high-water keeps the max
    assert g.value() == 3.0
    g.set_max(7.0)
    assert g.value() == 7.0
    # same name returns the same metric; kind mismatch raises
    assert reg.counter("c_total") is c
    with pytest.raises(ValueError):
        reg.gauge("c_total")


def test_disabled_registry_is_noop():
    reg = Registry(enabled=False)
    c = reg.counter("c")
    c.inc(5)
    reg.gauge("g").set(1)
    reg.histogram("h").observe(0.1)
    reg.record_span("s", 0.5)
    assert c.value() == 0.0
    assert reg.snapshot()["counters"]["c"]["series"] == []
    assert reg.span_durations("s") == []


def test_label_cardinality_budget():
    reg = Registry(max_series=4)
    c = reg.counter("c")
    for i in range(4):
        c.inc(series=i)
    with pytest.raises(CardinalityError):
        c.inc(series="one-too-many")
    # existing series still record after the breach attempt
    c.inc(series=0)
    assert c.value(series=0) == 2.0


def test_histogram_buckets_exact():
    reg = Registry()
    h = reg.histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.1, 0.5, 2.0, 100.0):
        h.observe(v)
    s = reg.snapshot()["histograms"]["h"]["series"][0]
    # le-0.1 gets 0.05 and 0.1 (boundary is inclusive), le-1.0 gets 0.5,
    # le-10 gets 2.0, +Inf gets 100.0
    assert s["bucket_counts"] == [2, 1, 1, 1]
    assert s["count"] == 5
    assert s["sum"] == pytest.approx(102.65)
    assert h.stats() == (5, pytest.approx(102.65))


def test_prometheus_text_golden():
    reg = Registry()
    reg.counter("req_total", "requests").inc(3, kind="a")
    reg.gauge("depth").set(2)
    h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    assert reg.prometheus_text() == (
        '# TYPE depth gauge\n'
        'depth 2\n'
        '# HELP lat_seconds latency\n'
        '# TYPE lat_seconds histogram\n'
        'lat_seconds_bucket{le="0.1"} 1\n'
        'lat_seconds_bucket{le="1"} 2\n'
        'lat_seconds_bucket{le="+Inf"} 3\n'
        'lat_seconds_sum 5.55\n'
        'lat_seconds_count 3\n'
        '# HELP req_total requests\n'
        '# TYPE req_total counter\n'
        'req_total{kind="a"} 3\n')


def test_span_recording():
    reg = Registry()
    with trace.span("phase/x", registry=reg, n=3):
        pass
    ds = reg.span_durations("phase/x")
    assert len(ds) == 1 and ds[0] >= 0.0
    cnt, _ = reg.histogram("repro_span_seconds").stats(span="phase/x")
    assert cnt == 1


# ---------------------------------------------------------------------------
# JSONL stream -> dump replay
# ---------------------------------------------------------------------------


def test_jsonl_replay_roundtrip(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = Registry(jsonl_path=path)
    reg.counter("tok_total", "tokens").inc(7, phase="decode")
    reg.gauge("occ").set(0.5)
    h = reg.histogram("lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.7)
    with trace.span("bench/x", registry=reg):
        pass
    reg.close()
    replayed = dump.replay(path)
    a, b = reg.snapshot(), replayed.snapshot()
    assert a["counters"] == b["counters"]
    assert a["gauges"] == b["gauges"]
    assert a["histograms"] == b["histograms"]
    assert replayed.span_durations("bench/x") == \
        reg.span_durations("bench/x")
    # and the exporters agree byte-for-byte
    assert reg.prometheus_text() == replayed.prometheus_text()


def test_dump_cli(tmp_path, capsys):
    path = str(tmp_path / "m.jsonl")
    reg = Registry(jsonl_path=path)
    reg.counter("c").inc(2)
    reg.close()
    assert dump.main(["--input", path, "--format", "json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["counters"]["c"]["series"][0]["value"] == 2.0
    outfile = str(tmp_path / "m.prom")
    assert dump.main(["--input", path, "--format", "prom",
                      "-o", outfile]) == 0
    assert "c 2" in open(outfile).read()


# ---------------------------------------------------------------------------
# FLOP accounting vs hand counts
# ---------------------------------------------------------------------------


def test_junction_stats_match_mask_hand_count():
    n_in, n_out, rho, b = 64, 128, 0.25, 16
    bp = make_block_pattern(n_in, n_out, rho, block_in=b, block_out=b,
                            seed=0)
    st = flops.junction_stats(bp)
    mask = bp.to_mask()
    nnz = int(mask.sum())           # surviving weight elements
    assert st.dense_macs == n_in * n_out
    assert st.sparse_macs == nnz    # one MAC per stored weight per row
    assert st.density == pytest.approx(nnz / (n_in * n_out))
    assert st.speedup == pytest.approx((n_in * n_out) / nnz)
    assert st.weight_bytes == 4 * nnz
    assert st.dense_weight_bytes == 4 * n_in * n_out
    assert st.index_bytes == 4 * bp.block_idx.size
    assert st.label == f"64x128b16x16r{st.density:g}"


def test_register_exports_gauges():
    reg = Registry()
    bp = make_block_pattern(64, 64, 0.5, block_in=16, block_out=16, seed=1)
    st = flops.register(bp, registry=reg)
    j = st.label
    assert reg.gauge("repro_junction_density").value(junction=j) == \
        pytest.approx(st.density)
    assert reg.gauge("repro_junction_sparse_macs").value(junction=j) == \
        st.sparse_macs
    assert reg.gauge("repro_junction_speedup").value(junction=j) == \
        pytest.approx(st.speedup)
    flops.register(bp, registry=reg)   # idempotent gauges, counted twice
    assert reg.counter("repro_junction_patterns_total").value(
        junction=j) == 2.0


def test_fit_block_pattern_registers_into_default_registry():
    from repro.core.block_pattern import fit_block_pattern
    from repro.nn.common import SparsityConfig
    sp = SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                        block_in=16, block_out=16)
    bp = fit_block_pattern(48, 96, 0.5, sp)
    st = flops.junction_stats(bp)
    reg = metrics.get_registry()
    assert reg.gauge("repro_junction_dense_macs").value(
        junction=st.label) == st.dense_macs


# ---------------------------------------------------------------------------
# jit purity: metrics on == metrics off, on the lowered HLO
# ---------------------------------------------------------------------------


def _tiny_model():
    from repro.nn import ModelConfig, SparsityConfig, build_model
    cfg = ModelConfig(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=256, attn_chunk=16, loss_chunk=16, dtype="float32",
        remat=False,
        sparsity=SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                                block_in=16, block_out=16))
    return build_model(cfg)


def _paged_step_hlo(metrics_on: bool) -> str:
    from repro.nn.common import dtype_of
    from repro.serving import EngineConfig, ServingEngine
    model = _tiny_model()
    params = model.init(jax.random.key(0))
    eng = ServingEngine(
        model, params,
        EngineConfig(max_slots=2, page_size=8, total_pages=16,
                     max_pages_per_seq=4, token_budget=8,
                     prefill_chunk=8, metrics=metrics_on),
        registry=Registry(enabled=metrics_on))
    i32 = np.int32
    cache_avals = jax.eval_shape(
        lambda: model.stack.init_paged_cache(2, 16, 8,
                                             dtype_of(model.cfg)))
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    args = (p_avals, cache_avals,
            jax.ShapeDtypeStruct((2, 4), i32),
            jax.ShapeDtypeStruct((2, 1), i32),
            jax.ShapeDtypeStruct((2,), i32),
            jax.ShapeDtypeStruct((2,), i32),
            jax.ShapeDtypeStruct((2,), i32))
    return eng._step.lower(*args).as_text()


def test_engine_step_hlo_identical_with_metrics_on_or_off():
    assert _paged_step_hlo(True) == _paged_step_hlo(False)


def _train_step_hlo(metrics_on: bool) -> str:
    from repro.train import Trainer, TrainerConfig
    model = _tiny_model()
    tr = Trainer(model, TrainerConfig(metrics=metrics_on),
                 registry=Registry(enabled=metrics_on))
    batch = {"tokens": np.zeros((2, 16), np.int32),
             "labels": np.zeros((2, 16), np.int32)}
    step = tr._make_step(batch)
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    from repro.optim import adam
    o_avals = jax.eval_shape(adam.init, p_avals)
    b_avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    return step.lower(p_avals, o_avals, b_avals).as_text()


def test_train_step_hlo_identical_with_metrics_on_or_off():
    assert _train_step_hlo(True) == _train_step_hlo(False)


def test_no_host_sync_primitives_in_instrumented_steps():
    """sparselint SL201 over the engine step and trainer step traced with
    metrics ENABLED: instrumentation must not smuggle a callback/infeed
    into the traced programs."""
    from repro.analysis.jaxpr_pass import lint_closed_jaxpr
    from repro.nn.common import dtype_of
    from repro.optim import adam
    from repro.serving import EngineConfig, ServingEngine
    from repro.train import Trainer, TrainerConfig

    model = _tiny_model()
    params = model.init(jax.random.key(0))
    eng = ServingEngine(
        model, params,
        EngineConfig(max_slots=2, page_size=8, total_pages=16,
                     max_pages_per_seq=4, token_budget=8, prefill_chunk=8),
        registry=Registry())
    i32 = np.int32
    cache_avals = jax.eval_shape(
        lambda: model.stack.init_paged_cache(2, 16, 8,
                                             dtype_of(model.cfg)))
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    traced = eng._step.trace(
        p_avals, cache_avals,
        jax.ShapeDtypeStruct((2, 4), i32),
        jax.ShapeDtypeStruct((2, 1), i32),
        jax.ShapeDtypeStruct((2,), i32),
        jax.ShapeDtypeStruct((2,), i32),
        jax.ShapeDtypeStruct((2,), i32))
    sl201 = [f for f in lint_closed_jaxpr(traced.jaxpr, "paged_step[obs]")
             if f.code == "SL201"]
    assert sl201 == [], sl201

    tr = Trainer(model, TrainerConfig(), registry=Registry())
    batch = {"tokens": np.zeros((2, 16), np.int32),
             "labels": np.zeros((2, 16), np.int32)}
    step = tr._make_step(batch)
    o_avals = jax.eval_shape(adam.init, p_avals)
    b_avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batch)
    traced = step.trace(p_avals, o_avals, b_avals)
    sl201 = [f for f in lint_closed_jaxpr(traced.jaxpr, "train_step[obs]")
             if f.code == "SL201"]
    assert sl201 == [], sl201


def test_dispatch_counter_counts_at_trace_time():
    from repro.kernels import ops
    reg = metrics.get_registry()
    c = reg.counter("repro_junction_dispatch_total")
    bp = make_block_pattern(64, 64, 0.5, block_in=16, block_out=16, seed=0)
    w = jnp.zeros((bp.n_rb, bp.d_in_b, 16, 16))
    x = jnp.zeros((4, 64))
    before = c.value(backend="xla", form="plain")
    f = jax.jit(lambda x, w: ops.csd_matmul(x, w, bp, backend="xla"))
    f(x, w)     # trace + compile: exactly one dispatch count
    f(x, w)     # cached executable: no re-trace, no new count
    assert c.value(backend="xla", form="plain") == before + 1


def test_fwd_tiling_counters_count_at_trace_time():
    """The forward's grid steps per call and fan-in slots per step, counted
    when a call is traced: a plain call over 24 rows whose derived tiling
    takes the whole fan-in (4 slots) in one step per right block, and a
    batched call with 2 slots a step over 3 experts x 2 row blocks."""
    from repro.kernels import csd_spmm
    reg = metrics.get_registry()
    steps = reg.counter("repro_junction_fwd_grid_steps_total")
    slots = reg.gauge("repro_junction_fwd_slots_per_step")
    bp = make_block_pattern(64, 32, 0.5, block_in=8, block_out=8, seed=0)
    assert (bp.n_rb, bp.d_in_b) == (4, 4)
    w = jnp.zeros((bp.n_rb, bp.d_in_b, 8, 8))
    before = steps.value(form="plain")
    f = jax.jit(lambda x, w: csd_spmm.csd_spmm_fwd(
        x, w, bp.block_idx, interpret=True))
    f(jnp.zeros((24, 64)), w)
    f(jnp.zeros((24, 64)), w)  # cached executable: no new count
    # grid (1, 1 row block of 24, 4 right blocks, 1 chunk)
    assert steps.value(form="plain") == before + 4
    assert slots.value(form="plain", fan_in=4) == 4

    before = steps.value(form="batched")
    csd_spmm.csd_spmm_fwd(jnp.zeros((3, 16, 64)), jnp.zeros((3,) + w.shape),
                          bp.block_idx, block_m=8, fan_in_block=2,
                          interpret=True)
    # grid (3 experts, 2 row blocks, 4 right blocks, 2 chunks)
    assert steps.value(form="batched") == before + 3 * 2 * 4 * 2
    assert slots.value(form="batched", fan_in=4) == 2


# ---------------------------------------------------------------------------
# surfaces: HTTP endpoint, timed_call
# ---------------------------------------------------------------------------


def test_metrics_http_endpoint():
    reg = Registry()
    reg.counter("c_total").inc(4)
    server = metrics.serve_http(reg, port=0)
    try:
        port = server.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
        assert "c_total 4" in body
        j = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=5).read())
        assert j["counters"]["c_total"]["series"][0]["value"] == 4.0
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/nope", timeout=5)
    finally:
        server.shutdown()


def test_timed_call_reads_registry_spans():
    reg = Registry()
    f = jax.jit(lambda x: x * 2)
    us = trace.timed_call(f, jnp.ones((8,)), iters=3, warmup=1,
                          name="mul", registry=reg)
    assert us > 0
    assert len(reg.span_durations("bench/mul")) == 3
    cnt, _ = reg.histogram("repro_span_seconds").stats(span="bench/mul")
    assert cnt == 3


# ---------------------------------------------------------------------------
# names: model scopes in the op_name metadata, engine spans per step
# ---------------------------------------------------------------------------

SCOPES = ("attn/proj", "attn/core", "attn/decode", "ffn", "moe/router",
          "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "loss")
# the substrings by which the benchmark's trace reduction puts a device op
# into a kernel family (``FAMILIES`` in bench/trace_reduce.py): no scope
# may hold one, or XLA ops under it would count as kernel time
FAMILY_KEYS = ("_fwd_kernel", "_dx_kernel", "_dw_kernel", "csd_spmm",
               "_paged_decode", "paged_decode", "pallas_call",
               "tpu_custom_call")
ENGINE_SPANS = {"engine/step", "engine/schedule", "engine/prefill",
                "engine/decode", "engine/verify", "engine/sync",
                "engine/commit", "engine/finish"}


def _scopes_in(lowered) -> set:
    """The scopes that appear as path segments of the op_name locations
    of a lowered program, through transform wrappers such as
    ``transpose(jvp(moe/dispatch))``."""
    found = set()
    for name in re.findall(r'loc\("([^"]+)"',
                           lowered.as_text(debug_info=True)):
        path = "/" + re.sub(r"[\w.-]+\(|\)", "", name) + "/"
        found.update(sc for sc in SCOPES if f"/{sc}/" in path)
    return found


def test_scope_names_hold_no_kernel_family_key():
    assert not [(sc, k) for sc in SCOPES for k in FAMILY_KEYS if k in sc]


def test_model_scopes_reach_op_names():
    """A tiny MoE training step and a tiny dense paged step (prefill
    chunk and decode) name every scope of the model's layers."""
    from repro.nn import MoEConfig, ModelConfig, build_model
    from repro.nn.common import dtype_of
    from repro.optim import adam
    from repro.serving import EngineConfig, ServingEngine
    from repro.train import Trainer, TrainerConfig

    moe = build_model(ModelConfig(
        n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
        vocab_size=64, attn_chunk=16, loss_chunk=16, dtype="float32",
        remat=False, moe=MoEConfig(n_routed=4, top_k=2, d_expert=16)))
    tr = Trainer(moe, TrainerConfig(), registry=Registry())
    batch = {"tokens": np.zeros((2, 16), np.int32),
             "labels": np.zeros((2, 16), np.int32)}
    p_avals = jax.eval_shape(moe.init, jax.random.key(0))
    train = _scopes_in(tr._make_step(batch).lower(
        p_avals, jax.eval_shape(adam.init, p_avals),
        jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                     batch)))
    assert train >= {"attn/proj", "attn/core", "moe/router", "moe/dispatch",
                     "moe/experts", "moe/combine", "lm_head", "loss"}

    model = _tiny_model()
    eng = ServingEngine(
        model, model.init(jax.random.key(0)),
        EngineConfig(max_slots=2, page_size=8, total_pages=16,
                     max_pages_per_seq=4, token_budget=8, prefill_chunk=8),
        registry=Registry())
    i32 = np.int32
    cache = jax.eval_shape(lambda: model.stack.init_paged_cache(
        2, 16, 8, dtype_of(model.cfg)))
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    serve = set()
    for chunk in (1, 4):
        serve |= _scopes_in(eng._step.lower(
            p_avals, cache, jax.ShapeDtypeStruct((2, 4), i32),
            jax.ShapeDtypeStruct((2, chunk), i32),
            *[jax.ShapeDtypeStruct((2,), i32)] * 3))
    assert serve >= {"attn/proj", "attn/core", "attn/decode", "ffn",
                     "lm_head"}
    assert train | serve == set(SCOPES)


def _span_counts(reg) -> dict:
    h = reg.histogram("repro_span_seconds")
    return {dict(k)["span"]: s.count for k, s in h.series.items()}


def test_engine_step_records_its_host_phases():
    """One prefill step (three prompts end in one group) and one decode
    step record the documented spans, each once per step or per call,
    except ``engine/sync``, once per prompt that ends."""
    from repro.serving import EngineConfig, ServingEngine

    model = _tiny_model()
    reg = Registry()
    eng = ServingEngine(
        model, model.init(jax.random.key(0)),
        EngineConfig(max_slots=4, page_size=8, total_pages=16,
                     max_pages_per_seq=4, token_budget=32,
                     prefill_chunk=8, backend="xla"), registry=reg)
    for i in range(3):
        eng.add_request(np.full(8, i + 1, np.int32), 4)
    before = _span_counts(reg)
    plan, _ = eng.step()
    assert len(plan.prefill_groups) == 1 and not plan.decode_slots
    prefill = _span_counts(reg)
    plan, _ = eng.step()
    assert len(plan.decode_slots) == 3
    decode = _span_counts(reg)
    assert set(decode) <= ENGINE_SPANS
    once = {"engine/step": 1, "engine/schedule": 1, "engine/commit": 1,
            "engine/finish": 1}

    def diff(after, prev):
        return {k: v - prev.get(k, 0) for k, v in after.items()
                if v > prev.get(k, 0)}

    assert diff(prefill, before) == dict(
        once, **{"engine/prefill": 1, "engine/sync": 3})
    assert diff(decode, prefill) == dict(
        once, **{"engine/decode": 1, "engine/sync": 1})
