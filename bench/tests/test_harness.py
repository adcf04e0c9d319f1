"""The benchmark's own tests (``python -m pytest bench/tests``): the
yardstick's arithmetic, the generators, lookup by name, the junction
densities of both configurations, and the trace reduction."""
from __future__ import annotations

import json
import types
from pathlib import Path

import numpy as np
import pytest

from bench import trace_reduce, work
from bench.harness import lookup, traffic
from bench.harness.bigram import BigramLM

ROOT = Path(__file__).resolve().parents[2]


def test_junction_work_hand_count():
    # qwen2_7b up junction at 128 x 128: 148 right blocks, 14 slots each
    j = work.Junction(3584, 18944, 148, 14, 128, 128)
    assert j.macs_per_row == 148 * 14 * 128 * 128
    assert j.density == 0.5
    flops, nbytes = work.junction_call(j, 2048, 2, 2, 2)
    assert flops == 2 * 2048 * 148 * 14 * 128 * 128
    assert nbytes == 148 * 14 * 128 * 128 * 2 + 2048 * 3584 * 2 \
        + 2048 * 18944 * 2
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work.roofline_s(flops, nbytes, peaks)
    assert bound == "compute" and t == pytest.approx(flops / 197e12)
    t16, bound16 = work.roofline_s(*work.junction_call(j, 16, 2, 2, 2),
                                   peaks)
    assert bound16 == "memory"


def test_paged_decode_hand_count():
    flops, nbytes = work.paged_decode_call([100, 300], n_kv=4, groups=7,
                                           head_dim=128, kv_bytes=2,
                                           q_bytes=2)
    assert flops == 2 * 2 * 400 * 4 * 7 * 128
    assert nbytes == 2 * 400 * 4 * 128 * 2 + 2 * 2 * 4 * 7 * 128 * 2


def test_useful_flops_counts_real_context():
    g = work.Geometry(d_model=8, n_layers=2, n_heads=2, n_kv_heads=1,
                      head_dim=4, vocab=10,
                      junctions=(work.Junction(8, 16, 1, 1, 8, 16),))
    # one run of 3 tokens from position 5: contexts 6, 7, 8
    f = work.forward_useful_flops(g, [(5, 3)], logit_rows=1)
    assert f == 2 * g.layer_macs * 2 * 3 + 4 * 21 * 2 * 4 * 2 + 2 * 8 * 10


@pytest.mark.parametrize("mix", ["serve.chat", "serve.offline_decode"])
def test_generator_deterministic(mix):
    spec = json.loads((ROOT / "bench/traffic" / f"{mix}.json").read_text())
    seed = 2 ** 31 + 12345
    a, b = (traffic.Generator(spec, seed, 1000) for _ in range(2))
    ra = a.schedule(2.0, 30) if spec["arrival"] == "poisson" else a.block()
    rb = b.schedule(2.0, 30) if spec["arrival"] == "poisson" else b.block()
    assert [(r.due, r.max_new, r.prompt.tobytes()) for r in ra] == \
        [(r.due, r.max_new, r.prompt.tobytes()) for r in rb]
    c = traffic.Generator(spec, seed + 1, 1000)
    rc = c.schedule(2.0, 30) if spec["arrival"] == "poisson" else c.block()
    # another seed: the same sizes, in another order unless the mix
    # fixes the order, and other tokens
    assert sorted(r.max_new for r in rc) == sorted(r.max_new for r in ra)
    same_order = [r.max_new for r in rc] == [r.max_new for r in ra]
    assert same_order == (spec.get("order") == "fixed")
    assert [r.prompt.tobytes() for r in rc] != \
        [r.prompt.tobytes() for r in ra]


def test_lengths_follow_the_mix():
    spec = json.loads((ROOT / "bench/traffic/serve.chat.json").read_text())
    q = traffic._quantiles(spec["prompt_len"], 1001)
    assert q.min() >= 32 and q.max() <= 3584
    assert np.median(q) == 512


def test_bigram_deterministic():
    a = BigramLM(1000, 2 ** 33 + 7).batch(3, 2, 50)
    b = BigramLM(1000, 2 ** 33 + 7).batch(3, 2, 50)
    assert all((a[k] == b[k]).all() for k in a)
    assert (a["tokens"][:, 1:] == a["labels"][:, :-1]).all()


def test_every_cell_and_metric_found_by_name():
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bm["workloads"]:
        c = lookup.find_cell(w["name"], bm)
        assert c.kind in ("serve", "train")
        assert c.per_layer, w["name"]
        for m in c.per_layer:
            assert hasattr(c.metric_reader(m["name"]), "read")
        assert hasattr(c.reference(), "served_gaps")
    with pytest.raises(KeyError):
        lookup.find_cell("no.such.cell", bm)


@pytest.mark.parametrize("config", ["qwen2_7b", "granite_moe_1b_a400m"])
def test_junction_densities_are_sparse(config):
    from repro.nn import build_model

    from bench.harness.serve import junction_patterns

    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    model = build_model(lookup.model_config(cfg["model"]))
    pats = junction_patterns(model)
    assert {j: p.density for j, p in pats.items()} == \
        {"up": 0.5, "gate": 0.5, "down": 0.75}
    for j, p in pats.items():
        assert (p.block_in, p.block_out) == (128, 128)
        assert [p.n_lb, p.n_rb, p.d_in_b] == cfg["record"]["junctions"][j][:3]


@pytest.mark.parametrize("config", ["qwen2_7b", "granite_moe_1b_a400m"])
def test_program_tables_are_the_configurations(config):
    """The reference builds each junction's table from the configuration
    alone; the program's tables are those, and other densities' are not
    (nor, where a bank is deeper than one address, another seed's:
    granite's expert junctions have one-address banks, so any seed
    gives them the same table)."""
    import copy

    from repro.nn import build_model

    from bench.harness.serve import table_mismatch

    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    model = build_model(lookup.model_config(cfg["model"]))
    assert table_mismatch(model, cfg) == 0
    other = copy.deepcopy(cfg)
    other["model"]["sparsity"]["rho_ffn"] = [0.75, 0.5]
    assert table_mismatch(model, other) > 0
    if config == "qwen2_7b":
        other = copy.deepcopy(cfg)
        other["junction_seeds"]["down"] += 1
        assert table_mismatch(model, other) > 0


def _ev(name, start, dur, stats=()):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats))


def test_trace_reduction():
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[
            _ev("fusion.12", 100, 50),
            _ev("custom-call.3", 160, 100,
                [("long_name", "_fwd_kernel pallas")]),
            _ev("fusion.13", 240, 40),          # overlaps the kernel
            _ev("copy.1", 900, 200),            # runs past the window
        ])])
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(name="python", events=[
            _ev("bench/window", 0, 1000),
            _ev("engine/step", 0, 600),
            _ev("engine/decode", 300, 250),
        ])])
    r = trace_reduce.reduce_planes([dev, host])
    assert r["window_s"] == pytest.approx(1000e-9)
    # busy: [100, 150) + [160, 280) + [900, 1000) = 50 + 120 + 100
    assert r["busy_s"] == pytest.approx(270e-9)
    assert r["families"] == {"csd_spmm": pytest.approx(100e-9)}
    assert r["ops"]["fusion"] == pytest.approx(90e-9)
    gaps = dict(r["idle_gaps"])
    # the gaps [0,100), [150,160) and [280,900) have their middles in
    # engine/step and outside engine/decode (300..550)
    assert gaps["engine/step"] == pytest.approx((100 + 10 + 620) * 1e-9)


def test_trace_reduction_needs_the_window():
    dev = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name="XLA Ops", events=[_ev("f", 0, 1)])])
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes([dev])
