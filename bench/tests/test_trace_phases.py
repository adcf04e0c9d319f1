"""The split reductions of ``bench/trace_phases.py`` on hand-built planes,
and the readers of the serving kernels' rooflines on a hand-built ctx."""
from __future__ import annotations

import types
from pathlib import Path

import pytest

from bench import trace_phases, work
from bench.harness import lookup

ROOT = Path(__file__).resolve().parents[2]
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _planes(ops, host, modules=()):
    """Planes, lines and events that, as the profiler's, can be walked
    once."""
    def line(name, events):
        return types.SimpleNamespace(name=name, events=iter(events))

    lines = [line("XLA Ops", ops)]
    if modules:
        lines.append(line("XLA Modules", modules))
    return iter([
        types.SimpleNamespace(name="/device:TPU:0", lines=iter(lines)),
        types.SimpleNamespace(name="/host:CPU",
                              lines=iter([line("python", host)]))])


def test_idle_is_split_at_span_boundaries():
    planes = _planes(
        [_ev("%fusion.1 = f32[8] fusion()", 0, 100),
         _ev("%fusion.2 = f32[8] fusion()", 900, 100)],
        [_ev("bench/window", 0, 1000),
         _ev("engine/step", 0, 800),
         # siblings: the gap [100, 900) runs through both, then leaves
         # the step
         _ev("engine/commit", 50, 250),
         _ev("engine/finish", 300, 200),
         _ev("engine/sync", 120, 30)])
    idle = trace_phases.idle_by_span(planes)
    assert idle == pytest.approx({
        "engine/commit": (300 - 100 - 30) * 1e-9,
        "engine/sync": 30e-9,
        "engine/finish": 200e-9,
        "engine/step": (800 - 500) * 1e-9,
        trace_phases.NO_SPAN: (900 - 800) * 1e-9})
    assert sum(idle.values()) == pytest.approx(800e-9)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/while/body/closed_call/moe/dispatch/jit(sort)/sort",
     "moe/dispatch"),
    ("jit(step)/transpose(jvp(moe/dispatch))/gather", "moe/dispatch"),
    ("jit(step)/jvp(checkpoint)/ffn/junction_fwd/pallas_call", "ffn"),
    ("jit(step)/loss/lm_head/dot_general", "lm_head"),
    ("jit(step)/attn/proj/dot_general", "attn/proj"),
    ("jit(step)/attn/decode/paged_attention/pallas_call", "attn/decode"),
    ("jit(step)/attn/add", ""),
    ("jit(step)/while/body/add", ""),
])
def test_scope_of(op_name, scope):
    assert trace_phases.scope_of(op_name) == scope


def test_scopes_count_self_time():
    hlo = "\n".join([
        "HloModule jit_step, entry_computation_layout={()->f32[]}",
        "ENTRY %main (p: f32[8]) -> f32[8] {",
        '  %while.3 = (s32[]) while(%t), body=%b, metadata={op_name='
        '"jit(step)/while"}',
        '  %sort.1 = (f32[8]) sort(%p), metadata={op_name='
        '"jit(step)/while/body/transpose(jvp(moe/dispatch))/sort"}',
        '  %fusion.7 = f32[8] fusion(%sort.1), metadata={op_name='
        '"jit(step)/while/body/moe/combine/mul"}',
        # a kernel's metadata runs over three lines
        '  %junction_fwd.1 = f32[8] custom-call(%p), custom_call_target='
        '"tpu_custom_call", frontend_attributes={kernel_metadata={',
        '"kernel":"csd_spmm_fwd"',
        '}}, metadata={op_name="jit(step)/while/body/transpose(jvp('
        'checkpoint))/moe/experts/junction_fwd/pallas_call"}',
        '  ROOT %add.2 = f32[8] add(%p, %p), metadata={op_name='
        '"jit(step)/add"}',
        "}"])
    program, table = trace_phases.op_names(hlo)
    assert program == "jit_step"
    assert table["sort.1"].endswith("moe/dispatch))/sort")
    planes = _planes(
        [_ev("%while.3 = (s32[]) while(%t), body=%b", 100, 500),
         _ev("%sort.1 = (f32[8]) sort(f32[8] %p)", 150, 200),
         _ev("%fusion.7 = f32[8] fusion(f32[8] %sort.1)", 400, 100),
         _ev("%junction_fwd.1 = f32[8] custom-call(f32[8] %p)", 520, 40),
         _ev("%add.2 = f32[8] add(f32[8] %p, f32[8] %p)", 700, 50),
         _ev("%sort.1 = (f32[8]) sort(f32[8] %p)", 1200, 10)],
        [_ev("bench/window", 0, 1000)],
        [_ev("jit_step(123)", 90, 800)])
    assert table["junction_fwd.1"].endswith("experts/junction_fwd/pallas_call")
    # the loop (self time 160) and the add have no scope; the last sort
    # starts after the window
    assert trace_phases.scopes(planes, {program: table}) == pytest.approx(
        {"moe/dispatch": 200e-9, "moe/combine": 100e-9,
         "moe/experts": 40e-9})


def _chat_ctx(families):
    cell = lookup.find_cell("qwen2_7b.serve.chat")
    j = work.Junction(3584, 18944, 148, 14, 128, 128)
    g = work.Geometry(d_model=3584, n_layers=2, n_heads=28, n_kv_heads=4,
                      head_dim=128, vocab=152064, junctions=(j, j))
    steps = [{"calls": [
        {"kind": "prefill", "chunk": 64, "runs": [(0, 64)],
         "logit_rows": 1},
        {"kind": "decode", "chunk": 1, "runs": [(99, 1), (299, 1)],
         "logit_rows": 2, "lengths": [100, 300]}]}]
    return {"trace": {"families": families}, "steps": steps,
            "geometry": g, "peaks": PEAKS, "engine": {"max_slots": 8},
            "dtype_bytes": 2, "cell": cell}


def test_csd_spmm_roofline_chat_hand_count():
    reader = lookup.find_cell("qwen2_7b.serve.chat").metric_reader(
        "csd_spmm_roofline.chat")
    macs = 148 * 14 * 128 * 128
    # 512 prefill rows: compute-bound; 8 decode rows: the slab's bytes
    prefill = 2 * 512 * macs / 197e12
    decode = (macs * 2 + 8 * 3584 * 2 + 8 * 18944 * 2) / 819e9
    least = 2 * 2 * (prefill + decode)     # 2 layers x 2 junctions
    assert reader.read(_chat_ctx({"csd_spmm": 0.5})) == pytest.approx(
        100 * least / 0.5)
    assert reader.read(_chat_ctx({"pallas": 0.5})) is None


def test_paged_decode_roofline_chat_hand_count():
    reader = lookup.find_cell("qwen2_7b.serve.chat").metric_reader(
        "paged_decode_roofline.chat")
    # K and V of 400 positions x 4 heads x 128, q in and o out, 2 layers
    nbytes = 2 * 400 * 4 * 128 * 2 + 2 * 2 * 28 * 128 * 2
    assert reader.read(_chat_ctx({"paged_decode": 1e-3})) == \
        pytest.approx(100 * 2 * nbytes / 819e9 / 1e-3)
    assert reader.read(_chat_ctx({"pallas": 1e-3})) is None
