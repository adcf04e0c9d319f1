"""Compile the main-path Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers a kernel at qwen2_7b's published widths in
bf16 and hands it to the TPU compiler for a chip that is described, not
attached. The compiler refuses what interpret mode accepts (block shapes
off the (8, 128) tiling, comparisons the vector unit lacks), so these
tests guard the chip path at no chip time.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and with several test
workers every worker imports this file.
"""
import collections
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.block_pattern import fit_block_pattern
from repro.kernels import csd_spmm, ops
from repro.kernels.flash_attention import paged_decode_attention
from repro.kernels.names import KERNELS

# the substrings by which the benchmark's trace reduction puts a device op
# into a kernel family (``FAMILIES`` in bench/trace_reduce.py)
FAMILY_KEYS = ("_fwd_kernel", "_dx_kernel", "_dw_kernel", "csd_spmm",
               "_paged_decode", "paged_decode")
QWEN = get_config("qwen2_7b")
TOKENS = 256          # two 128-row tiles
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_compile_cache):
    return SingleDeviceSharding(topo.devices[0])


def _pattern(junction):
    """qwen2_7b's FFN junction patterns at published widths."""
    sp = QWEN.sparsity
    if junction == "up":
        return fit_block_pattern(QWEN.d_model, QWEN.d_ff, sp.rho_ffn[0], sp)
    return fit_block_pattern(QWEN.d_ff, QWEN.d_model, sp.rho_ffn[1], sp)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _instructions(text):
    """The compiled program's instructions, one string each (an
    instruction's kernel metadata spans lines)."""
    out, open_ = [], False
    for line in text.splitlines():
        if re.match(r"\s*(ROOT )?%", line):
            out.append(line)
            open_ = True
        elif open_ and line.strip():
            out[-1] += line
        else:
            open_ = False
    return out


def _compile(fn, *args, derived=("get-tuple-element", "copy")):
    """Compile for the described chip; count the kernel names of its
    Pallas calls, each checked against its instruction's name. A trace
    prints each op with its operands' instruction names, so a kernel name
    may appear only in the kernel's own instruction, and in the
    instructions XLA ``derived`` from it (the tuple reads of its outputs,
    which do not run, and a copy that changes an output's layout): else
    ops that consume its output would be counted as the kernel."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    kernels = collections.Counter()
    for ins in _instructions(text):
        if 'custom_call_target="tpu_custom_call"' in ins:
            m = re.search(r'%([\w.-]+) = .*kernel_metadata=\{\s*'
                          r'"kernel":"(\w+)"', ins)
            assert m, ins[:200]
            # under a transform the name carries it: transpose_jvp_..._
            assert KERNELS[m.group(2)] in m.group(1), ins[:200]
            kernels[m.group(2)] += 1
        elif not [d for d in derived if f" {d}(" in ins]:
            assert not [k for k in FAMILY_KEYS if k in ins], ins[:200]
    return kernels


@pytest.mark.parametrize("junction", ["up", "down"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_fwd_with_bias_compiles(one_chip, junction, quant):
    bp = _pattern(junction)
    w_shape = (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out)
    x = _spec((TOKENS, bp.n_in), BF16, one_chip)
    b = _spec((bp.n_out,), BF16, one_chip)
    if quant:
        w = _spec(w_shape, jnp.int8, one_chip)
        s = _spec(w_shape[:2], jnp.float32, one_chip)
        kernels = _compile(lambda x, w, s, b: csd_spmm.csd_spmm_fwd(
            x, w, bp.block_idx, bias=b, activation="relu", w_scale=s),
            x, w, s, b)
        assert set(kernels) == {"csd_spmm_fwd_int8"}
    else:
        w = _spec(w_shape, BF16, one_chip)
        kernels = _compile(lambda x, w, b: csd_spmm.csd_spmm_fwd(
            x, w, bp.block_idx, bias=b, activation="relu"), x, w, b)
        assert set(kernels) == {"csd_spmm_fwd"}


@pytest.mark.parametrize("activation", ["relu", "gelu"])
def test_fwd_dx_dw_gradient_compiles(one_chip, activation):
    bp = _pattern("up")
    x = _spec((TOKENS, bp.n_in), BF16, one_chip)
    w = _spec((bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out), BF16,
              one_chip)
    b = _spec((bp.n_out,), BF16, one_chip)

    def loss(x, w, b):
        y = ops.csd_matmul(x, w, bp, bias=b, activation=activation,
                           backend="pallas")
        return jnp.sum(y.astype(jnp.float32))

    kernels = _compile(jax.grad(loss, argnums=(0, 1, 2)), x, w, b)
    # forward, dx and dw each lower to their own kernel
    assert set(kernels) == {"csd_spmm_fwd", "csd_spmm_dx", "csd_spmm_dw"}


def test_batched_fwd_and_gradient_compile(one_chip):
    experts = 4
    bp = _pattern("up")
    x = _spec((experts, TOKENS, bp.n_in), BF16, one_chip)
    w = _spec((experts, bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out),
              BF16, one_chip)
    b = _spec((experts, bp.n_out), BF16, one_chip)
    assert _compile(lambda x, w, b: csd_spmm.csd_spmm_fwd(
        x, w, bp.block_idx, bias=b, activation="relu"), x, w, b).keys() \
        == {"csd_spmm_fwd_batched"}

    def loss(x, w, b):
        y = ops.csd_matmul(x, w, bp, bias=b, activation="gelu",
                           backend="pallas")
        return jnp.sum(y.astype(jnp.float32))

    assert _compile(jax.grad(loss, argnums=(0, 1, 2)), x, w, b).keys() == {
        "csd_spmm_fwd_batched", "csd_spmm_dx_batched",
        "csd_spmm_dw_batched"}


# the cells' junctions at 128 x 128 tiles (bench/configs): qwen2_7b up
# (28 -> 148 blocks, fan-in 14) and down (148 -> 28, fan-in 111), granite's
# expert up (8 -> 4, fan-in 4) and down (4 -> 8, fan-in 3)
CELL_JUNCTIONS = {"qwen_up": (3584, 18944, 0.5), "qwen_down": (18944, 3584, 0.75),
                  "granite_up": (1024, 512, 0.5),
                  "granite_down": (512, 1024, 0.75)}


@pytest.mark.parametrize("junction,experts,rows", [
    ("qwen_up", 0, 8), ("qwen_up", 0, 512),
    ("qwen_down", 0, 8), ("qwen_down", 0, 512),
    ("granite_up", 32, 5120), ("granite_down", 32, 5120),
], ids=["qwen_up-decode", "qwen_up-prefill", "qwen_down-decode",
        "qwen_down-prefill", "granite_up-train", "granite_down-train"])
def test_folded_fwd_compiles_at_cell_widths(one_chip, junction, experts,
                                            rows):
    """The forward as ``csd_matmul`` runs it in the cells (decode's 8 rows,
    prefill's 512, 32 experts of 5,120 rows): it compiles at the
    compiler's default VMEM limit under its kernel name, and the launch
    sparselint captures at these shapes has no finding: its per-step
    working set fits SL104's default budget, its output tiles are
    revisited in consecutive chunks (SL101) and the epilogue chunk is the
    last (SL103)."""
    from repro.analysis import grid_pass
    from repro.analysis.capture import capture_launch
    from repro.core.block_pattern import make_block_pattern
    bp = make_block_pattern(*CELL_JUNCTIONS[junction], block_in=128,
                            block_out=128, seed=0)
    lead = (experts,) if experts else ()
    w_shape = lead + (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out)

    def fwd(x, w):
        return ops.csd_matmul(x, w, bp, backend="pallas")

    kernels = _compile(fwd, _spec(lead + (rows, bp.n_in), BF16, one_chip),
                       _spec(w_shape, BF16, one_chip))
    assert kernels == {"csd_spmm_fwd_batched" if experts
                       else "csd_spmm_fwd": 1}
    launch = capture_launch(fwd, jnp.zeros(lead + (rows, bp.n_in), BF16),
                            jnp.zeros(w_shape, BF16))
    case = grid_pass.KernelCase(junction, lambda: launch, epilogue_axis=3)
    findings, cost = grid_pass.analyze_launch(launch, case)
    assert findings == [], [f.message for f in findings]
    assert cost["vmem_bytes_per_step"] <= grid_pass.DEFAULT_VMEM_BUDGET
    # the fold engaged: fewer grid steps than one per fan-in slot
    assert launch.grid[3] < bp.d_in_b or bp.d_in_b == 1


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles(one_chip, quant):
    """The paged decode at the chat cell's shapes (8 slots of 256 pages of
    16 tokens, qwen2_7b's 4 KV heads of 128 and 7 groups, a pool of 1,500
    pages): it compiles as one kernel under its kernel name, and the launch
    sparselint captures there has no finding: its blocks of 32 pages a step
    fit SL104's budget, each row's output is revisited in consecutive steps
    (SL101) and finalized on its last block (SL103), and every page index,
    clamped ones included, stays in the pool (SL105). The rows' lengths
    mix an empty row, one page, one block and the full table."""
    from repro.analysis import grid_pass
    from repro.analysis.capture import capture_launch
    from repro.kernels.flash_attention import _paged_decode_pallas
    slots, page_size, pages_per_seq, pool = 8, 16, 256, 1500
    hkv, dh = QWEN.n_kv_heads, QWEN.head_dim
    groups = QWEN.n_heads // hkv
    assert (hkv, groups, dh) == (4, 7, 128)
    kv_dtype = jnp.int8 if quant else BF16
    shapes = [(slots, hkv, groups, dh), (pool, page_size, hkv, dh),
              (pool, page_size, hkv, dh), (slots, pages_per_seq), (slots,)]
    dtypes = [BF16, kv_dtype, kv_dtype, jnp.int32, jnp.int32]
    if quant:
        shapes += [(pool, page_size)] * 2
        dtypes += [jnp.float32] * 2

    def decode(q, k, v, pt, ln, *sc):
        return paged_decode_attention(
            q, k, v, pt, ln, backend="pallas", k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None)

    kernels = _compile(decode, *[_spec(sh, dt, one_chip)
                                 for sh, dt in zip(shapes, dtypes)])
    assert kernels == {"paged_decode_attention": 1}

    lengths = np.asarray([0, 1, 16, 17, 512, 513, 2000, 4096], np.int32)
    table = np.full((slots, pages_per_seq), -1, np.int32)
    perm = np.random.default_rng(0).permutation(pool)
    used = 0
    for i, ln in enumerate(lengths):
        n = -(-int(ln) // page_size)
        table[i, :n] = perm[used:used + n]
        used += n
    args = [jnp.zeros(sh, dt) for sh, dt in zip(shapes, dtypes)]
    args[3], args[4] = jnp.asarray(table), jnp.asarray(lengths)
    launch = capture_launch(
        _paged_decode_pallas, *args[:5], window=None, softcap=None,
        scale=dh ** -0.5, interpret=False,
        k_scale=args[5] if quant else None,
        v_scale=args[6] if quant else None)
    case = grid_pass.KernelCase("paged_decode_attention", lambda: launch,
                                epilogue_axis=1)
    findings, cost = grid_pass.analyze_launch(launch, case)
    assert findings == [], [f.message for f in findings]
    assert cost["vmem_bytes_per_step"] <= grid_pass.DEFAULT_VMEM_BUDGET
    # blocked: 32 pages a step, 8 blocks a row
    assert launch.grid == (slots, pages_per_seq // 32)


@pytest.mark.parametrize("chunk", [1, 64], ids=["decode", "prefill"])
def test_paged_step_names_its_kernels(one_chip, chunk):
    """The engine's paged step at qwen2_7b widths (two layers, 8 slots):
    its layer body runs the three FFN junctions (up, gate, down) as
    ``csd_spmm_fwd`` and, in decode, the paged attention; every other op
    (the dense attention projections and head among them) is XLA's and
    carries no kernel name, not even the ops that read a kernel's output
    (strict: no derived instruction is let through either)."""
    from repro.nn import build_model
    from repro.nn.common import dtype_of

    cfg = dataclasses.replace(
        QWEN, n_layers=2,
        sparsity=dataclasses.replace(QWEN.sparsity, backend="pallas"))
    model = build_model(cfg)
    slots, pages = 8, 64

    def shapes(tree):
        return jax.tree.map(
            lambda x: _spec(x.shape, x.dtype, one_chip), tree)

    params = shapes(jax.eval_shape(model.init, jax.random.key(0)))
    cache = shapes(jax.eval_shape(lambda: model.stack.init_paged_cache(
        slots, pages, 16, dtype_of(cfg))))
    i32 = jnp.int32
    kernels = _compile(
        lambda p, c, pt, t, pos, n, s: model.paged_step(
            p, t, pos, n, c, pt, s, backend="pallas"),
        params, cache, _spec((slots, 16), i32, one_chip),
        _spec((slots, chunk), i32, one_chip),
        *[_spec((slots,), i32, one_chip)] * 3, derived=())
    want = {"csd_spmm_fwd": 3}
    if chunk == 1:
        want["paged_decode_attention"] = 1
    assert kernels == want


def test_junction_widths_are_published():
    """The compiles above are at the published widths, not a smoke cut."""
    up, down = _pattern("up"), _pattern("down")
    assert (up.n_in, up.n_out) == (3584, 18944)
    assert (down.n_in, down.n_out) == (18944, 3584)
    assert (up.block_in, up.block_out) == (256, 512)
