"""sparselint certifies the certifier: deliberately broken artifacts must
produce exactly the expected finding codes, and the shipped tree must
produce none."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from repro.analysis import grid_pass, jaxpr_pass, pattern_pass
from repro.analysis.capture import CapturedLaunch, capture_launch
from repro.analysis.findings import Finding, Report, apply_suppressions
from repro.core import sparsity
from repro.core.block_pattern import (fit_block_pattern, make_block_pattern,
                                      partition_pattern)
from repro.launch.mesh import make_mesh


def _codes(findings):
    return sorted({f.code for f in findings})


# ---------------------------------------------------------------------------
# Pass 1: grid analysis
# ---------------------------------------------------------------------------


def test_injected_aliasing_kernel_flags_sl101():
    """The race-broken csd_spmm_fwd copy (accumulation dim hoisted
    outermost) must produce SL101 and nothing else."""
    case = grid_pass.injected_alias_case()
    findings, _ = grid_pass.analyze_launch(case.build(), case)
    assert _codes(findings) == ["SL101"], findings
    assert len(findings) > 0


def _manual_launch(in_spec, in_shape, grid=(2,)):
    return CapturedLaunch(
        name="synthetic", grid=grid,
        in_specs=[in_spec],
        out_specs=[pl.BlockSpec((2, 5), lambda i: (0, 0))],
        out_shapes=[((4, 10), np.dtype("float32"))],
        in_shapes=[(in_shape, np.dtype("float32"))],
        scalar_args=[], scratch_shapes=[], num_scalar_prefetch=0)


def test_non_dividing_blockspec_flags_sl102():
    launch = _manual_launch(pl.BlockSpec((3, 5), lambda i: (0, 0)), (4, 10))
    findings, _ = grid_pass.analyze_launch(
        launch, grid_pass.KernelCase("synthetic", lambda: launch))
    assert "SL102" in _codes(findings), findings


def test_out_of_range_index_map_flags_sl105():
    launch = _manual_launch(pl.BlockSpec((2, 5), lambda i: (i + 5, 0)),
                            (4, 10))
    findings, _ = grid_pass.analyze_launch(
        launch, grid_pass.KernelCase("synthetic", lambda: launch))
    assert "SL105" in _codes(findings), findings


def test_vmem_budget_flags_sl104():
    launch = _manual_launch(pl.BlockSpec((2, 5), lambda i: (0, 0)), (4, 10))
    findings, _ = grid_pass.analyze_launch(
        launch, grid_pass.KernelCase("synthetic", lambda: launch),
        vmem_budget=16)
    assert "SL104" in _codes(findings), findings


def test_shipped_kernels_have_no_findings():
    """Every shipped Pallas kernel family passes the grid pass clean."""
    findings, cost, covered = grid_pass.run()
    assert findings == [], [str(f.to_dict()) for f in findings]
    # the ISSUE scope: fwd/dx/dw in 4-D and 5-D forms + paged decode
    for want in ("csd_spmm_fwd_4d_relu", "csd_spmm_fwd_5d_batched",
                 "csd_spmm_dx_4d", "csd_spmm_dx_5d_batched",
                 "csd_spmm_dw_4d_db", "csd_spmm_dw_5d_batched",
                 "paged_decode_attention", "flash_attention_fwd"):
        assert want in covered, covered
        assert cost[want]["steps"] > 1


def test_capture_records_real_launch():
    """capture_launch sees the true grid of the real entry point."""
    bp = make_block_pattern(256, 512, 0.5, block_in=128, block_out=128)
    from repro.kernels import csd_spmm
    x = jnp.zeros((128, bp.n_in), jnp.float32)
    w = jnp.zeros((bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out),
                  jnp.float32)
    launch = capture_launch(csd_spmm.csd_spmm_fwd, x, w, bp.block_idx,
                            block_m=128)
    # (E = 1, row blocks, right blocks, fan-in chunks): the whole fan-in
    # is reduced in one step
    assert launch.grid == (1, 1, bp.n_rb, 1)
    assert launch.num_scalar_prefetch == 1
    # index maps evaluate with the real pattern array
    blk = launch.eval_index_map(launch.in_specs[0], (0, 0, 1, 0))
    assert blk == (0, 0, int(bp.block_idx[1, 0]))


# ---------------------------------------------------------------------------
# Pass 2: jaxpr lint
# ---------------------------------------------------------------------------


def test_shard_map_missing_psum_flags_sl205():
    mesh = make_mesh((1,), ("model",))

    def broken(x):
        return jax.shard_map(lambda xl: xl.sum(axis=0), mesh=mesh,
                             in_specs=P("model"), out_specs=P(),
                             check_vma=False)(x)

    traced = jax.jit(broken).trace(jax.ShapeDtypeStruct((4, 8),
                                                        jnp.float32))
    findings = jaxpr_pass.lint_closed_jaxpr(traced.jaxpr, "broken")
    assert _codes(findings) == ["SL205"], findings


def test_shard_map_with_psum_is_clean():
    mesh = make_mesh((1,), ("model",))

    def ok(x):
        return jax.shard_map(
            lambda xl: jax.lax.psum(xl.sum(axis=0), "model"), mesh=mesh,
            in_specs=P("model"), out_specs=P(), check_vma=False)(x)

    traced = jax.jit(ok).trace(jax.ShapeDtypeStruct((4, 8), jnp.float32))
    assert jaxpr_pass.lint_closed_jaxpr(traced.jaxpr, "ok") == []


def test_whole_slab_dequant_flags_sl206():
    """The injected quantization-defeating junction (whole-slab upcast
    before csd_matmul) must trip SL206; the shipped fused-dequant path
    on the same shapes must stay clean."""
    from repro.core.block_pattern import make_block_pattern
    from repro.core.quant import dequantize_slab, quantize_slab
    from repro.kernels import ops as kops

    bp = make_block_pattern(64, 64, 0.5, block_in=16, block_out=16, seed=0)
    w_aval = jax.ShapeDtypeStruct((bp.n_rb, bp.d_in_b, 16, 16), jnp.int8)
    s_aval = jax.ShapeDtypeStruct((bp.n_rb, bp.d_in_b), jnp.float32)
    x_aval = jax.ShapeDtypeStruct((4, 64), jnp.float32)

    def bad(x, w, s):
        return kops.csd_matmul(x, dequantize_slab(w, s), bp, backend="xla")

    traced = jax.jit(bad).trace(x_aval, w_aval, s_aval)
    findings = jaxpr_pass._lint_quant(traced.jaxpr, "bad", None)
    assert _codes(findings) == ["SL206"], findings

    def good(x, w, s):
        return kops.csd_matmul(x, w, bp, backend="xla", w_scale=s)

    traced = jax.jit(good).trace(x_aval, w_aval, s_aval)
    assert jaxpr_pass._lint_quant(traced.jaxpr, "good", None) == []
    # the batched (expert-major) fallback's vmapped per-slot converts
    # must not pattern-match the 5-D slab shape either
    e = 3
    w5 = jax.ShapeDtypeStruct((e, bp.n_rb, bp.d_in_b, 16, 16), jnp.int8)
    s5 = jax.ShapeDtypeStruct((e, bp.n_rb, bp.d_in_b), jnp.float32)
    x5 = jax.ShapeDtypeStruct((e, 4, 64), jnp.float32)
    traced = jax.jit(good).trace(x5, w5, s5)
    assert jaxpr_pass._lint_quant(traced.jaxpr, "good5", None) == []


def test_selftest_inject_produces_sl206():
    """run(inject=True) adds the broken quant subject and it must fire —
    the CI gate that proves SL206 has teeth."""
    traced, _, subject = jaxpr_pass._trace_quant_inject(None)
    assert subject == "quant_inject[selftest]"
    findings = jaxpr_pass._lint_quant(traced.jaxpr, subject, None)
    assert _codes(findings) == ["SL206"], findings


def test_missing_donation_flags_sl202():
    aval = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)  # 4 MiB

    def f(x):
        return x * 2.0

    text = jax.jit(f).trace(aval).lower().as_text()
    findings = jaxpr_pass.lint_donation(text, (aval,), "nodonate")
    assert _codes(findings) == ["SL202"], findings

    text = jax.jit(f, donate_argnums=(0,)).trace(aval).lower().as_text()
    assert jaxpr_pass.lint_donation(text, (aval,), "donate") == []


def test_host_callback_flags_sl201():
    def f(x):
        jax.debug.callback(lambda v: None, x)
        return x + 1

    traced = jax.jit(f).trace(jax.ShapeDtypeStruct((4,), jnp.float32))
    findings = jaxpr_pass.lint_closed_jaxpr(traced.jaxpr, "cb")
    assert "SL201" in _codes(findings), findings


def test_large_baked_constant_flags_sl204():
    big = jnp.zeros((512, 1024), jnp.float32)  # 2 MiB closure constant

    def f(x):
        return x + big

    traced = jax.jit(f).trace(
        jax.ShapeDtypeStruct((512, 1024), jnp.float32))
    findings = jaxpr_pass.lint_closed_jaxpr(traced.jaxpr, "const")
    assert "SL204" in _codes(findings), findings


# ---------------------------------------------------------------------------
# Pass 3: pattern invariants
# ---------------------------------------------------------------------------


def _demo():
    return make_block_pattern(512, 512, 0.5, block_in=128, block_out=128)


def test_valid_pattern_is_clean():
    assert pattern_pass.check_pattern(_demo(), "demo") == []


def test_duplicate_edge_flags_sl301():
    bp = _demo()
    idx = np.asarray(bp.block_idx).copy()
    idx[0, 1] = idx[0, 0]  # same left block twice in one row
    bad = dataclasses.replace(bp, block_idx=idx)
    codes = _codes(pattern_pass.check_pattern(bad, "dup"))
    assert "SL301" in codes, codes


def test_scatter_gather_mismatch_flags_sl303():
    bp = _demo()
    oi = np.asarray(bp.out_idx).copy()
    osl = np.asarray(bp.out_slot)
    # retarget one scatter entry of left block 0 at a (right block, slot)
    # cell it does not actually feed — still duplicate-free, but no longer
    # the transpose of block_idx
    taken = {(int(r), int(s)) for r, s in zip(oi[0], osl[0])}
    s0 = int(osl[0, 0])
    oi[0, 0] = next(r for r in range(bp.n_rb) if (r, s0) not in taken)
    bad = dataclasses.replace(bp, out_idx=oi)
    codes = _codes(pattern_pass.check_pattern(bad, "mismatch"))
    assert "SL303" in codes, codes


def test_out_of_range_pattern_flags_sl304():
    bp = _demo()
    idx = np.asarray(bp.block_idx).copy()
    idx[0, 0] = bp.n_lb + 3
    bad = dataclasses.replace(bp, block_idx=idx)
    assert "SL304" in _codes(pattern_pass.check_pattern(bad, "oob"))


def test_unbalanced_shard_pattern_flags_sl305():
    part = partition_pattern(_demo(), 2)
    ov = np.asarray(part.out_valid).copy()
    ov[1, 0, :] = 0  # drop one shard's slots: unbalanced work
    bad = dataclasses.replace(part, out_valid=ov)
    codes = _codes(pattern_pass.check_partition(bad, "unbal"))
    assert "SL305" in codes, codes


def test_valid_partition_is_clean():
    part = partition_pattern(_demo(), 4)
    assert pattern_pass.check_partition(part, "demo") == []


# ---------------------------------------------------------------------------
# debug wiring + repair semantics (satellite 3)
# ---------------------------------------------------------------------------


def test_fit_block_pattern_debug_certifies():
    class SP:
        enabled, block_in, block_out = True, 128, 128
        method, seed, cf_type, dither = "clashfree", 0, 1, False

    bp = fit_block_pattern(512, 512, 0.5, SP(), debug=True)
    assert bp is not None


def test_pattern_debug_env_flag(monkeypatch):
    monkeypatch.setenv("REPRO_PATTERN_DEBUG", "1")
    part = partition_pattern(_demo(), 2)  # must not raise
    assert part.n_shards == 2


def test_repair_raises_when_impossible():
    rng = np.random.default_rng(0)
    # left id 0 occurs 6 times but only 2 rows exist
    idx = np.zeros((2, 3), np.int64)
    with pytest.raises(ValueError, match="impossible"):
        sparsity._repair_duplicates(idx, n_left=4, rng=rng)
    # rows wider than the left side can never be duplicate-free
    idx = np.tile(np.arange(5), (2, 1))
    with pytest.raises(ValueError, match="impossible"):
        sparsity._repair_duplicates(idx, n_left=3, rng=rng)


def test_repair_still_fixes_feasible_duplicates():
    rng = np.random.default_rng(0)
    idx = np.array([[0, 0, 1], [2, 3, 1]])  # feasible: swap 0 with 2/3
    out = sparsity._repair_duplicates(idx, n_left=4, rng=rng)
    assert all(len(set(r)) == len(r) for r in out.tolist())
    assert sorted(np.asarray(out).reshape(-1).tolist()) == \
        sorted(idx.reshape(-1).tolist())


# ---------------------------------------------------------------------------
# report + CLI plumbing
# ---------------------------------------------------------------------------


def test_suppressions_mark_but_keep_findings():
    fs = [Finding("SL101", "kern_a", "boom"),
          Finding("SL101", "kern_b", "boom")]
    out = apply_suppressions(fs, [("SL101", "kern_a", "known issue")])
    assert out[0].suppressed and out[0].justification == "known issue"
    assert not out[1].suppressed
    r = Report(findings=out)
    assert len(r.unsuppressed()) == 1
    assert "suppressed" in r.to_text()


def test_cli_exit_codes():
    from repro.analysis import lint
    assert lint.main(["--passes", "grid,pattern", "--format", "json",
                      "--output", "/dev/null"]) == 0
    assert lint.main(["--passes", "grid", "--selftest-inject",
                      "--output", "/dev/null"]) == 1
