"""Pallas kernel sweeps vs pure-jnp oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import block_weights_to_dense, make_block_pattern
from repro.kernels import csd_spmm, ops, ref
from repro.kernels.flash_attention import flash_attention


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


# -- CSD-SpMM: shape x dtype x density sweep ---------------------------------

SPMM_CASES = [
    # (n_in, n_out, bl, br, rho, m, block_m)
    (64, 64, 8, 8, 0.5, 16, 8),
    (128, 64, 16, 16, 0.25, 32, 16),
    (64, 128, 8, 16, 0.75, 24, 8),
    (96, 48, 8, 8, 1.0 / 3.0, 8, 8),
    (256, 256, 32, 32, 0.125, 64, 32),
]


def _d_in_b(n_in, n_out, bl, br, rho):
    return make_block_pattern(n_in, n_out, rho, block_in=bl,
                              block_out=br).d_in_b


def _part_block(d_in_b):
    """The largest divisor of the fan-in between 1 and the whole, or None
    (a fan-in of 1, 2 or a prime)."""
    return next((k for k in range(d_in_b - 1, 1, -1) if d_in_b % k == 0),
                None)


def _fan_in_block(fan_in, d_in_b):
    """Fan-in slots per grid step for a test mode: ``whole`` (derived, the
    whole fan-in at these sizes), ``one`` (one slot a step, the unfolded
    schedule) or ``part`` (several chunks of several slots)."""
    if fan_in == "whole":
        return None
    if fan_in == "one":
        return 1
    return _part_block(d_in_b)


def _fan_in_params(cases, geometry):
    """(case, fan_in) pairs; ``part`` only where the fan-in has a divisor
    between 1 and the whole."""
    return [pytest.param(c, fi, id=f"{i}-{fi}")
            for i, c in enumerate(cases)
            for fi in ("whole", "one", "part")
            if fi != "part" or _part_block(_d_in_b(*geometry(c)))]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "case,fan_in", _fan_in_params(SPMM_CASES, lambda c: c[:5]))
def test_csd_spmm_fwd(case, fan_in, dtype):
    n_in, n_out, bl, br, rho, m, bm = case
    bp = make_block_pattern(n_in, n_out, rho, block_in=bl, block_out=br,
                            seed=1)
    x = jax.random.normal(jax.random.key(0), (m, n_in), dtype)
    w = jax.random.normal(jax.random.key(1),
                          (bp.n_rb, bp.d_in_b, bl, br), dtype)
    k = _fan_in_block(fan_in, bp.d_in_b)
    if k is None:
        assert csd_spmm.fwd_tiling(m, bp.d_in_b, bl, br, x_dtype=dtype,
                                   w_dtype=dtype, block_m=bm) \
            == (bm, bp.d_in_b)
    y_ref = ref.csd_spmm_fwd_ref(x, w, bp.block_idx)
    y = csd_spmm.csd_spmm_fwd(x, w, bp.block_idx, block_m=bm,
                              fan_in_block=k, interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", SPMM_CASES[:3])
def test_csd_spmm_dx_dw(case):
    n_in, n_out, bl, br, rho, m, bm = case
    bp = make_block_pattern(n_in, n_out, rho, block_in=bl, block_out=br,
                            seed=2)
    dy = jax.random.normal(jax.random.key(2), (m, n_out))
    x = jax.random.normal(jax.random.key(3), (m, n_in))
    w = jax.random.normal(jax.random.key(4),
                          (bp.n_rb, bp.d_in_b, bl, br))
    dx = csd_spmm.csd_spmm_dx(dy, w, bp.out_idx, bp.out_slot, block_m=bm,
                              interpret=True)
    dx_ref = ref.csd_spmm_dx_ref(dy, w, bp.out_idx, bp.out_slot)
    np.testing.assert_allclose(dx, dx_ref, atol=2e-5, rtol=2e-5)
    dw = csd_spmm.csd_spmm_dw(x, dy, bp.block_idx, block_in=bl,
                              block_out=br, block_m=bm, interpret=True)
    dw_ref = ref.csd_spmm_dw_ref(x, dy, bp.block_idx, bl, br)
    np.testing.assert_allclose(dw, dw_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("case", SPMM_CASES[:3])
def test_csd_spmm_backward_kernels_match_xla_paths(case):
    """Interpret-mode Pallas dx/dw == the `_xla_dx`/`_xla_dw` fallback
    lowerings — the backward kernels are certified against the exact
    slot-sweep forms the XLA backend executes, not only the ref oracles."""
    n_in, n_out, bl, br, rho, m, bm = case
    bp = make_block_pattern(n_in, n_out, rho, block_in=bl, block_out=br,
                            seed=7)
    pat = ops._Pat(bp)
    dy = jax.random.normal(jax.random.key(10), (m, n_out))
    x = jax.random.normal(jax.random.key(11), (m, n_in))
    w = jax.random.normal(jax.random.key(12),
                          (bp.n_rb, bp.d_in_b, bl, br))
    dx = csd_spmm.csd_spmm_dx(dy, w, bp.out_idx, bp.out_slot, block_m=bm,
                              interpret=True)
    np.testing.assert_allclose(dx, ops._xla_dx(dy, w, pat.out_idx, pat.out_slot), atol=2e-5,
                               rtol=2e-5)
    dw = csd_spmm.csd_spmm_dw(x, dy, bp.block_idx, block_in=bl,
                              block_out=br, block_m=bm, interpret=True)
    np.testing.assert_allclose(dw, ops._xla_dw(x, dy, pat.block_idx, pat.block_in,
                                            pat.block_out), atol=2e-5,
                               rtol=2e-5)


# -- batched (expert-major) kernels vs vmapped oracles -----------------------

BATCHED_CASES = [
    # (E, n_in, n_out, bl, br, rho, m, block_m)
    (2, 64, 64, 8, 8, 0.5, 16, 8),
    (3, 64, 48, 8, 8, 0.5, 16, 8),
    (4, 128, 64, 16, 16, 0.25, 32, 16),
]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "case,fan_in", _fan_in_params(BATCHED_CASES, lambda c: c[1:6]))
def test_csd_spmm_fwd_batched(case, fan_in, dtype):
    e, n_in, n_out, bl, br, rho, m, bm = case
    bp = make_block_pattern(n_in, n_out, rho, block_in=bl, block_out=br,
                            seed=1)
    x = jax.random.normal(jax.random.key(0), (e, m, n_in), dtype)
    w = jax.random.normal(jax.random.key(1),
                          (e, bp.n_rb, bp.d_in_b, bl, br), dtype)
    y_ref = ref.csd_spmm_fwd_batched_ref(x, w, bp.block_idx)
    y = csd_spmm.csd_spmm_fwd(x, w, bp.block_idx, block_m=bm,
                              fan_in_block=_fan_in_block(fan_in, bp.d_in_b),
                              interpret=True)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("case", BATCHED_CASES[:2])
def test_csd_spmm_dx_dw_batched(case):
    """Batched backward kernels vs vmapped ref oracles AND the vmapped XLA
    fallback paths (both lowerings of the same expert-major layout)."""
    e, n_in, n_out, bl, br, rho, m, bm = case
    bp = make_block_pattern(n_in, n_out, rho, block_in=bl, block_out=br,
                            seed=2)
    pat = ops._Pat(bp)
    dy = jax.random.normal(jax.random.key(2), (e, m, n_out))
    x = jax.random.normal(jax.random.key(3), (e, m, n_in))
    w = jax.random.normal(jax.random.key(4),
                          (e, bp.n_rb, bp.d_in_b, bl, br))
    dx = csd_spmm.csd_spmm_dx(dy, w, bp.out_idx, bp.out_slot, block_m=bm,
                              interpret=True)
    np.testing.assert_allclose(
        dx, ref.csd_spmm_dx_batched_ref(dy, w, bp.out_idx, bp.out_slot),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(dx, ops._xla_dx_batched(dy, w, pat),
                               atol=2e-5, rtol=2e-5)
    dw = csd_spmm.csd_spmm_dw(x, dy, bp.block_idx, block_in=bl,
                              block_out=br, block_m=bm, interpret=True)
    np.testing.assert_allclose(
        dw, ref.csd_spmm_dw_batched_ref(x, dy, bp.block_idx, bl, br),
        atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(dw, ops._xla_dw_batched(x, dy, pat),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("fan_in", ["whole", "one", "part"])
@pytest.mark.parametrize("batched", [True, False], ids=["5d", "4d"])
def test_csd_spmm_fwd_batched_epilogue(batched, fan_in, dtype):
    """Fused bias+activation == epilogue outside, in the batched and the
    plain kernel. With the fan-in split over chunks (``one``, ``part``) the
    epilogue must fire once, on the last chunk: a bias added per chunk or
    an activation of a partial sum would show."""
    e, n_in, n_out, bl, br, m, bm = 3, 64, 48, 8, 8, 16, 8
    bp = make_block_pattern(n_in, n_out, 0.5, block_in=bl, block_out=br,
                            seed=3)
    x = jax.random.normal(jax.random.key(5), (e, m, n_in), dtype)
    w = jax.random.normal(jax.random.key(6),
                          (e, bp.n_rb, bp.d_in_b, bl, br), dtype)
    b = jax.random.normal(jax.random.key(7), (e, n_out), dtype)
    z = ref.csd_spmm_fwd_batched_ref(x, w, bp.block_idx).astype(
        jnp.float32) + b[:, None].astype(jnp.float32)
    if not batched:
        x, w, b, z = x[0], w[0], b[0], z[0]
    k = _fan_in_block(fan_in, bp.d_in_b)
    tol = _tol(dtype) if dtype == jnp.bfloat16 \
        else dict(atol=1e-5, rtol=1e-5)

    def close(a, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(want, np.float32), **tol)

    y = csd_spmm.csd_spmm_fwd(x, w, bp.block_idx, bias=b,
                              activation="relu", block_m=bm,
                              fan_in_block=k, interpret=True)
    close(y, jax.nn.relu(z))
    # save_preact returns the pre-activation alongside gelu output
    y2, z2 = csd_spmm.csd_spmm_fwd(x, w, bp.block_idx, bias=b,
                                   activation="gelu", save_preact=True,
                                   block_m=bm, fan_in_block=k,
                                   interpret=True)
    close(z2, z)
    close(y2, jax.nn.gelu(z, approximate=True))


@pytest.mark.parametrize("batched", [False, True], ids=["4d", "5d"])
def test_csd_spmm_fwd_refuses_non_dividing_fan_in_block(batched):
    """A fan-in chunk must divide the fan-in: every chunk's weight block
    lies inside the slab, so no step is masked."""
    bp = make_block_pattern(64, 64, 0.5, block_in=8, block_out=8, seed=1)
    assert bp.d_in_b == 4
    lead = (2,) if batched else ()
    x = jnp.zeros(lead + (16, 64))
    w = jnp.zeros(lead + (bp.n_rb, bp.d_in_b, 8, 8))
    with pytest.raises(ValueError, match="does not divide"):
        csd_spmm.csd_spmm_fwd(x, w, bp.block_idx, block_m=8, fan_in_block=3,
                              interpret=True)


def test_csd_matmul_grad_matches_dense_oracle():
    bp = make_block_pattern(64, 48, 0.5, block_in=8, block_out=8, seed=0)
    x = jax.random.normal(jax.random.key(0), (16, 64))
    w = jax.random.normal(jax.random.key(1), (bp.n_rb, bp.d_in_b, 8, 8))

    def loss_sparse(w):
        y = ops.csd_matmul(x, w, bp, backend="pallas", block_m=8,
                           interpret=True)
        return jnp.sum(jnp.sin(y))

    def loss_dense(w):
        return jnp.sum(jnp.sin(x @ block_weights_to_dense(w, bp)))

    np.testing.assert_allclose(loss_sparse(w), loss_dense(w), rtol=1e-5)
    g1 = jax.grad(loss_sparse)(w)
    g2 = jax.grad(loss_dense)(w)
    np.testing.assert_allclose(g1, g2, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("experts", [0, 3], ids=["4d", "5d"])
@pytest.mark.parametrize("rows,dtype,block_m", [
    ((4, 7), jnp.float32, 8),     # odd M: padding to the given block
    ((8,), jnp.float32, None),    # one f32 sublane tile, derived
    ((8,), jnp.bfloat16, None),   # half a bf16 tile: padded to 16 rows
], ids=["f32-bm8", "f32-8rows", "bf16-8rows"])
def test_csd_matmul_xla_equals_pallas(rows, dtype, block_m, experts):
    bp = make_block_pattern(64, 64, 0.25, block_in=16, block_out=16, seed=3)
    lead = (experts,) if experts else ()
    x = jax.random.normal(jax.random.key(5), lead + rows + (64,), dtype)
    w = jax.random.normal(jax.random.key(6),
                          lead + (bp.n_rb, bp.d_in_b, 16, 16), dtype)
    y1 = ops.csd_matmul(x, w, bp, backend="xla")
    y2 = ops.csd_matmul(x, w, bp, backend="pallas", block_m=block_m,
                        interpret=True)
    tol = _tol(dtype) if dtype == jnp.bfloat16 \
        else dict(atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **tol)
    if block_m is None:
        # the row block is the rows the call has, to a whole sublane tile:
        # no padding to 128
        from repro.analysis.capture import capture_launch
        launch = capture_launch(ops.csd_matmul, x, w, bp, backend="pallas",
                                interpret=True)
        assert launch.out_specs[0].block_shape[1] == \
            csd_spmm.sublane_rows(dtype)
        assert launch.grid[1] == 1


# -- flash attention sweep ------------------------------------------------------

ATTN_CASES = [
    dict(causal=True),
    dict(causal=False),
    dict(causal=True, window=8),
    dict(causal=True, logit_softcap=30.0),
    dict(causal=True, window=16, logit_softcap=50.0),
]


@pytest.mark.parametrize("kwargs", ATTN_CASES)
@pytest.mark.parametrize("dims", [(2, 32, 32, 4, 2, 8), (1, 16, 16, 4, 4, 16),
                                  (2, 16, 16, 8, 1, 8)])
def test_flash_attention_vs_ref(kwargs, dims):
    b, sq, skv, hq, hkv, dh = dims
    q = jax.random.normal(jax.random.key(1), (b, sq, hq, dh))
    k = jax.random.normal(jax.random.key(2), (b, skv, hkv, dh))
    v = jax.random.normal(jax.random.key(3), (b, skv, hkv, dh))
    o_ref = ref.mha_ref(q, k, v, **kwargs)
    o = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                        **kwargs)
    np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.bfloat16])
def test_flash_attention_bf16(dtype):
    b, s, hq, hkv, dh = 2, 32, 4, 2, 8
    q = jax.random.normal(jax.random.key(1), (b, s, hq, dh), dtype)
    k = jax.random.normal(jax.random.key(2), (b, s, hkv, dh), dtype)
    v = jax.random.normal(jax.random.key(3), (b, s, hkv, dh), dtype)
    o_ref = ref.mha_ref(q, k, v, causal=True)
    o = flash_attention(q, k, v, block_q=8, block_k=8, interpret=True,
                        causal=True)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_flash_attention_decode_offset():
    b, skv, hq, hkv, dh = 2, 32, 4, 2, 8
    q = jax.random.normal(jax.random.key(1), (b, 1, hq, dh))
    k = jax.random.normal(jax.random.key(2), (b, skv, hkv, dh))
    v = jax.random.normal(jax.random.key(3), (b, skv, hkv, dh))
    for off in (0, 13, 31):
        o_ref = ref.mha_ref(q, k, v, causal=True, q_offset=off)
        o = flash_attention(q, k, v, causal=True, q_offset=off, block_q=1,
                            block_k=8, interpret=True)
        np.testing.assert_allclose(o, o_ref, atol=2e-5, rtol=2e-5)
