"""CSD-SpMM — Clash-free Structured pre-Defined Sparse Matrix Multiply.

Pallas/TPU kernels for the block-circulant pre-defined sparse junction
(DESIGN.md §2). This is the compute hot-spot the paper accelerates: eq. (2a)
forward, eq. (3b) backward-data, eq. (4b) backward-weights — lifted from
per-edge FPGA processing to per-tile MXU processing.

Mapping of the paper's architecture onto the TPU grid:

* the ``z`` parallel edge processors  -> one (block_m x bR) output tile per
  grid step; every MXU issue covers bL*bR "edges";
* the ``z`` banked activation SRAMs   -> VMEM tiles of ``x`` selected by the
  *scalar-prefetched* pattern ``block_idx`` (the interleaved-order access of
  Fig. 2(b): the index map plays the role of the address generator built
  from the seed vector ``phi``);
* clash-freedom                       -> each grid step streams one left
  block per fan-in slot it reduces (the forward folds ``k`` slots into a
  step, the backward kernels one); consecutive ``f`` steps revisit the
  same *output* tile so the partial sum stays resident in VMEM (the
  "natural order" write of Fig. 2(b));
* the sigmoid/ReLU unit next to the edge processors -> the fused epilogue:
  bias-add + activation are applied on the last fan-in chunk while the
  accumulator tile is still in VMEM, so the pre-activation never
  round-trips HBM (see ``csd_spmm_fwd(bias=..., activation=...)``).

Weight layout: ``w[n_rb, d_in_b, bL, bR]`` — right-block major, exactly the
paper's edge numbering (§III-B: "edges are numbered sequentially ... on the
right side of the junction").

Batched (expert-major) junctions: every kernel also accepts a stacked
weight slab ``w[E, n_rb, d_in_b, bL, bR]`` with activations
``x[E, M, n_in]`` — the layout of MoE expert FFNs, where ``E`` experts
share one junction *pattern* but own private weights. The expert index
becomes the *leading* (outermost, slowest-varying) grid dimension, so one
``BlockPattern`` is scalar-prefetched once and serves every expert — the
paper's "not tied to a specific number of neurons" architecture replicated
per expert with zero extra pattern memory. Inner grid order (row tile,
right block, fan-in slot or chunk) is unchanged, so the per-expert
schedule, VMEM residency, and clash-freedom argument are identical to the
unbatched case; the plain forward runs as the batched one with E = 1.

All kernels are validated against ``ref.py`` in interpret mode (CPU) by
``tests/test_kernels.py``; ``tests/test_tpu_compile.py`` compiles them to
Mosaic for a described TPU v5e at qwen2_7b's widths.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics as _obs_metrics
from .names import pallas_names
from .vmem import VMEM_BUDGET


# ---------------------------------------------------------------------------
# Forward: y[m, rb] = sum_f x[m, block_idx[rb, f]] @ w[rb, f]
#
# Each grid step reduces a chunk of k fan-in slots: a grid step costs a
# fixed few tenths of a microsecond, against 21 ns of MXU work for one
# (128 x 128) slot, so the step count, not the work, set the time of the
# one-slot-per-step schedule. ``fwd_tiling`` derives k and the row block
# from the shapes and the VMEM budget.
#
# Fused epilogue: on the LAST fan-in chunk of each output tile the partial
# sum is still resident in VMEM, so bias-add and the activation are applied
# there — the pre-activation never round-trips HBM. This mirrors the FPGA
# architecture (Dey et al. §III): the sigmoid/ReLU unit sits next to the
# edge processors, directly on the accumulated activation memory.
# ---------------------------------------------------------------------------

# activations the fused epilogue supports. "gelu" is the tanh approximation
# — the same function the model stack's activation registry binds to the
# name (jax.nn.gelu default), keeping fused and unfused paths bit-comparable.
ACTIVATIONS = ("relu", "gelu")


def apply_activation(z: jax.Array, activation: Optional[str]) -> jax.Array:
    """The one definition of every fusable activation — used inside the
    kernel epilogue, by the XLA fallback, and by layers applying the same
    nonlinearity out-of-kernel, so the variants can never drift."""
    if activation is None:
        return z
    if activation == "relu":
        return jnp.maximum(z, 0)
    if activation == "gelu":
        return jax.nn.gelu(z, approximate=True)
    raise ValueError(f"unsupported fused activation {activation!r}")


def mask_cotangent(dy: jax.Array, aux: jax.Array,
                   activation: Optional[str]) -> jax.Array:
    """Fused-epilogue backward: fold the activation derivative into the
    cotangent. ``aux`` is the saved output ``y`` for relu (its sign IS the
    mask) and the saved pre-activation ``z`` for gelu. Pure jnp, so the
    same definition runs inside the Pallas BP/UP kernel bodies (the fused
    backward epilogue — the cotangent never round-trips HBM unmasked) and
    on host-side tiles in tests."""
    if activation is None:
        return dy
    if activation == "relu":
        # compare in f32: the v5e vector unit has no bf16 comparison
        return dy * (aux.astype(jnp.float32) > 0).astype(dy.dtype)
    if activation == "gelu":
        # analytic derivative of the tanh approximation — matches what
        # jax.vjp derives for jax.nn.gelu(approximate=True) to rounding
        z = aux.astype(jnp.float32)
        c = np.float32(np.sqrt(2.0 / np.pi))
        a = np.float32(0.044715)
        t = jnp.tanh(c * (z + a * z * z * z))
        g = 0.5 * (1.0 + t) \
            + 0.5 * z * (1.0 - t * t) * c * (1.0 + 3.0 * a * z * z)
        return (dy.astype(jnp.float32) * g).astype(dy.dtype)
    raise ValueError(f"unsupported fused activation {activation!r}")


def _bias_tile(br: int) -> tuple:
    """Block of one right block's bias (or db) row in the ``(n_rb, 1, bR)``
    layout: the block-row dim is squeezed and the trailing ``(1, bR)``
    equals the array's own trailing dims, which is what the TPU's (8, 128)
    block rule accepts for a one-row tile."""
    return (pl.squeezed, 1, br)


# Row blocks stop growing here: at 512 rows an x tile already holds four
# times a weight tile's bytes, so re-reading the weights once per row block
# costs at most a fifth of the call's bytes, and VMEM is left to fan-in.
MAX_BLOCK_M = 512


def sublane_rows(dtype) -> int:
    """Rows of one TPU tile of ``dtype`` (8 for f32, 16 for bf16)."""
    return max(8, 32 // np.dtype(dtype).itemsize)


def fwd_rows(m: int, dtype) -> int:
    """Rows a forward call of ``m`` rows is padded to when its row block is
    derived: a sublane tile's multiple up to 128 rows (decode's handful of
    rows stays a handful), else a multiple of 128 (the backward kernels'
    row block)."""
    if m <= 128:
        return -(-m // sublane_rows(dtype)) * sublane_rows(dtype)
    return -(-m // 128) * 128


def _fwd_vmem(block_m: int, k: int, bl: int, br: int, x_bytes: int,
              w_bytes: int, n_out: int, has_bias: bool) -> int:
    """Per-step working set as SL104 counts it: every in/out block double
    buffered: k x tiles, the (k, bL, bR) weight block, the f32 output
    tile(s), the bias row."""
    per = (k * (block_m * bl * x_bytes + bl * br * w_bytes)
           + n_out * block_m * br * 4 + (br * 4 if has_bias else 0))
    return 2 * per


def fwd_tiling(m: int, d_in_b: int, bl: int, br: int, *, x_dtype, w_dtype,
               n_out: int = 1, has_bias: bool = False,
               block_m: Optional[int] = None,
               fan_in_block: Optional[int] = None) -> tuple:
    """``(block_m, k)`` of a forward call over ``m`` rows: the row block and
    the fan-in slots each grid step reduces.

    The step count per right block is ``(m / block_m) * ceil(d_in_b / k)``;
    the pair with the fewest steps whose working set fits SL104's VMEM
    budget wins, the larger row block on a tie. ``k`` divides ``d_in_b``
    (every block lies inside the slab), so the whole fan-in where it fits.
    Row blocks are sublane-tile multiples dividing ``m``, up to
    ``MAX_BLOCK_M``. An explicit ``block_m`` (tests, the tune cache) is
    taken as given, as is ``fan_in_block`` (tests), which must divide
    ``d_in_b``; with no pair that fits, the smallest candidates.
    """
    if fan_in_block is not None and d_in_b % fan_in_block:
        raise ValueError(f"fan_in_block={fan_in_block} does not divide "
                         f"the fan-in d_in_b={d_in_b}")
    s = sublane_rows(x_dtype)
    if block_m is not None:
        rows = [block_m]
    else:
        rows = [b for b in range(s, min(m, MAX_BLOCK_M) + 1, s)
                if m % b == 0] or [m]
    if fan_in_block is not None:
        slots = [fan_in_block]
    else:
        slots = [d for d in range(d_in_b, 0, -1) if d_in_b % d == 0]
    xb, wb = np.dtype(x_dtype).itemsize, np.dtype(w_dtype).itemsize
    best, best_key = (rows[0], slots[-1]), None
    for bm in rows:
        for k in slots:
            if _fwd_vmem(bm, k, bl, br, xb, wb, n_out,
                         has_bias) > VMEM_BUDGET:
                continue
            key = ((m // bm) * (d_in_b // k), -bm)
            if best_key is None or key < best_key:
                best, best_key = (bm, k), key
            break  # slots run largest first: the first that fits is best
    return best


def _fwd_kernel(*refs, k: int, d_in_b: int, quant: bool,
                activation: Optional[str], has_bias: bool,
                save_preact: bool):
    """One grid step ``(e, i, r, f)``: reduce fan-in slots ``f*k .. f*k+k-1``
    of output tile ``(e, i, r)``.

    refs: idx, [scale] (scalar prefetch), k x tiles, the (k, bL, bR)
    weight block, [bias], y, [preact]; ``k`` divides ``d_in_b``."""
    n_sp = 2 if quant else 1
    scale_ref = refs[1] if quant else None
    x_refs = refs[n_sp:n_sp + k]
    w_ref = refs[n_sp + k]
    rest = refs[n_sp + k + 1:]
    b_ref = rest[0] if has_bias else None
    out_refs = rest[1:] if has_bias else rest
    y_ref = out_refs[0]
    e, r, f = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    n_f = d_in_b // k

    @pl.when(f == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    for j in range(k):
        x = x_refs[j][0]  # (block_m, bL)
        w = w_ref[0, 0, j]  # (bL, bR)
        if quant:
            w = w.astype(x.dtype)  # int8 -> compute dtype, in register
        p = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                                preferred_element_type=y_ref.dtype)
        if quant:
            p = p * scale_ref[e, r, f * k + j]  # per-block scale, SMEM
        y_ref[0] += p

    if has_bias or activation is not None or save_preact:
        @pl.when(f == n_f - 1)
        def _epilogue():
            z = y_ref[0]
            if has_bias:
                z = z + b_ref[...].astype(z.dtype)  # (1, bR) broadcasts
            if save_preact:
                out_refs[1][0] = z
            y_ref[0] = apply_activation(z, activation)


def _count_fwd(form: str, steps: int, k: int, d_in_b: int) -> None:
    """Trace-time counters of the forward's tiling (like
    ``repro_junction_dispatch_total``: per compiled call, no op in the
    program): grid steps of each call, and the fan-in slots each step
    reduces, per form and fan-in."""
    reg = _obs_metrics.get_registry()
    reg.counter(
        "repro_junction_fwd_grid_steps_total",
        "csd_spmm forward grid steps per traced call, by form",
    ).inc(steps, form=form)
    reg.gauge(
        "repro_junction_fwd_slots_per_step",
        "fan-in slots a csd_spmm forward grid step reduces, by form and "
        "fan-in",
    ).set(k, form=form, fan_in=d_in_b)


def csd_spmm_fwd(
    x: jax.Array,
    w: jax.Array,
    block_idx: np.ndarray,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    save_preact: bool = False,
    block_m: Optional[int] = None,
    fan_in_block: Optional[int] = None,
    interpret: bool = False,
    w_scale: Optional[jax.Array] = None,
):
    """Forward block-sparse matmul with optional fused bias/activation.

    x: (M, n_in) with n_in = n_lb*bL; w: (n_rb, d_in_b, bL, bR);
    block_idx: (n_rb, d_in_b) int32; bias: (n_rb*bR,) or None ->
    y: (M, n_rb*bR) = activation(x @ W_sparse + bias).

    Batched (expert-major) form: w (E, n_rb, d_in_b, bL, bR) with
    x (E, M, n_in) and bias (E, n_rb*bR) -> y (E, M, n_rb*bR); the expert
    index is the leading grid dimension and the pattern is shared. The
    plain form runs as the batched one with E = 1.

    Grid ``(E, M/block_m, n_rb, d_in_b/k)``: each step reduces ``k``
    fan-in slots of one output tile, which stays in VMEM across the
    chunks; the epilogue fires on the last chunk. ``block_m`` and ``k``
    come from the shapes (``fwd_tiling``) unless given (``fan_in_block``,
    a divisor of ``d_in_b``, is a seam for tests); ``M`` must be a
    multiple of ``block_m``. Each of the ``k`` x tiles has its own
    BlockSpec, indexed through the scalar-prefetched pattern.

    ``save_preact=True`` additionally returns the pre-activation
    ``z = x @ W_sparse + bias`` (needed by the backward pass of non-masking
    activations like gelu); the return value is then ``(y, z)``.

    ``w_scale`` selects the int8-quantized forward (inference only, no
    VJP): ``w`` must be int8 with per-block scales ``(n_rb, d_in_b)``
    (resp. ``(E, n_rb, d_in_b)``) from ``core.quant.quantize_slab``;
    dequantization is folded into each slot's accumulate, before the
    epilogue.
    """
    if activation is not None and activation not in ACTIVATIONS:
        raise ValueError(f"unsupported fused activation {activation!r}")
    quant = w_scale is not None
    if quant:
        if save_preact:
            raise ValueError(
                "save_preact is unsupported on the quantized path "
                "(inference-only; training stays full-width)")
        if w.dtype != jnp.int8:
            raise ValueError(f"w_scale given but w.dtype={w.dtype}, "
                             f"expected int8")
    batched = w.ndim == 5
    if not batched:
        x, w = x[None], w[None]
        bias = None if bias is None else bias[None]
        w_scale = None if w_scale is None else w_scale[None]
    e, m, n_in = x.shape
    _, n_rb, d_in_b, bl, br = w.shape
    if n_in % bl:
        raise ValueError("n_in not divisible by block_in")
    has_bias = bias is not None
    n_out = 2 if save_preact else 1
    block_m, k = fwd_tiling(m, d_in_b, bl, br, x_dtype=x.dtype,
                            w_dtype=w.dtype, n_out=n_out, has_bias=has_bias,
                            block_m=block_m, fan_in_block=fan_in_block)
    if m % block_m:
        raise ValueError(f"M={m} not divisible by block_m={block_m}")
    acc_dtype = jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float32) \
        else x.dtype

    # scalar prefetch: the pattern (and the int8 scales)
    prefetch = [jnp.asarray(block_idx, jnp.int32)]
    if quant:
        prefetch.append(jnp.asarray(w_scale, jnp.float32))
    in_specs = [
        # x tile j of the step: row block i, the left block the pattern
        # names for slot f*k + j of right block r
        pl.BlockSpec((1, block_m, bl),
                     lambda e, i, r, f, idx, *_, j=j: (e, i, idx[r, f * k + j]))
        for j in range(k)]
    # weight block: slots f*k .. f*k+k-1 of right block r, contiguous
    in_specs.append(pl.BlockSpec((1, 1, k, bl, br),
                                 lambda e, i, r, f, *_: (e, r, f, 0, 0)))
    operands = [x] * k + [w]
    if has_bias:
        # bias as (E, n_rb, 1, bR): one right-block row per output tile
        in_specs.append(pl.BlockSpec((pl.squeezed,) + _bias_tile(br),
                                     lambda e, i, r, f, *_: (e, r, 0, 0)))
        operands.append(bias.reshape(e, n_rb, 1, br))
    out_spec = pl.BlockSpec((1, block_m, br),
                            lambda e, i, r, f, *_: (e, i, r))
    out_shape = jax.ShapeDtypeStruct((e, m, n_rb * br), acc_dtype)
    grid = (e, m // block_m, n_rb, d_in_b // k)
    form = {(False, False): "plain", (False, True): "batched",
            (True, False): "quant", (True, True): "quant_batched"}[
                (quant, batched)]
    kernel_name = "csd_spmm_fwd" + ("_int8" if quant else "") \
        + ("_batched" if batched else "")
    _count_fwd(form, int(np.prod(grid)), k, d_in_b)
    kernel = functools.partial(_fwd_kernel, k=k, d_in_b=d_in_b, quant=quant,
                               activation=activation, has_bias=has_bias,
                               save_preact=save_preact)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=grid,
            in_specs=in_specs,
            out_specs=(out_spec,) * n_out if save_preact else out_spec,
        ),
        out_shape=(out_shape,) * n_out if save_preact else out_shape,
        interpret=interpret,
        **pallas_names(kernel_name),
    )(*prefetch, *operands)
    outs = out if save_preact else (out,)
    outs = tuple(o.astype(x.dtype) if batched else o[0].astype(x.dtype)
                 for o in outs)
    return outs if save_preact else outs[0]


# ---------------------------------------------------------------------------
# Backward-data: dx[m, lb] = sum_g dy[m, out_idx[lb, g]] @ w[out_idx, out_slot].T
# (eq. (3b): the transpose pattern is itself structured — degrees swap)
#
# Fused backward epilogue: when ``activation`` is given, the cotangent is
# masked (``mask_cotangent``) tile-by-tile INSIDE the kernel from the saved
# ``aux`` (y for relu, pre-activation for gelu) — the unmasked dy is read
# straight from HBM and never materialized masked.
#
# ``out_valid`` (same shape as out_idx, 0/1) marks padded scatter entries:
# shard-local transpose patterns have non-uniform out-degree and are padded
# to a fixed d_loc; padded entries contribute zero.
# ---------------------------------------------------------------------------


def _dx_kernel(*refs, batched: bool, has_valid: bool,
               activation: Optional[str]):
    ns = 3 if has_valid else 2
    scalar_refs, rest = refs[:ns], refs[ns:]
    ovalid_ref = scalar_refs[2] if has_valid else None
    if activation is not None:
        dy_ref, aux_ref, w_ref, dx_ref = rest
    else:
        (dy_ref, w_ref, dx_ref), aux_ref = rest, None
    base = 1 if batched else 0
    l = pl.program_id(base + 1)
    g = pl.program_id(base + 2)

    @pl.when(g == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    def tile(ref):
        return ref[0] if batched else ref[...]

    dy = tile(dy_ref)  # (block_m, bR)
    if activation is not None:
        dy = mask_cotangent(dy, tile(aux_ref), activation)
    w = w_ref[0, 0, 0] if batched else w_ref[0, 0]  # (bL, bR)
    contrib = jax.lax.dot_general(
        dy, w, (((1,), (1,)), ((), ())),
        preferred_element_type=dx_ref.dtype)
    if has_valid:
        contrib = contrib * ovalid_ref[l, g].astype(contrib.dtype)
    if batched:
        dx_ref[0] += contrib
    else:
        dx_ref[...] += contrib


def csd_spmm_dx(
    dy: jax.Array,
    w: jax.Array,
    out_idx,
    out_slot,
    *,
    out_valid=None,
    aux: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    block_m: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """dx: (M, n_in). dy: (M, n_rb*bR); the scatter pattern arrays come from
    ``BlockPattern.out_idx/out_slot`` (reverse adjacency) and may be traced
    jnp arrays (the sharded path selects them per-device). Batched form:
    dy (E, M, n_rb*bR), w (E, n_rb, d_in_b, bL, bR) -> dx (E, M, n_in).

    ``aux``/``activation`` select the fused backward epilogue (cotangent
    masked in-kernel); ``out_valid`` zeroes padded scatter entries."""
    batched = w.ndim == 5
    if batched:
        e, m, _ = dy.shape
        _, n_rb, d_in_b, bl, br = w.shape
    else:
        m, _ = dy.shape
        n_rb, d_in_b, bl, br = w.shape
    n_lb, d_out_b = out_idx.shape
    if m % block_m:
        raise ValueError(f"M={m} not divisible by block_m={block_m}")
    acc_dtype = jnp.float32 if dy.dtype in (jnp.bfloat16, jnp.float32) else dy.dtype

    has_valid = out_valid is not None
    ns = 3 if has_valid else 2

    def imap(fn):
        # index maps receive (grid..., *scalar_refs); ``*s`` absorbs the
        # optional ovalid ref so one lambda serves both arities
        if batched:
            return (lambda e_, i, l, g, oidx, oslot, *s: fn(
                (e_,), i, l, g, oidx, oslot))
        return (lambda i, l, g, oidx, oslot, *s: fn(
            (), i, l, g, oidx, oslot))

    dy_map = imap(lambda e_, i, l, g, oidx, oslot: e_ + (i, oidx[l, g]))
    w_map = imap(lambda e_, i, l, g, oidx, oslot:
                 e_ + (oidx[l, g], oslot[l, g], 0, 0))
    dx_map = imap(lambda e_, i, l, g, oidx, oslot: e_ + (i, l))

    one = (1,) if batched else ()
    dy_spec = pl.BlockSpec(one + (block_m, br), dy_map)
    in_specs = [dy_spec]
    operands = [jnp.asarray(out_idx, jnp.int32),
                jnp.asarray(out_slot, jnp.int32)]
    if has_valid:
        operands.append(jnp.asarray(out_valid, jnp.int32))
    operands.append(dy)
    if activation is not None:
        if aux is None:
            raise ValueError("fused backward epilogue needs aux")
        in_specs.append(dy_spec)
        operands.append(aux)
    in_specs.append(pl.BlockSpec(one + (1, 1, bl, br), w_map))
    operands.append(w)

    grid = ((e,) if batched else ()) + (m // block_m, n_lb, d_out_b)
    out_shape = jax.ShapeDtypeStruct(
        ((e,) if batched else ()) + (m, n_lb * bl), acc_dtype)
    kernel = functools.partial(_dx_kernel, batched=batched,
                               has_valid=has_valid, activation=activation)
    dx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=ns,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec(one + (block_m, bl), dx_map),
        ),
        out_shape=out_shape,
        interpret=interpret,
        **pallas_names("csd_spmm_dx_batched" if batched else "csd_spmm_dx"),
    )(*operands)
    return dx.astype(dy.dtype)


# ---------------------------------------------------------------------------
# Backward-weights: dw[rb, f] = x[:, block_idx[rb, f]].T @ dy[:, rb]
# (eq. (4b) per tile, accumulated over the batch)
#
# Fused backward epilogue as in the dx kernel; with ``want_db`` the bias
# cotangent db[rb] = sum_m masked_dy[m, rb] rides along as a second output
# (accumulated on the first fan-in slot only, so each dy tile is counted
# once).
# ---------------------------------------------------------------------------


def _dw_kernel(*refs, batched: bool, activation: Optional[str],
               want_db: bool):
    if activation is not None:
        idx_ref, x_ref, dy_ref, aux_ref = refs[:4]
        out_refs = refs[4:]
    else:
        idx_ref, x_ref, dy_ref = refs[:3]
        aux_ref = None
        out_refs = refs[3:]
    dw_ref = out_refs[0]
    db_ref = out_refs[1] if want_db else None
    base = 1 if batched else 0
    f = pl.program_id(base + 1)
    i = pl.program_id(base + 2)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    def tile(ref):
        return ref[0] if batched else ref[...]

    x = tile(x_ref)    # (block_m, bL)
    dy = tile(dy_ref)  # (block_m, bR)
    if activation is not None:
        dy = mask_cotangent(dy, tile(aux_ref), activation)
    acc = jax.lax.dot_general(
        x, dy, (((0,), (0,)), ((), ())),
        preferred_element_type=dw_ref.dtype)
    if batched:
        dw_ref[0, 0, 0] += acc
    else:
        dw_ref[0, 0] += acc

    if want_db:
        @pl.when((f == 0) & (i == 0))
        def _init_db():
            db_ref[...] = jnp.zeros_like(db_ref)

        @pl.when(f == 0)
        def _acc_db():
            db_ref[...] += jnp.sum(
                dy.astype(db_ref.dtype), axis=0, keepdims=True
            ).reshape(db_ref.shape)


def csd_spmm_dw(
    x: jax.Array,
    dy: jax.Array,
    block_idx,
    *,
    block_in: int,
    block_out: int,
    aux: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    want_db: bool = False,
    block_m: int = 128,
    interpret: bool = False,
):
    """dw: (n_rb, d_in_b, bL, bR), batch-accumulated (innermost grid dim).
    Batched (expert-major) form: x (E, M, n_in), dy (E, M, n_out) ->
    dw (E, n_rb, d_in_b, bL, bR); per-expert accumulation over M only —
    any 3-D input IS interpreted as expert-batched (fwd/dx dispatch on the
    unambiguous w.ndim; dw has no w, so the rank of x decides).

    ``aux``/``activation`` select the fused backward epilogue; with
    ``want_db`` returns ``(dw, db)`` where db (f32, (n_out,) or (E,
    n_out)) is the masked bias cotangent."""
    if x.ndim != dy.ndim or x.ndim not in (2, 3):
        raise ValueError(
            f"x/dy must both be 2-D (unbatched) or 3-D (expert-batched), "
            f"got {x.shape} / {dy.shape}")
    batched = x.ndim == 3
    if batched:
        e, m, n_in = x.shape
    else:
        m, n_in = x.shape
    n_rb, d_in_b = block_idx.shape
    bl, br = block_in, block_out
    if m % block_m:
        raise ValueError(f"M={m} not divisible by block_m={block_m}")

    one = (1,) if batched else ()

    def imap(fn):
        if batched:
            return lambda e_, r, f, i, idx: fn((e_,), r, f, i, idx)
        return lambda r, f, i, idx: fn((), r, f, i, idx)

    x_map = imap(lambda e_, r, f, i, idx: e_ + (i, idx[r, f]))
    dy_map = imap(lambda e_, r, f, i, idx: e_ + (i, r))
    dw_map = imap(lambda e_, r, f, i, idx: e_ + (r, f, 0, 0))
    db_map = imap(lambda e_, r, f, i, idx: e_ + (r, 0, 0))

    in_specs = [pl.BlockSpec(one + (block_m, bl), x_map),
                pl.BlockSpec(one + (block_m, br), dy_map)]
    operands = [jnp.asarray(block_idx, jnp.int32), x, dy]
    if activation is not None:
        if aux is None:
            raise ValueError("fused backward epilogue needs aux")
        in_specs.append(pl.BlockSpec(one + (block_m, br), dy_map))
        operands.append(aux)

    grid = ((e,) if batched else ()) + (n_rb, d_in_b, m // block_m)
    dw_spec = pl.BlockSpec(one + (1, 1, bl, br), dw_map)
    dw_shape = jax.ShapeDtypeStruct(
        ((e,) if batched else ()) + (n_rb, d_in_b, bl, br), jnp.float32)
    if want_db:
        sq = (pl.squeezed,) if batched else ()
        out_specs = (dw_spec, pl.BlockSpec(sq + _bias_tile(br), db_map))
        out_shapes = (dw_shape, jax.ShapeDtypeStruct(
            ((e,) if batched else ()) + (n_rb, 1, br), jnp.float32))
    else:
        out_specs = dw_spec
        out_shapes = dw_shape
    kernel = functools.partial(_dw_kernel, batched=batched,
                               activation=activation, want_db=want_db)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        out_shape=out_shapes,
        interpret=interpret,
        **pallas_names("csd_spmm_dw_batched" if batched else "csd_spmm_dw"),
    )(*operands)
    if want_db:
        dw, db = out
        return dw.astype(x.dtype), db.reshape(
            ((e,) if batched else ()) + (n_rb * br,))
    return out.astype(x.dtype)
