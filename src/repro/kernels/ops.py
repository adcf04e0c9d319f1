"""Jit'd public ops wrapping the Pallas kernels, with XLA fallbacks.

``csd_matmul`` is THE differentiable block-sparse junction primitive — the
single execution path every layer in the model stack routes through
(``core.sparse_linear.SparseLinear`` block modes, ``nn.layers.Linear``,
``nn.mlp.SparseMLP``, the FFN junctions). It computes

    y = activation(x @ W_sparse + bias)

with the bias/activation epilogue *fused* into the junction: the Pallas
kernel applies it on the last fan-in slot while the accumulator tile is
still in VMEM (the activation never round-trips HBM), and the XLA
fallback applies it on the slot-wise accumulator so XLA fuses it into the
final slot's consumer. Backend selection:

* ``backend="pallas"``    — pl.pallas_call kernels (TPU; ``interpret=True``
                            executes the same kernel bodies on CPU and is
                            what the test suite sweeps);
* ``backend="xla"``       — slot-wise gather/scatter einsum forms
                            (GSPMD-friendly; what the multi-pod dry-run
                            lowers, letting the SPMD partitioner place
                            collectives);
* ``backend="dense"``     — the dense-ref escape hatch: densify the slab
                            (static zero-filled block gather) and run ONE
                            dense GEMM. Algebraically identical, grads
                            flow only to pattern blocks; the winning move
                            in regimes where structured sparsity loses to
                            a single dense matmul (e.g. rho=0.5 on CPU);
* ``backend="auto"``      — *measured*-auto: consult the ``repro.tune``
                            dispatch cache at trace time (key: op,
                            M-regime, junction dims, rho, E, dtype/quant,
                            device kind) and run the benchmarked winner;
                            on a cache miss (or ``REPRO_TUNE_DISABLE=1``)
                            fall back to the static heuristic — pallas on
                            TPU, xla elsewhere.

``dataflow`` picks the XLA lowering of the forward: ``"gather"`` is
column-parallel (each right block pulls its fan-in — output-sharding
friendly), ``"scatter"`` is row-parallel (each left block pushes partial
sums — input-sharding friendly, GSPMD turns the segment-sum into the
Megatron-style all-reduce). Both are algebraically identical; the Pallas
kernel serves both.

The custom VJP wires the paper's three operations exactly as the hardware
does (Fig. 3): FF = ``csd_spmm_fwd``, BP = ``csd_spmm_dx`` over the
*transpose* pattern, UP = ``csd_spmm_dw``; all three share one weight
layout, the paper's single weight memory bank. The fused epilogue's
gradient is handled by masking the incoming cotangent (relu: sign of the
saved output; gelu: derivative at the saved pre-activation) before it
enters BP/UP.

Batched (expert-major) junctions — the MoE layout
-------------------------------------------------
Passing ``w`` with a leading expert dimension, ``(E, n_rb, d_in_b, bL,
bR)``, selects the batched junction path: ``x`` is ``(E, ..., n_in)``
(one activation slab per expert), ``bias`` is ``(E, n_out)``, and the
result is ``(E, ..., n_out)``. All ``E`` experts share ONE compile-time
``BlockPattern``:

* Pallas — the expert index is the leading (outermost) grid dimension of
  the same FF/BP/UP kernels; the pattern is scalar-prefetched once and
  re-read per expert, so pattern memory does not scale with ``E``;
* XLA fallback — the slot-wise gather/scatter sweeps are ``jax.vmap``-ed
  over the expert dim, keeping the one-output-intermediate peak per
  expert. The fallback is selected exactly as in the unbatched case:
  ``backend="auto"`` resolves to Pallas on TPU and XLA everywhere else
  (and is what GSPMD partitions inside the MoE ``shard_map``).

The batched custom VJP routes expert junctions through the same three
operations, so a stack of expert FFNs trains exactly like the paper's
single junction — this is what ``nn.ffn.MoE`` runs when
``SparsityConfig.moe_sparsity`` is enabled.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.block_pattern import BlockPattern
from ..device import on_tpu
from ..obs import metrics as _obs_metrics
from . import csd_spmm, ref
from .csd_spmm import apply_activation  # noqa: F401 — re-export: layers
#   applying the nonlinearity out-of-kernel use the same one definition


def _count_dispatch(backend: str, form: str) -> None:
    """Per-backend junction dispatch counter. ``csd_matmul`` is called at
    trace time (host-side Python inside ``jax.jit``), so this counts
    junction *instantiations per compiled executable*, not per-step
    executions — which is the useful number: it says which backend/form
    every compiled program routed each junction through, without putting
    any op (or host sync) into the traced program itself. A Pallas kernel
    run by the interpreter counts as backend ``interpret``."""
    _obs_metrics.get_registry().counter(
        "repro_junction_dispatch_total",
        "csd_matmul dispatches by backend/form (counted at trace time)",
    ).inc(backend=backend, form=form)


def _resolve(backend: str) -> str:
    if backend == "auto":
        return "pallas" if on_tpu() else "xla"
    return backend


def run_replicated(fn, mesh, *args):
    """``fn(*args)`` whole on every device of ``mesh``, every operand and
    result replicated. XLA cannot partition a Mosaic kernel, so under a
    multi-device mesh a Pallas call that has no sharded form runs inside
    this ``shard_map`` (sharded operands are gathered at entry)."""
    from jax.sharding import PartitionSpec as P
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def _pad_rows(x, batched: bool, block_m: Optional[int]):
    """Flatten ``x``'s leading dims to M rows (per expert when batched:
    the M axis is -2 in both layouts) and pad M for a Pallas grid: to a
    multiple of ``block_m`` when it is given, else to the forward's derived
    row count (``csd_spmm.fwd_rows``). Returns ``(padded, m)``."""
    xf = x.reshape(((x.shape[0],) if batched else ()) + (-1, x.shape[-1]))
    m = xf.shape[-2]
    rows = -(-m // block_m) * block_m if block_m \
        else csd_spmm.fwd_rows(m, x.dtype)
    if rows > m:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 2) + [(0, rows - m), (0, 0)])
    return xf, m


def _unpad_rows(y, x, m: int):
    """Inverse of ``_pad_rows`` on a result: drop the padded rows and
    restore ``x``'s leading dims."""
    y = y[..., :m, :]
    return y.reshape(x.shape[:-1] + (y.shape[-1],))


def _bwd_block_m(block_m: Optional[int], rows: int) -> int:
    """Row block of the dx/dw kernels: the explicit one, else 128, or the
    whole call where ``_pad_rows`` left fewer rows than that."""
    return block_m or min(rows, 128)


# Static pattern arrays are hashed by id for custom_vjp staticness; wrap them
# in a hashable carrier.
class _Pat:
    """Hashable wrapper for the static pattern (numpy arrays)."""

    def __init__(self, bp: BlockPattern):
        self.block_idx = np.asarray(bp.block_idx, np.int32)
        self.out_idx = np.asarray(bp.out_idx, np.int32)
        self.out_slot = np.asarray(bp.out_slot, np.int32)
        # scatter-form padding mask of shard-local patterns (None = all
        # entries real); every scatter-form consumer below honors it
        self.out_valid = None if getattr(bp, "out_valid", None) is None \
            else np.asarray(bp.out_valid, np.int32)
        self.block_in = bp.block_in
        self.block_out = bp.block_out
        self._key = (self.block_idx.tobytes(), self.out_idx.tobytes(),
                     None if self.out_valid is None
                     else self.out_valid.tobytes(),
                     bp.block_in, bp.block_out)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _Pat) and self._key == other._key


# ---------------------------------------------------------------------------
# Slot-wise XLA implementations. The naive gather-einsum oracle (ref.py)
# materializes the activations expanded per (right-block, fan-in slot) —
# an O(n_rb * d_in_b * bL / n_in) blowup (200x+ for narrow output blocks).
# Processing one fan-in slot at a time keeps the peak at one output-sized
# intermediate: this is the XLA analogue of the kernel's grid loop over f,
# and exactly the paper's "one sweep at a time" schedule (§III-B).
# ---------------------------------------------------------------------------


def _acc_dtype(dtype, n_slots):
    """Cross-slot accumulator dtype: each dot already accumulates in f32
    internally; for few slots a bf16 running sum halves the dominant
    accumulator HBM traffic at negligible numeric cost."""
    if dtype == jnp.bfloat16:
        return dtype if n_slots <= 8 else jnp.float32
    return dtype


def _slot_sweep(slot, acc0, xs):
    """Accumulate ``slot`` over the fan slots (leading dim of every array
    in ``xs``): unrolled for small fan so XLA fuses the short chain,
    ``lax.scan`` otherwise. Shared by every slot-wise XLA form so the
    unroll threshold / accumulator policy cannot diverge between them."""
    n_slots = xs[0].shape[0]
    if n_slots <= 4:
        for i in range(n_slots):
            acc0, _ = slot(acc0, tuple(x[i] for x in xs))
        return acc0
    y, _ = jax.lax.scan(slot, acc0, xs)
    return y


def _xla_fwd(x, w, block_idx):
    """x: (..., n_in) — leading dims preserved so GSPMD keeps their
    (batch, seq) sharding through the take/einsum chain (flattening them
    merges sharded axes and the partitioner gives up -> full replication).
    ``block_idx`` (n_rb, d_in_b) may be numpy or a traced jnp array (the
    sharded path selects the shard-local pattern by ``axis_index``)."""
    n_rb, d_in_b, bl, br = w.shape
    lead = x.shape[:-1]
    xb = x.reshape(lead + (-1, bl))
    idx = jnp.asarray(block_idx).T  # (d_in_b, n_rb)

    def slot(acc, inp):
        idx_f, w_f = inp
        lhs = jnp.take(xb, idx_f, axis=-2)  # (..., n_rb, bL)
        y_f = jnp.einsum("...ri,rio->...ro", lhs, w_f.astype(lhs.dtype))
        return acc + y_f.astype(acc.dtype), None

    acc0 = jnp.zeros(lead + (n_rb, br), _acc_dtype(x.dtype, d_in_b))
    y = _slot_sweep(slot, acc0, (idx, jnp.moveaxis(w, 1, 0)))
    return y.reshape(lead + (n_rb * br,)).astype(x.dtype)


def _xla_fwd_scatter(x, w, out_idx, out_slot, out_valid=None):
    """Row-parallel slot-wise forward: each left block pushes its partial
    product into the right blocks it feeds (segment-sum over the reverse
    adjacency). Same O(one output intermediate) peak as ``_xla_fwd``; the
    different dataflow gives GSPMD the input-sharded lowering.
    ``out_valid`` zeroes padded entries of shard-local scatter forms."""
    n_rb, d_in_b, bl, br = w.shape
    n_lb, d_out_b = out_idx.shape
    lead = x.shape[:-1]
    xb = x.reshape(lead + (n_lb, bl))
    oidx = jnp.asarray(out_idx).T    # (d_out_b, n_lb)
    oslot = jnp.asarray(out_slot).T
    xs = (oidx, oslot)
    if out_valid is not None:
        xs = xs + (jnp.asarray(out_valid).T,)

    def slot(acc, inp):
        oi, os = inp[0], inp[1]
        w_g = w[oi, os].astype(xb.dtype)            # (n_lb, bL, bR)
        if out_valid is not None:
            w_g = w_g * inp[2][:, None, None].astype(w_g.dtype)
        p = jnp.einsum("...li,lio->...lo", xb, w_g)
        contrib = jax.ops.segment_sum(
            jnp.moveaxis(p.astype(acc.dtype), -2, 0), oi,
            num_segments=n_rb)
        return acc + jnp.moveaxis(contrib, 0, -2), None

    acc0 = jnp.zeros(lead + (n_rb, br), _acc_dtype(x.dtype, d_out_b))
    y = _slot_sweep(slot, acc0, xs)
    return y.reshape(lead + (n_rb * br,)).astype(x.dtype)


def _xla_fwd_quant(x, w, scales, block_idx):
    """Quantized gather-form forward (inference only): ``w`` int8 with
    per-block scales ``(n_rb, d_in_b)``. Each slot's int8 block is widened
    per-slot (a rank-3 (n_rb, bL, bR) convert — never the whole 4-D slab,
    which is SL206's contract) and the f32 scale is applied to the slot's
    partial sum before accumulation."""
    n_rb, d_in_b, bl, br = w.shape
    lead = x.shape[:-1]
    xb = x.reshape(lead + (-1, bl))
    idx = jnp.asarray(block_idx).T  # (d_in_b, n_rb)

    def slot(acc, inp):
        idx_f, w_f, s_f = inp  # w_f (n_rb, bL, bR) int8; s_f (n_rb,) f32
        lhs = jnp.take(xb, idx_f, axis=-2)  # (..., n_rb, bL)
        y_f = jnp.einsum("...ri,rio->...ro", lhs, w_f.astype(lhs.dtype))
        return acc + y_f.astype(acc.dtype) * s_f[:, None], None

    acc0 = jnp.zeros(lead + (n_rb, br), jnp.float32)
    y = _slot_sweep(slot, acc0,
                    (idx, jnp.moveaxis(w, 1, 0),
                     jnp.moveaxis(jnp.asarray(scales, jnp.float32), 1, 0)))
    return y.reshape(lead + (n_rb * br,)).astype(x.dtype)


def _xla_fwd_scatter_quant(x, w, scales, out_idx, out_slot, out_valid=None):
    """Quantized row-parallel forward: per-slot rank-3 int8 gathers with
    the gathered f32 scale folded into the partial sum (masking the scale,
    not the slab, zeroes padded shard-local entries)."""
    n_rb, d_in_b, bl, br = w.shape
    n_lb, d_out_b = out_idx.shape
    lead = x.shape[:-1]
    xb = x.reshape(lead + (n_lb, bl))
    sc = jnp.asarray(scales, jnp.float32)
    oidx = jnp.asarray(out_idx).T    # (d_out_b, n_lb)
    oslot = jnp.asarray(out_slot).T
    xs = (oidx, oslot)
    if out_valid is not None:
        xs = xs + (jnp.asarray(out_valid).T,)

    def slot(acc, inp):
        oi, os = inp[0], inp[1]
        w_g = w[oi, os].astype(xb.dtype)  # (n_lb, bL, bR) rank-3 convert
        s_g = sc[oi, os]                  # (n_lb,) f32
        if out_valid is not None:
            s_g = s_g * inp[2].astype(s_g.dtype)
        p = jnp.einsum("...li,lio->...lo", xb, w_g)
        p = p.astype(acc.dtype) * s_g[:, None]
        contrib = jax.ops.segment_sum(
            jnp.moveaxis(p, -2, 0), oi, num_segments=n_rb)
        return acc + jnp.moveaxis(contrib, 0, -2), None

    acc0 = jnp.zeros(lead + (n_rb, br), jnp.float32)
    y = _slot_sweep(slot, acc0, xs)
    return y.reshape(lead + (n_rb * br,)).astype(x.dtype)


def _xla_dx(dy, w, out_idx, out_slot, out_valid=None):
    """``out_valid`` (n_lb, d_out_b) 0/1 marks padded entries of a
    shard-local (non-uniform out-degree) scatter pattern; padded entries
    contribute zero."""
    n_rb, d_in_b, bl, br = w.shape
    n_lb, d_out_b = out_idx.shape
    lead = dy.shape[:-1]
    dyb = dy.reshape(lead + (n_rb, br))
    oidx = jnp.asarray(out_idx).T    # (d_out_b, n_lb)
    oslot = jnp.asarray(out_slot).T
    xs = (oidx, oslot)
    if out_valid is not None:
        xs = xs + (jnp.asarray(out_valid).T,)

    def slot(acc, inp):
        oi, os = inp[0], inp[1]
        lhs = jnp.take(dyb, oi, axis=-2)            # (..., n_lb, bR)
        w_g = w[oi, os].astype(lhs.dtype)           # (n_lb, bL, bR)
        if out_valid is not None:
            w_g = w_g * inp[2][:, None, None].astype(w_g.dtype)
        d = jnp.einsum("...lo,lio->...li", lhs, w_g)
        return acc + d.astype(acc.dtype), None

    acc0 = jnp.zeros(lead + (n_lb, bl), _acc_dtype(dy.dtype, d_out_b))
    dx = _slot_sweep(slot, acc0, xs)
    return dx.reshape(lead + (n_lb * bl,)).astype(dy.dtype)


def _xla_dw(x, dy, block_idx, bl, br):
    n_rb, d_in_b = block_idx.shape
    lead = x.shape[:-1]
    xb = x.reshape(lead + (-1, bl))
    dyb = dy.reshape(lead + (n_rb, br))
    idx = jnp.asarray(block_idx).T

    def slot(_, idx_f):
        lhs = jnp.take(xb, idx_f, axis=-2)  # (..., n_rb, bL)
        return None, jnp.einsum("...ri,...ro->rio",
                                lhs, dyb.astype(lhs.dtype))

    if d_in_b <= 4:
        dws = [slot(None, idx[f])[1] for f in range(d_in_b)]
        dw = jnp.stack(dws, axis=1)
    else:
        _, dws = jax.lax.scan(slot, None, idx)
        dw = jnp.moveaxis(dws, 0, 1)
    return dw.astype(x.dtype)


# ---------------------------------------------------------------------------
# Batched (expert-major) XLA fallbacks: the slot sweeps vmapped over the
# leading expert dim of x and w. The pattern is closed over (shared by all
# experts), so only the weight slab and activations are mapped — the
# per-expert peak memory is identical to the unbatched sweep.
# ---------------------------------------------------------------------------


def _xla_fwd_batched(x, w, pat, dataflow):
    if dataflow == "scatter":
        return jax.vmap(lambda xe, we: _xla_fwd_scatter(
            xe, we, pat.out_idx, pat.out_slot, pat.out_valid))(x, w)
    return jax.vmap(lambda xe, we: _xla_fwd(xe, we, pat.block_idx))(x, w)


def _xla_fwd_quant_batched(x, w, scales, pat, dataflow):
    if dataflow == "scatter":
        return jax.vmap(lambda xe, we, se: _xla_fwd_scatter_quant(
            xe, we, se, pat.out_idx, pat.out_slot, pat.out_valid))(
                x, w, scales)
    return jax.vmap(lambda xe, we, se: _xla_fwd_quant(
        xe, we, se, pat.block_idx))(x, w, scales)


def _xla_dx_batched(dy, w, pat):
    return jax.vmap(lambda de, we: _xla_dx(
        de, we, pat.out_idx, pat.out_slot, pat.out_valid))(dy, w)


def _xla_dw_batched(x, dy, pat):
    return jax.vmap(lambda xe, de: _xla_dw(
        xe, de, pat.block_idx, pat.block_in, pat.block_out))(x, dy)


# ---------------------------------------------------------------------------
# Dense-ref escape hatch (backend="dense"). The autotuner's measurement
# says some regimes (rho=0.5 at training M on CPU) lose to one dense GEMM
# no matter which sparse dataflow runs — the paper's complexity win is a
# FLOP count, the crossover point is a device property. Densify with a
# STATIC slot map + jnp.take (one appended zero block serves every hole),
# never a scatter: the take fuses into the GEMM's prologue (~2% overhead
# at M=512) where `.at[].set()` costs tens of ms per call.
# ---------------------------------------------------------------------------


def _dense_map(pat: _Pat) -> np.ndarray:
    """Static flat map dense block (lb, rb) -> slab slot, sentinel = the
    appended zero block. Cached on the pattern carrier (pure numpy)."""
    cached = getattr(pat, "_dense_map_arr", None)
    if cached is not None:
        return cached
    n_rb, d_in_b = pat.block_idx.shape
    n_lb = pat.out_idx.shape[0]
    sentinel = n_rb * d_in_b
    slot_of = np.full((n_lb, n_rb), sentinel, np.int32)
    rows = np.repeat(np.arange(n_rb, dtype=np.int32), d_in_b)
    slot_of[pat.block_idx.reshape(-1), rows] = np.arange(
        n_rb * d_in_b, dtype=np.int32)
    if int((slot_of != sentinel).sum()) != n_rb * d_in_b:
        raise ValueError(
            "backend='dense' requires distinct (left, right) block pairs "
            "per pattern (duplicate fan-in entry found)")
    pat._dense_map_arr = slot_of.reshape(-1)
    return pat._dense_map_arr


def _densify_slab(w, pat: _Pat):
    """(n_rb, d_in_b, bL, bR) slab -> (n_in, n_out) dense weight (zeros at
    non-pattern blocks). Batched: (E, ...) -> (E, n_in, n_out)."""
    if w.ndim == 5:
        return jax.vmap(lambda we: _densify_slab(we, pat))(w)
    n_rb, d_in_b, bl, br = w.shape
    n_lb = pat.out_idx.shape[0]
    wf = jnp.concatenate([w.reshape(n_rb * d_in_b, bl, br),
                          jnp.zeros((1, bl, br), w.dtype)])
    dense = jnp.take(wf, jnp.asarray(_dense_map(pat)), axis=0)
    dense = jnp.moveaxis(dense.reshape(n_lb, n_rb, bl, br), -2, -3)
    return dense.reshape(n_lb * bl, n_rb * br)


def _dense_grad_slab(dwd, pat: _Pat):
    """Gather the slab-layout weight gradient back out of a dense
    (n_in, n_out) gradient — grads at zero blocks are structurally zero
    and are dropped, exactly matching the sparse-path dw."""
    if dwd.ndim == 3:
        return jax.vmap(lambda g: _dense_grad_slab(g, pat))(dwd)
    n_rb, d_in_b = pat.block_idx.shape
    bl, br = pat.block_in, pat.block_out
    n_lb = pat.out_idx.shape[0]
    g = jnp.moveaxis(dwd.reshape(n_lb, bl, n_rb, br), 1, 2)
    g = g.reshape(n_lb * n_rb, bl, br)
    flat = (pat.block_idx.astype(np.int64) * n_rb
            + np.arange(n_rb, dtype=np.int64)[:, None])  # (n_rb, d_in_b)
    dw = jnp.take(g, jnp.asarray(flat.reshape(-1)), axis=0)
    return dw.reshape(n_rb, d_in_b, bl, br)


# ---------------------------------------------------------------------------
# Differentiable core. Signature: (x, w, b) differentiable; everything else
# static. ``b`` is a zero-length placeholder when has_bias is False so the
# custom_vjp arity stays fixed. Batched-ness is a shape property
# (w.ndim == 5), not an extra static flag — both layouts trace through the
# same custom_vjp.
# ---------------------------------------------------------------------------


def _fwd_impl(x, w, b, pat, has_bias, activation, backend, dataflow,
              block_m, interpret, want_preact=False):
    """Returns (y, preact): preact is the pre-activation z = xW + b when the
    caller is the VJP forward and the backward needs it (gelu), else None
    (relu recovers its mask from y; the primal never pays for the extra
    kernel output)."""
    batched = w.ndim == 5
    if backend == "pallas":
        bias = b if has_bias else None
        if activation == "gelu" and want_preact:
            return csd_spmm.csd_spmm_fwd(
                x, w, pat.block_idx, bias=bias, activation="gelu",
                save_preact=True, block_m=block_m, interpret=interpret)
        y = csd_spmm.csd_spmm_fwd(
            x, w, pat.block_idx, bias=bias, activation=activation,
            block_m=block_m, interpret=interpret)
        return y, None
    if backend == "dense":
        wd = _densify_slab(w, pat).astype(x.dtype)
        z = jnp.einsum("e...i,eio->e...o", x, wd) if batched else x @ wd
    elif batched:
        z = _xla_fwd_batched(x, w, pat, dataflow)
    elif dataflow == "scatter":
        z = _xla_fwd_scatter(x, w, pat.out_idx, pat.out_slot,
                             pat.out_valid)
    else:
        z = _xla_fwd(x, w, pat.block_idx)
    if has_bias:
        bb = b
        if batched:  # (E, n_out) broadcast over the per-expert leading dims
            bb = b.reshape((b.shape[0],) + (1,) * (z.ndim - 2) + b.shape[1:])
        z = z + bb.astype(z.dtype)
    y = csd_spmm.apply_activation(z, activation)
    return y, (z if activation == "gelu" else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _csd_matmul(x, w, b, pat: _Pat, has_bias: bool,
                activation: Optional[str], backend: str, dataflow: str,
                block_m: Optional[int], interpret: bool):
    y, _ = _fwd_impl(x, w, b, pat, has_bias, activation, backend, dataflow,
                     block_m, interpret)
    return y


def _fwd_vjp(x, w, b, pat, has_bias, activation, backend, dataflow,
             block_m, interpret):
    y, preact = _fwd_impl(x, w, b, pat, has_bias, activation, backend,
                          dataflow, block_m, interpret, want_preact=True)
    # relu's gradient mask is recoverable from the output itself — no extra
    # residual; gelu needs the pre-activation the kernel emitted. b rides
    # along so db can match its dtype exactly.
    aux = y if activation == "relu" else preact
    return y, (x, w, b, aux)


def _mask_dy_xla(dy, aux, activation):
    """XLA-path fused-epilogue gradient: mask/scale the cotangent before
    it enters BP (dx) and UP (dw) — eq. (3)/(4) with the activation
    derivative folded into delta. (The Pallas path masks *inside* the
    BP/UP kernels instead — the fused backward epilogue.)"""
    if activation == "relu":
        return dy * (aux > 0).astype(dy.dtype)
    if activation == "gelu":
        _, act_vjp = jax.vjp(
            lambda z: jax.nn.gelu(z, approximate=True),
            aux.astype(jnp.float32))
        return act_vjp(dy.astype(jnp.float32))[0].astype(dy.dtype)
    return dy


def _bwd_vjp(pat, has_bias, activation, backend, dataflow, block_m,
             interpret, res, dy):
    x, w, b, aux = res
    # keep backward slot traffic in the compute dtype — f32 cotangents
    # double the (already dominant) gather/accumulate HBM bytes
    dy = dy.astype(x.dtype)
    batched = w.ndim == 5
    if backend == "pallas":
        block_m = _bwd_block_m(block_m, x.shape[-2])
        # fused backward epilogue: the raw cotangent streams into the
        # BP/UP kernels which mask it tile-by-tile from aux (and fold the
        # bias cotangent into the UP sweep) — no separate elementwise op,
        # no masked-dy round-trip through HBM
        dx = csd_spmm.csd_spmm_dx(dy, w, pat.out_idx, pat.out_slot,
                                  out_valid=pat.out_valid, aux=aux,
                                  activation=activation,
                                  block_m=block_m, interpret=interpret)
        if has_bias:
            dw, db = csd_spmm.csd_spmm_dw(
                x, dy, pat.block_idx, block_in=pat.block_in,
                block_out=pat.block_out, aux=aux, activation=activation,
                want_db=True, block_m=block_m, interpret=interpret)
            db = db.astype(b.dtype)
        else:
            dw = csd_spmm.csd_spmm_dw(
                x, dy, pat.block_idx, block_in=pat.block_in,
                block_out=pat.block_out, aux=aux, activation=activation,
                block_m=block_m, interpret=interpret)
            db = jnp.zeros((0,), b.dtype)
        return dx, dw.astype(w.dtype), db
    dy = _mask_dy_xla(dy, aux, activation)
    if has_bias:
        # batched: keep the per-expert leading dim — db is (E, n_out)
        axes = tuple(range(1 if batched else 0, dy.ndim - 1))
        db = jnp.sum(dy.astype(jnp.float32), axis=axes).astype(b.dtype)
    else:
        db = jnp.zeros((0,), b.dtype)
    if backend == "dense":
        # BP/UP against the densified weight: dx = dy @ W^T, dw = x^T dy
        # gathered back to slab layout (zero-block grads dropped — the
        # same structural-zero contract as the sparse sweeps)
        wd = _densify_slab(w, pat).astype(dy.dtype)
        if batched:
            dx = jnp.einsum("e...o,eio->e...i", dy, wd)
            xf = x.reshape(x.shape[0], -1, x.shape[-1])
            dyf = dy.reshape(dy.shape[0], -1, dy.shape[-1])
            dwd = jnp.einsum("emi,emo->eio", xf, dyf.astype(xf.dtype))
        else:
            dx = jnp.einsum("...o,io->...i", dy, wd)
            xf = x.reshape(-1, x.shape[-1])
            dyf = dy.reshape(-1, dy.shape[-1])
            dwd = xf.T @ dyf.astype(xf.dtype)
        dw = _dense_grad_slab(dwd, pat)
        return dx.astype(x.dtype), dw.astype(w.dtype), db
    if batched:
        dx = _xla_dx_batched(dy, w, pat)
        dw = _xla_dw_batched(x, dy, pat)
    else:
        dx = _xla_dx(dy, w, pat.out_idx, pat.out_slot, pat.out_valid)
        dw = _xla_dw(x, dy, pat.block_idx, pat.block_in, pat.block_out)
    return dx, dw.astype(w.dtype), db


_csd_matmul.defvjp(_fwd_vjp, _bwd_vjp)


# ---------------------------------------------------------------------------
# Sharded (model-parallel) junctions — the jax_pallas form of the paper's
# size-flexible hardware: the same junction processed k block-row ranges at
# a time, one range per mesh device. Under ``shard_map`` every device runs
# its shard-local scalar-prefetched pattern against its slab rows:
#
#   FF — shard-local forward over the local gather pattern; the output
#        feature axis comes out sharded over ``axis`` (column-parallel);
#   BP — shard-local dx over the local (padded, validity-masked) scatter
#        pattern, then ``psum`` over ``axis`` (each shard contributes the
#        cotangent flowing through its output rows);
#   UP — dw and db are SHARD-LOCAL: a device's weight rows only ever see
#        its own dy shard, so weight gradients (and therefore Adam state)
#        stay sharded over ``axis`` ZeRO-style with no extra collectives.
#
# The global slab keeps its logical (n_rb, d_in_b, bL, bR) layout sharded
# contiguously on the block-row dim — exactly what a NamedSharding row
# chunking produces, so entering the shard_map moves no weight data.
# ---------------------------------------------------------------------------


class _ShardPat:
    """Hashable static carrier of a partitioned pattern (stacked per-shard
    arrays; selected per-device by ``axis_index`` inside the shard_map)."""

    def __init__(self, part):
        self.idx = np.asarray(part.idx, np.int32)
        self.oidx = np.asarray(part.out_idx, np.int32)
        self.oslot = np.asarray(part.out_slot, np.int32)
        self.ovalid = np.asarray(part.out_valid, np.int32)
        self.block_in = part.parent.block_in
        self.block_out = part.parent.block_out
        self.n_shards = part.n_shards
        self._key = (self.idx.tobytes(), self.oidx.tobytes(),
                     self.oslot.tobytes(), self.ovalid.tobytes(),
                     self.block_in, self.block_out)

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _ShardPat) and self._key == other._key


_PARTITION_CACHE: dict = {}


def get_partition(pattern: BlockPattern, axis_size: int):
    """Cached ``partition_pattern`` (patterns are immutable; partitioning
    is pure numpy work we only want once per (pattern, k))."""
    from ..core.block_pattern import partition_pattern
    key = (pattern.block_idx.tobytes(), pattern.block_in,
           pattern.block_out, pattern.n_in, pattern.n_out, axis_size)
    part = _PARTITION_CACHE.get(key)
    if part is None:
        part = _PARTITION_CACHE[key] = partition_pattern(pattern, axis_size)
    return part


def _shard_specs(batched, has_bias, lead, axis):
    from jax.sharding import PartitionSpec as P
    x_spec = P(*lead, None)
    if batched:
        w_spec = P(None, axis, None, None, None)
    else:
        w_spec = P(axis, None, None, None)
    if has_bias:
        b_spec = P(None, axis) if batched else P(axis)
    else:
        b_spec = P(axis)  # zero-length placeholder: 0 % k == 0
    y_spec = P(*lead, axis)
    return x_spec, w_spec, b_spec, y_spec


def _local_pattern(spat, axis):
    """Per-device slices of the stacked pattern arrays (traced by
    ``axis_index`` — the device id IS the address-generator seed here)."""
    s = jax.lax.axis_index(axis)
    return (jnp.asarray(spat.idx)[s], jnp.asarray(spat.oidx)[s],
            jnp.asarray(spat.oslot)[s], jnp.asarray(spat.ovalid)[s])


def _spmd_fwd_call(x, w, b, spat, has_bias, activation, backend, block_m,
                   interpret, mesh, axis, lead, want_aux):
    batched = w.ndim == 5
    x_spec, w_spec, b_spec, y_spec = _shard_specs(
        batched, has_bias, lead, axis)

    def local(xl, wl, bl):
        idx, _, _, _ = _local_pattern(spat, axis)
        if backend == "pallas":
            bias_l = bl if has_bias else None
            if want_aux and activation == "gelu":
                return csd_spmm.csd_spmm_fwd(
                    xl, wl, idx, bias=bias_l, activation="gelu",
                    save_preact=True, block_m=block_m, interpret=interpret)
            y = csd_spmm.csd_spmm_fwd(
                xl, wl, idx, bias=bias_l, activation=activation,
                block_m=block_m, interpret=interpret)
            return (y, y) if want_aux else y
        if batched:
            z = jax.vmap(lambda xe, we: _xla_fwd(xe, we, idx))(xl, wl)
        else:
            z = _xla_fwd(xl, wl, idx)
        if has_bias:
            bb = bl
            if batched:
                bb = bl.reshape((bl.shape[0],) + (1,) * (z.ndim - 2)
                                + bl.shape[1:])
            z = z + bb.astype(z.dtype)
        y = csd_spmm.apply_activation(z, activation)
        if want_aux:
            return y, (z if activation == "gelu" else y)
        return y

    out_specs = (y_spec, y_spec) if want_aux else y_spec
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(x_spec, w_spec, b_spec),
                       out_specs=out_specs, check_vma=False)
    return fn(x, w, b)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9,
                                                    10, 11))
def _csd_matmul_spmd(x, w, b, spat: _ShardPat, has_bias: bool,
                     activation: Optional[str], backend: str,
                     block_m: Optional[int], interpret: bool, mesh,
                     axis: str, lead: tuple):
    return _spmd_fwd_call(x, w, b, spat, has_bias, activation, backend,
                          block_m, interpret, mesh, axis, lead,
                          want_aux=False)


def _spmd_fwd_vjp(x, w, b, spat, has_bias, activation, backend, block_m,
                  interpret, mesh, axis, lead):
    if activation is None:
        y = _spmd_fwd_call(x, w, b, spat, has_bias, activation, backend,
                           block_m, interpret, mesh, axis, lead,
                           want_aux=False)
        aux = y  # unused by the backward; placeholder with y's sharding
    else:
        y, aux = _spmd_fwd_call(x, w, b, spat, has_bias, activation,
                                backend, block_m, interpret, mesh, axis,
                                lead, want_aux=True)
    return y, (x, w, b, aux)


def _spmd_bwd_vjp(spat, has_bias, activation, backend, block_m, interpret,
                  mesh, axis, lead, res, dy):
    from jax.sharding import PartitionSpec as P
    x, w, b, aux = res
    dy = dy.astype(x.dtype)
    batched = w.ndim == 5
    x_spec, w_spec, b_spec, y_spec = _shard_specs(
        batched, has_bias, lead, axis)
    bl_, br_ = spat.block_in, spat.block_out
    # mesh axes the batch (lead) dims are mapped over: dw/db sum over the
    # batch, so their shard-local partials must all-reduce over these axes
    # (dw's out-spec is unmapped over them — sparselint SL205)
    lead_axes = tuple(
        a for entry in lead if entry is not None
        for a in (entry if isinstance(entry, tuple) else (entry,)))

    def local(xl, wl, bll, auxl, dyl):
        idx, oidx, oslot, ovalid = _local_pattern(spat, axis)
        if backend == "pallas":
            bm = _bwd_block_m(block_m, xl.shape[-2])
            dxl = csd_spmm.csd_spmm_dx(
                dyl, wl, oidx, oslot, out_valid=ovalid, aux=auxl,
                activation=activation, block_m=bm,
                interpret=interpret)
            if has_bias:
                dwl, dbl = csd_spmm.csd_spmm_dw(
                    xl, dyl, idx, block_in=bl_, block_out=br_, aux=auxl,
                    activation=activation, want_db=True, block_m=bm,
                    interpret=interpret)
            else:
                dwl = csd_spmm.csd_spmm_dw(
                    xl, dyl, idx, block_in=bl_, block_out=br_, aux=auxl,
                    activation=activation, block_m=bm,
                    interpret=interpret)
                dbl = jnp.zeros((0,), jnp.float32)
        else:
            dym = _mask_dy_xla(dyl, auxl, activation)
            if batched:
                dxl = jax.vmap(lambda de, we: _xla_dx(
                    de, we, oidx, oslot, ovalid))(dym, wl)
                dwl = jax.vmap(lambda xe, de: _xla_dw(
                    xe, de, idx, bl_, br_))(xl, dym)
            else:
                dxl = _xla_dx(dym, wl, oidx, oslot, ovalid)
                dwl = _xla_dw(xl, dym, idx, bl_, br_)
            if has_bias:
                axes = tuple(range(1 if batched else 0, dym.ndim - 1))
                dbl = jnp.sum(dym.astype(jnp.float32), axis=axes)
            else:
                dbl = jnp.zeros((0,), jnp.float32)
        # BP assembles the full input cotangent: every shard's output rows
        # pull on the whole input, so the partials all-reduce over `axis`
        dx = jax.lax.psum(dxl, axis)
        if lead_axes:
            dwl = jax.lax.psum(dwl, lead_axes)
            dbl = jax.lax.psum(dbl, lead_axes)
        return dx, dwl, dbl

    dx_spec = P(*lead, None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, w_spec, b_spec, y_spec, y_spec),
        out_specs=(dx_spec, w_spec, b_spec), check_vma=False)
    aux_arr = aux if activation is not None else dy
    dx, dw, db = fn(x, w, b, aux_arr, dy)
    return dx, dw.astype(w.dtype), db.astype(b.dtype)


_csd_matmul_spmd.defvjp(_spmd_fwd_vjp, _spmd_bwd_vjp)


def _csd_matmul_sharded(x, w, pattern, bias, activation, backend, block_m,
                        interpret, mesh, axis, lead_spec):
    """Entry for the sharded path: validate the partition, normalize the
    lead spec, pad M for the Pallas layout, run the SPMD custom-VJP."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}")
    k = int(mesh.shape[axis])
    # partition_pattern guarantees a contiguous split (fixed-degree is
    # structural for BlockPattern), so the global slab's NamedSharding
    # row chunks are exactly the per-device slabs this path assumes
    part = get_partition(pattern, k)
    spat = _ShardPat(part)
    batched = w.ndim == 5
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((0,), x.dtype)
    if backend == "pallas":
        xf, m = _pad_rows(x, batched, block_m)
        lead = (None,) * (xf.ndim - 1)
        y = _csd_matmul_spmd(xf, w, b, spat, has_bias, activation, backend,
                             block_m, interpret, mesh, axis, lead)
        return _unpad_rows(y, x, m)
    if lead_spec is None:
        lead = (None,) * (x.ndim - 1)
    else:
        lead = tuple(lead_spec)
        if len(lead) != x.ndim - 1:
            raise ValueError(
                f"lead_spec {lead_spec} must cover the {x.ndim - 1} "
                f"leading dims of x {x.shape}")
    return _csd_matmul_spmd(x, w, b, spat, has_bias, activation, backend,
                            block_m, interpret, mesh, axis, lead)


# ---------------------------------------------------------------------------
# Quantized (int8-weight) forward — inference only, no VJP. The slab stays
# int8 all the way into the kernel / per-slot einsum; per-block f32 scales
# ride alongside (sharded with the same row chunking as the slab, so the
# serving engine's model-parallel path works unchanged).
# ---------------------------------------------------------------------------


def _quant_matmul(x, w, w_scale, pat, bias, activation, backend, dataflow,
                  block_m, interpret):
    batched = w.ndim == 5
    has_bias = bias is not None
    if backend == "pallas":
        xf, m = _pad_rows(x, batched, block_m)
        y = csd_spmm.csd_spmm_fwd(
            xf, w, pat.block_idx, bias=bias, activation=activation,
            block_m=block_m, interpret=interpret, w_scale=w_scale)
        return _unpad_rows(y, x, m)
    if batched:
        z = _xla_fwd_quant_batched(x, w, w_scale, pat, dataflow)
    elif dataflow == "scatter":
        z = _xla_fwd_scatter_quant(x, w, w_scale, pat.out_idx,
                                   pat.out_slot, pat.out_valid)
    else:
        z = _xla_fwd_quant(x, w, w_scale, pat.block_idx)
    if has_bias:
        bb = bias
        if batched:
            bb = bias.reshape((bias.shape[0],) + (1,) * (z.ndim - 2)
                              + bias.shape[1:])
        z = z + bb.astype(z.dtype)
    return csd_spmm.apply_activation(z, activation)


def _quant_matmul_sharded(x, w, w_scale, pattern, bias, activation, backend,
                          block_m, interpret, mesh, axis, lead_spec):
    """Sharded quantized forward: the scale array is row-chunked with the
    same contiguous split as the slab (``P(axis, None)`` for the 2-D
    scales, ``P(None, axis, None)`` batched), so each device's local
    scales line up with its local pattern rows."""
    from jax.sharding import PartitionSpec as P
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh has no axis {axis!r}")
    k = int(mesh.shape[axis])
    part = get_partition(pattern, k)
    spat = _ShardPat(part)
    batched = w.ndim == 5
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((0,), x.dtype)
    s_spec = P(None, axis, None) if batched else P(axis, None)

    def run(xf, lead):
        x_spec, w_spec, b_spec, y_spec = _shard_specs(
            batched, has_bias, lead, axis)

        def local(xl, wl, sl, bl):
            idx, _, _, _ = _local_pattern(spat, axis)
            bias_l = bl if has_bias else None
            if backend == "pallas":
                return csd_spmm.csd_spmm_fwd(
                    xl, wl, idx, bias=bias_l, activation=activation,
                    block_m=block_m, interpret=interpret, w_scale=sl)
            if batched:
                z = jax.vmap(lambda xe, we, se: _xla_fwd_quant(
                    xe, we, se, idx))(xl, wl, sl)
            else:
                z = _xla_fwd_quant(xl, wl, sl, idx)
            if has_bias:
                bb = bl
                if batched:
                    bb = bl.reshape((bl.shape[0],) + (1,) * (z.ndim - 2)
                                    + bl.shape[1:])
                z = z + bb.astype(z.dtype)
            return csd_spmm.apply_activation(z, activation)

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(x_spec, w_spec, s_spec, b_spec),
                           out_specs=y_spec, check_vma=False)
        return fn(xf, w, w_scale, b)

    if backend == "pallas":
        xf, m = _pad_rows(x, batched, block_m)
        y = run(xf, (None,) * (xf.ndim - 1))
        return _unpad_rows(y, x, m)
    if lead_spec is None:
        lead = (None,) * (x.ndim - 1)
    else:
        lead = tuple(lead_spec)
        if len(lead) != x.ndim - 1:
            raise ValueError(
                f"lead_spec {lead_spec} must cover the {x.ndim - 1} "
                f"leading dims of x {x.shape}")
    return run(x, lead)


def csd_matmul(
    x: jax.Array,
    w: jax.Array,
    pattern: BlockPattern,
    *,
    bias: Optional[jax.Array] = None,
    activation: Optional[str] = None,
    backend: str = "auto",
    dataflow: str = "gather",
    block_m: Optional[int] = None,
    interpret: bool = False,
    mesh=None,
    axis: Optional[str] = None,
    lead_spec=None,
    w_scale: Optional[jax.Array] = None,
) -> jax.Array:
    """Differentiable block-sparse junction: (..., n_in) -> (..., n_out),
    computing ``activation(x @ W_sparse + bias)`` with the epilogue fused
    into the matmul (see module docstring).

    Batched (expert-major) form: ``w`` of shape ``(E, n_rb, d_in_b, bL,
    bR)`` with ``x`` ``(E, ..., n_in)`` and ``bias`` ``(E, n_out)`` runs
    all ``E`` expert junctions over one shared pattern and returns
    ``(E, ..., n_out)`` (see module docstring).

    ``backend`` is ``"auto" | "pallas" | "xla" | "dense"``. ``"auto"`` is
    *measured*: the ``repro.tune`` dispatch cache is consulted at trace
    time and the benchmarked winner for this call's regime runs (miss or
    ``REPRO_TUNE_DISABLE=1`` -> the static heuristic). ``"dense"`` is the
    escape hatch: densify the slab and run one GEMM — same math, grads
    only at pattern blocks; plain/batched unquantized junctions only.

    ``activation`` is ``None | "relu" | "gelu"`` (gelu = tanh approximation,
    matching the model stack's activation registry). Leading dims are
    flattened to M (per expert in the batched form) and padded for the
    Pallas path: to ``block_m`` when it is given, else to the rows the
    forward derives its row block from (``csd_spmm.fwd_tiling``); the XLA
    path keeps leading dims intact so GSPMD preserves their sharding. The
    pattern is compile-time static.

    Sharded (model-parallel) form: pass ``mesh`` and ``axis`` (a mesh axis
    name) to partition the pattern and slab over ``mesh.shape[axis]``
    devices — each device runs its shard-local pattern under ``shard_map``
    (FF column-parallel, BP psum'd, UP shard-local; see the sharded-section
    comment). ``w``/``bias`` keep their logical layouts, row-sharded on the
    block-row / feature dim; ``lead_spec`` optionally names the mesh axes
    of ``x``'s leading dims (XLA path) so their sharding survives entry.
    Requires ``n_rb % mesh.shape[axis] == 0`` (see ``can_partition``).

    ``mesh`` without ``axis`` (a mesh is installed but this junction
    cannot shard over it) runs the Pallas kernel whole on every device
    (``run_replicated``); the XLA path leaves placement to GSPMD.

    Quantized form (inference only, no VJP): pass ``w`` as int8 with
    ``w_scale`` per-block f32 scales ``(n_rb, d_in_b)`` (batched:
    ``(E, n_rb, d_in_b)``) from ``core.quant.quantize_slab`` — the slab
    stays int8 into the kernel / per-slot einsum and dequantization is
    folded into the accumulate before the fused epilogue. Composes with
    the sharded form (scales row-chunk with the slab).
    """
    if activation is not None and activation not in csd_spmm.ACTIVATIONS:
        raise ValueError(f"unsupported fused activation {activation!r}")
    if dataflow not in ("gather", "scatter"):
        raise ValueError(f"unknown dataflow {dataflow!r}")
    batched = w.ndim == 5
    if batched and (x.ndim < 2 or x.shape[0] != w.shape[0]):
        raise ValueError(
            f"batched junction: x leading dim {x.shape} must match expert "
            f"count E={w.shape[0]}")
    if backend not in ("auto", "pallas", "xla", "dense"):
        raise ValueError(f"unknown backend {backend!r}")
    sharded = mesh is not None and axis is not None
    quant = w_scale is not None
    if quant:
        form = ("quant_sharded_batched" if batched else "quant_sharded") \
            if sharded else ("quant_batched" if batched else "quant")
    elif sharded:
        form = "sharded_batched" if batched else "sharded"
    else:
        form = "batched" if batched else "plain"
    if backend == "auto":
        # measured-auto (PR 10): consult the tune cache at trace time and
        # dispatch the benchmarked winner for this regime; a miss (or
        # REPRO_TUNE_DISABLE=1) falls back to the static heuristic below.
        # Sharded forms key on the shard-local output width — the tuning
        # decision follows partition_pattern's per-device shapes.
        from .. import tune
        k = int(mesh.shape[axis]) if sharded else 1
        lead = x.shape[1:-1] if batched else x.shape[:-1]
        m = 1
        for d in lead:
            m *= int(d)
        ent = tune.decide_junction(
            m=m, n_in=pattern.n_in, n_out=pattern.n_out // k,
            rho=pattern.density, E=w.shape[0] if batched else 0,
            dtype=str(x.dtype), quant=quant, form=form,
            block_in=pattern.block_in, block_out=pattern.block_out)
        if ent is not None:
            backend = str(ent["backend"])
            dataflow = str(ent.get("dataflow", dataflow))
            if ent.get("block_m") is not None:
                block_m = int(ent["block_m"])
        else:
            backend = _resolve(backend)
    if backend == "dense" and (quant or sharded):
        raise ValueError("backend='dense' supports only the plain/batched "
                         "unquantized junction")
    if mesh is not None and not sharded and backend == "pallas":
        return run_replicated(
            lambda x, w, b, s: csd_matmul(
                x, w, pattern, bias=b, activation=activation,
                backend=backend, dataflow=dataflow, block_m=block_m,
                interpret=interpret, w_scale=s),
            mesh, x, w, bias, w_scale)
    _count_dispatch("interpret" if backend == "pallas" and interpret
                    else backend, form)
    if quant:
        if w.dtype != jnp.int8:
            raise ValueError(
                f"w_scale given but w.dtype={w.dtype}, expected int8")
        if sharded:
            return _quant_matmul_sharded(
                x, w, w_scale, pattern, bias, activation, backend, block_m,
                interpret, mesh, axis, lead_spec)
        return _quant_matmul(x, w, w_scale, _Pat(pattern), bias,
                             activation, backend, dataflow, block_m,
                             interpret)
    if sharded:
        return _csd_matmul_sharded(x, w, pattern, bias, activation,
                                   backend, block_m, interpret, mesh, axis,
                                   lead_spec)
    pat = _Pat(pattern)
    has_bias = bias is not None
    b = bias if has_bias else jnp.zeros((0,), x.dtype)
    if backend == "pallas":
        xf, m = _pad_rows(x, batched, block_m)
        y = _csd_matmul(xf, w, b, pat, has_bias, activation, backend,
                        dataflow, block_m, interpret)
        return _unpad_rows(y, x, m)
    # xla: leading dims flow through untouched (sharding preserved)
    return _csd_matmul(x, w, b, pat, has_bias, activation, backend,
                       dataflow, block_m, interpret)
