"""Flash attention (Pallas/TPU) — forward kernel for the serving hot path.

Supports the features the assigned architectures need: causal masking, GQA
(kv-head grouping via the index map), sliding-window attention (gemma2/3
local layers), logit soft-capping (gemma2), and a ``q_offset`` for decode
(query positions offset against an existing KV cache).

Online-softmax over KV blocks (the standard flash recurrence): running
row-max ``m``, normalizer ``l`` and f32 accumulator live in VMEM scratch
(TPU-shaped: trailing dim 128). Out-of-window KV blocks are masked; on real
hardware the compiler hoists fully-masked blocks' loads are still issued —
the XLA chunked implementation in ``repro.nn.attention`` (used for
GSPMD-partitioned training and the dry-run) skips them structurally instead.

Validated in interpret mode against ``ref.mha_ref`` over shape/dtype sweeps
(``tests/test_kernels.py``).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs import metrics as obs_metrics
from .names import pallas_names
from .vmem import VMEM_BUDGET

_NEG_INF = -1e30
_LANES = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  q_offset: int, kv_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (block_q, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (block_k, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0) \
        + q_offset
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[:, :1]                        # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    # fully-masked rows: zero out (m_new stays -inf; exp(-inf - -inf)=nan)
    p = jnp.where(m_new > _NEG_INF / 2, p, 0.0)
    corr = jnp.where(m_prev > _NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)
    l_new = corr * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)

    v = v_ref[0, 0].astype(jnp.float32)          # (block_k, d)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr + pv
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ki == kv_blocks - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Skv, Hkv, Dh)
    v: jax.Array,  # (B, Skv, Hkv, Dh)
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    scale: Optional[float] = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Flash attention forward. Layout (B, S, H, Dh); returns like ``q``."""
    b, sq, hq, dh = q.shape
    _, skv, hkv, _ = k.shape
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    groups = hq // hkv
    scale = dh ** -0.5 if scale is None else scale
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    if sq % block_q or skv % block_k:
        raise ValueError("sequence lengths must divide block sizes")

    # (B, S, H, D) -> (B, H, S, D) for blocking over seq
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    grid = (b, hq, sq // block_q, skv // block_k)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=logit_softcap, block_q=block_q, block_k=block_k,
        q_offset=q_offset, kv_blocks=skv // block_k)

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh),
                         lambda bb, h, qi, ki: (bb, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bb, h, qi, ki: (bb, h // groups, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dh),
                         lambda bb, h, qi, ki: (bb, h // groups, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh),
                               lambda bb, h, qi, ki: (bb, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
        **pallas_names("flash_attention"),
    )(qt, kt, vt)
    return jnp.swapaxes(out, 1, 2)


# ---------------------------------------------------------------------------
# Paged decode attention (serving): one query token over a paged KV cache
# ---------------------------------------------------------------------------


# A grid step costs a fixed few tenths of a microsecond, against about
# 0.04 us to stream one 16-token page of K and V, so a step reads a block of
# pages (``decode_pages_per_step``), and the blocks a row walks are bound by
# its live range: a block wholly past the row's end (or before its window)
# maps to the nearest live block, so the pipeline issues no copy for it, and
# its compute is skipped.
MAX_BLOCK_TOKENS = 512


def decode_pages_per_step(n_pages: int, page_size: int, n_kv_heads: int,
                          groups: int, head_dim: int, *, q_dtype, kv_dtype,
                          quant: bool) -> int:
    """Pages of K and V one paged-decode grid step reads.

    The most pages whose double-buffered K and V blocks (and int8 scale
    rows), beside the query and output blocks and the softmax scratch,
    fit SL104's VMEM budget, capped at ``MAX_BLOCK_TOKENS`` keys a step;
    the largest divisor of ``n_pages`` under that cap where it is at least
    half the cap, else the cap (the tail block's slots clamp into the
    table)."""
    kv_bytes = page_size * n_kv_heads * head_dim * np.dtype(kv_dtype).itemsize
    per_page = 2 * 2 * (kv_bytes + (page_size * 4 if quant else 0))
    fixed = (2 * 2 * n_kv_heads * groups * head_dim
             * np.dtype(q_dtype).itemsize
             + n_kv_heads * groups * (head_dim + 2 * _LANES) * 4)
    cap = max(1, min(n_pages, MAX_BLOCK_TOKENS // page_size,
                     (VMEM_BUDGET - fixed) // per_page))
    div = max(p for p in range(1, cap + 1) if n_pages % p == 0)
    return div if 2 * div >= cap else cap


def _live_pages(length, *, page_size: int, n_pages: int,
                window: Optional[int]):
    """First and last logical page that hold a key the row attends to
    (page 0 for an empty row)."""
    last = jnp.minimum(jnp.maximum(length - 1, 0) // page_size, n_pages - 1)
    if window is None:
        return 0, last
    first = jnp.maximum(length - window, 0) // page_size
    return jnp.minimum(first, last), last


def _live_block(blk, length, *, ppb: int, page_size: int, n_pages: int,
                window: Optional[int]):
    """Page block ``blk`` clamped to the row's live blocks: a dead block
    becomes the nearest live one."""
    first, last = _live_pages(length, page_size=page_size, n_pages=n_pages,
                              window=window)
    return jnp.minimum(jnp.maximum(blk, first // ppb), last // ppb)


def _block_page(blk, j, length, *, ppb: int, page_size: int, n_pages: int,
                window: Optional[int]):
    """Logical page that slot ``j`` of page block ``blk`` reads: slot ``j``
    of the nearest live block, clamped to the row's live pages (which
    also keeps a tail block's slots inside the table)."""
    first, last = _live_pages(length, page_size=page_size, n_pages=n_pages,
                              window=window)
    live = _live_block(blk, length, ppb=ppb, page_size=page_size,
                       n_pages=n_pages, window=window)
    return jnp.minimum(jnp.maximum(live * ppb + j, first), last)


def _paged_decode_kernel(sp_ref, len_ref, q_ref, *refs, scale: float,
                         window: Optional[int], softcap: Optional[float],
                         page_size: int, ppb: int, n_pages: int,
                         n_blocks: int, n_kv_heads: int,
                         quant: bool = False):
    """One grid step = one block of ``ppb`` pages of one sequence, all KV
    heads at once. Each page's ``(page, Hkv, Dh)`` block keeps the pool's
    full trailing dims (the TPU's block rule refuses a single-head slice of
    them); each head concatenates its slice of the block's pages into one
    ``(ppb * page, Dh)`` operand of one logit and one PV matmul, and keeps
    its online-softmax state in its own row of the scratch. A block
    outside the row's live range computes nothing.

    refs: ``ppb`` K pages, ``ppb`` V pages, [``ppb`` K and ``ppb`` V scale
    rows], the output, the scratch. ``quant`` selects int8 KV pages: the
    per-token scale rows (``(1, page_size)`` rows of the scale buffers,
    selected by the same page-table index map) dequantize in register —
    the key scale multiplies the logits and the value scale the
    probabilities, so both stay in the lane orientation the logits
    already have. Pages stream from HBM at 1 byte/element."""
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    ks_refs, vs_refs = refs[2 * ppb:3 * ppb], refs[3 * ppb:4 * ppb]
    o_ref, acc_ref, m_ref, l_ref = refs[(4 if quant else 2) * ppb:]
    b = pl.program_id(0)
    blk = pl.program_id(1)

    @pl.when(blk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]                            # valid keys: kpos < length
    live = _live_block(blk, length, ppb=ppb, page_size=page_size,
                       n_pages=n_pages, window=window) == blk

    @pl.when(live & (length > 0))
    def _block():
        groups = q_ref.shape[2]
        tokens = ppb * page_size
        kpos = blk * tokens + jax.lax.broadcasted_iota(
            jnp.int32, (groups, tokens), 1)
        # a tail block's slots past the table repeat its last page
        mask = kpos < jnp.minimum(length, n_pages * page_size)
        if window is not None:
            mask &= kpos > (length - 1) - window
        if quant:
            ks = jnp.concatenate([r[...] for r in ks_refs], axis=1)
            vs = jnp.concatenate([r[...] for r in vs_refs], axis=1)

        for h in range(n_kv_heads):
            q = q_ref[0, h].astype(jnp.float32) * scale    # (G, Dh)
            k = jnp.concatenate(                           # (tokens, Dh)
                [r[0, :, h, :].astype(jnp.float32) for r in k_refs], axis=0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quant:
                s = s * ks                                 # (1, tokens)
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(mask, s, _NEG_INF)

            m_prev = m_ref[h][:, :1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            pexp = jnp.exp(s - m_new)
            pexp = jnp.where(m_new > _NEG_INF / 2, pexp, 0.0)
            corr = jnp.where(m_prev > _NEG_INF / 2, jnp.exp(m_prev - m_new),
                             0.0)
            l_new = corr * l_ref[h][:, :1] + jnp.sum(pexp, axis=1,
                                                     keepdims=True)
            if quant:
                pexp = pexp * vs                           # (1, tokens)
            v = jnp.concatenate(
                [r[0, :, h, :].astype(jnp.float32) for r in v_refs], axis=0)
            pv = jax.lax.dot_general(pexp, v, (((1,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            acc_ref[h] = acc_ref[h] * corr + pv
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(blk == n_blocks - 1)
    def _finalize():
        l = l_ref[:, :, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)


def _count_decode(form: str, steps: int, ppb: int) -> None:
    """Trace-time counters of the decode's blocking (like the junction
    forward's): grid steps of each call, and pages a step reads, by form."""
    reg = obs_metrics.get_registry()
    reg.counter(
        "repro_paged_decode_grid_steps_total",
        "paged decode attention grid steps per traced call, by form",
    ).inc(steps, form=form)
    reg.gauge(
        "repro_paged_decode_pages_per_step",
        "KV pages a paged decode attention grid step reads, by form",
    ).set(ppb, form=form)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, lengths, *,
                         window, softcap, scale, interpret,
                         k_scale=None, v_scale=None, pages_per_step=None):
    """Grid ``(B, ceil(n_pages / ppb))``; ``ppb`` comes from the shapes
    (``decode_pages_per_step``) unless given (``pages_per_step``, a seam
    for tests)."""
    b, hkv, g, dh = q.shape
    pool, page_size = k_pages.shape[:2]
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    ppb = pages_per_step or decode_pages_per_step(
        n_pages, page_size, hkv, g, dh, q_dtype=q.dtype,
        kv_dtype=k_pages.dtype, quant=quant)
    n_blocks = -(-n_pages // ppb)
    geo = dict(ppb=ppb, page_size=page_size, n_pages=n_pages, window=window)

    # the physical page each slot of each block reads, clamped to the row's
    # live range, is worked out for the whole table at once and
    # scalar-prefetched: the kernel-side form of the free-list indirection,
    # one load an index map
    logical = _block_page(jnp.arange(n_blocks)[None, :, None],
                          jnp.arange(ppb)[None, None, :],
                          lengths[:, None, None], **geo)
    slot_pages = jnp.maximum(jnp.take_along_axis(
        page_table, logical.reshape(b, n_blocks * ppb), axis=1), 0)

    def page(bb, blk, sp, j):
        return sp[bb, blk * ppb + j]

    kv_specs = [pl.BlockSpec(
        (1, page_size, hkv, dh),
        lambda bb, blk, sp, ln, j=j: (page(bb, blk, sp, j), 0, 0, 0))
        for j in range(ppb)]
    q_spec = pl.BlockSpec((1, hkv, g, dh),
                          lambda bb, blk, sp, ln: (bb, 0, 0, 0))
    in_specs = [q_spec] + kv_specs * 2
    operands = [slot_pages, lengths, q] + [k_pages] * ppb + [v_pages] * ppb
    if quant:
        # per-token scale row of the (P+1, page) buffers, same page id;
        # viewed as (P+1, 1, page) so the one-row tile spans full dims
        sc_specs = [pl.BlockSpec(
            (pl.squeezed, 1, page_size),
            lambda bb, blk, sp, ln, j=j: (page(bb, blk, sp, j), 0, 0))
            for j in range(ppb)]
        in_specs += sc_specs * 2
        operands += ([k_scale.reshape(pool, 1, page_size)] * ppb
                     + [v_scale.reshape(pool, 1, page_size)] * ppb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_blocks),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((hkv, g, dh), jnp.float32),
            pltpu.VMEM((hkv, g, _LANES), jnp.float32),
            pltpu.VMEM((hkv, g, _LANES), jnp.float32),
        ],
    )
    form = "int8" if quant else jnp.dtype(k_pages.dtype).name.replace(
        "bfloat16", "bf16")
    _count_decode(form, b * n_blocks, ppb)
    kernel = functools.partial(
        _paged_decode_kernel, scale=scale, window=window, softcap=softcap,
        page_size=page_size, ppb=ppb, n_pages=n_pages, n_blocks=n_blocks,
        n_kv_heads=hkv, quant=quant)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, dh), q.dtype),
        interpret=interpret,
        **pallas_names("paged_decode_attention"),
    )(*operands)


def _paged_decode_xla(q, k_pages, v_pages, page_table, lengths, *,
                      window, softcap, scale, k_scale=None, v_scale=None):
    """Gather-based fallback: materialize each sequence's logical KV view
    from its page table, then run the standard masked decode einsum.
    With ``k_scale``/``v_scale`` (int8 pages) the gathered view is
    dequantized per token before the einsum."""
    b, hkv, g, dh = q.shape
    page_size = k_pages.shape[1]
    idx = jnp.clip(page_table, 0, k_pages.shape[0] - 1)
    k = k_pages[idx].reshape(b, -1, hkv, dh)     # (B, S, Hkv, Dh)
    v = v_pages[idx].reshape(b, -1, hkv, dh)
    if k_scale is not None:
        ks = k_scale[idx].reshape(b, -1)
        vs = v_scale[idx].reshape(b, -1)
        k = k.astype(jnp.float32) * ks[:, :, None, None]
        v = v.astype(jnp.float32) * vs[:, :, None, None]
    logits = jnp.einsum("bhgd,bkhd->bhgk",
                        q.astype(jnp.float32) * scale,
                        k.astype(jnp.float32))
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    kpos = jnp.arange(k.shape[1])
    mask = kpos[None] < lengths[:, None]         # (B, S)
    if window is not None:
        mask &= kpos[None] > (lengths[:, None] - 1) - window
    logits = jnp.where(mask[:, None, None], logits, _NEG_INF)
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    p = jnp.where(m > _NEG_INF / 2, p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,           # (B, Hkv, G, Dh) — one grouped query token
    k_pages: jax.Array,     # (P, page_size, Hkv, Dh) physical page pool
    v_pages: jax.Array,
    page_table: jax.Array,  # (B, n_pages) int32, -1 = unmapped
    lengths: jax.Array,     # (B,) int32 — valid keys per row (kpos < len)
    *,
    window: Optional[int] = None,
    softcap: Optional[float] = None,
    scale: Optional[float] = None,
    backend: str = "auto",
    interpret: bool = False,
    k_scale: Optional[jax.Array] = None,  # (P, page_size) f32
    v_scale: Optional[jax.Array] = None,
    mesh=None,
) -> jax.Array:
    """Single-token attention over a paged KV cache; returns like ``q``.

    ``backend="auto"`` follows the repo convention: the Pallas kernel on
    TPU (page table scalar-prefetched, a block of pages per grid step,
    online softmax across blocks, the blocks outside each row's live range
    neither fetched nor computed), the gather-based XLA lowering
    elsewhere.
    Unmapped table entries are safe: their logical positions are >= the
    sequence length, so they are masked before the softmax.

    ``k_scale``/``v_scale`` select int8 KV pages (per-token scales from
    ``serving.kv_cache.write_kv_quant``): pages stream at 1 byte/element
    and are dequantized in register / post-gather.

    Under a multi-device ``mesh`` the Pallas kernel runs whole on every
    device (``ops.run_replicated``): XLA cannot partition it.
    """
    from .ops import _resolve, run_replicated
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    lengths = lengths.astype(jnp.int32)
    page_table = page_table.astype(jnp.int32)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("k_scale and v_scale must be given together")
    be = None
    if backend == "auto":
        # measured-auto (PR 10): trace-time consult of the tune cache for
        # this decode regime; a miss keeps the static heuristic. This is
        # the engine's decode-kernel selection — EngineConfig.backend
        # flows here through model.paged_step.
        from .. import tune
        ent = tune.decide_decode(
            b=q.shape[0], h_kv=q.shape[1], groups=q.shape[2],
            head_dim=q.shape[3], page_size=k_pages.shape[1],
            n_pages=page_table.shape[1], pool=k_pages.shape[0],
            quant=k_scale is not None, dtype=str(q.dtype))
        if ent is not None:
            be = str(ent["backend"])
    if be is None:
        be = _resolve(backend)
    # trace-time count, like the junctions' repro_junction_dispatch_total
    obs_metrics.get_registry().counter(
        "repro_decode_dispatch_total",
        "paged_decode_attention dispatches by backend (counted at trace "
        "time)").inc(backend="interpret" if be == "pallas" and interpret
                     else be)
    if be == "pallas":
        def kernel(q, k_pages, v_pages, page_table, lengths, k_scale,
                   v_scale):
            return _paged_decode_pallas(
                q, k_pages, v_pages, page_table, lengths, window=window,
                softcap=softcap, scale=scale, interpret=interpret,
                k_scale=k_scale, v_scale=v_scale)
        args = (q, k_pages, v_pages, page_table, lengths, k_scale, v_scale)
        if mesh is not None:
            return run_replicated(kernel, mesh, *args)
        return kernel(*args)
    return _paged_decode_xla(
        q, k_pages, v_pages, page_table, lengths, window=window,
        softcap=softcap, scale=scale, k_scale=k_scale, v_scale=v_scale)
