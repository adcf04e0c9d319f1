"""Pass 1 — grid/race analysis of the shipped Pallas kernels.

Symbolically enumerates every grid point of a captured launch (see
``capture.py``) and proves, per kernel:

* **SL101 (races)** — the set of grid steps writing one output tile must be
  a *contiguous run* in the TPU's sequential grid order. Pallas keeps an
  output block resident in VMEM only across consecutive steps that map to
  the same block; a non-consecutive revisit means the tile was flushed and
  the revisit clobbers (not accumulates) — the silent-wrong-gradient class.
  This is the block-level form of the paper's clash-freedom proof: the FPGA
  flow statically checks no two parallel lanes hit one memory bank, we
  check no two non-adjacent grid steps hit one VMEM tile.
* **SL102/SL105 (shape safety)** — every BlockSpec's block shape divides
  the bound array dim (entry points pad M before launching; the check sees
  post-pad operand shapes, so an unpadded path fails loudly here), and
  every evaluated index map stays inside the array. Out-of-range pattern
  entries (a corrupt ``block_idx``) surface as SL105.
* **SL103 (epilogue)** — kernels that fuse bias/activation on the *last
  fan-in slot* declare their epilogue grid axis; the pass proves each
  output tile's final visit carries ``idx[axis] == size-1`` and that the
  tile is visited exactly ``size`` times — the "epilogue fires once, last"
  contract the fused-VJP relies on.
* **SL104 (VMEM budget)** — per-step working set: double-buffered in/out
  blocks plus scratch must fit the configured budget (default half of the
  ~16 MiB/core TPU VMEM, leaving headroom for Mosaic's own allocations).

Also emits a ``pl.CostEstimate``-style report per kernel: grid size, HBM
bytes actually streamed (consecutive same-block steps stream nothing — the
quantity the accumulation ordering optimizes), and the per-step VMEM high
water mark.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels.vmem import VMEM_BUDGET
from .capture import CapturedLaunch, capture_launch
from .findings import Finding

# Default per-core VMEM budget for SL104: the one the kernels tile to.
DEFAULT_VMEM_BUDGET = VMEM_BUDGET


@dataclasses.dataclass
class KernelCase:
    """One registry entry: how to capture a kernel and what it promises."""

    name: str
    build: Callable[[], CapturedLaunch]
    # grid axis whose last index fires the fused epilogue (None = no fused
    # epilogue contract to check)
    epilogue_axis: Optional[int] = None
    # output indices the epilogue contract applies to (default: all)
    epilogue_outputs: Optional[Tuple[int, ...]] = None


def _spec_block_shape(spec) -> Optional[Tuple[int, ...]]:
    """Block shape as ints; a squeezed dim (``pl.squeezed``) is a
    one-element block of that dim."""
    bs = getattr(spec, "block_shape", None)
    if bs is None:
        return None
    return tuple(b if isinstance(b, int) else 1 for b in bs)


def analyze_launch(launch: CapturedLaunch, case: KernelCase,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET
                   ) -> Tuple[List[Finding], dict]:
    """Run all grid-pass checks on one captured launch."""
    findings: List[Finding] = []
    subject = case.name
    grid = launch.grid
    specs = (
        [("in", i, s, launch.in_shapes[i])
         for i, s in enumerate(launch.in_specs)]
        + [("out", i, s, launch.out_shapes[i])
           for i, s in enumerate(launch.out_specs)])

    # -- SL102: block divisibility + SL104 static VMEM accounting ---------
    vmem_bytes = 0
    for kind, i, spec, (shape, dtype) in specs:
        bs = _spec_block_shape(spec)
        if bs is None:
            continue
        if len(bs) != len(shape):
            findings.append(Finding(
                "SL102", subject,
                f"{kind}[{i}] block rank {len(bs)} != array rank "
                f"{len(shape)}", {"block": bs, "shape": shape}))
            continue
        for d, (dim, blk) in enumerate(zip(shape, bs)):
            if blk <= 0 or dim % blk:
                findings.append(Finding(
                    "SL102", subject,
                    f"{kind}[{i}] block dim {d}: {blk} does not divide "
                    f"array dim {dim} (implicit pad is not masked)",
                    {"block": bs, "shape": shape}))
        # in/out blocks are double-buffered by the Pallas pipeline
        vmem_bytes += 2 * int(np.prod(bs)) * np.dtype(dtype).itemsize
    for shape, dtype in launch.scratch_shapes:
        vmem_bytes += int(np.prod(shape)) * np.dtype(dtype).itemsize
    if vmem_bytes > vmem_budget:
        findings.append(Finding(
            "SL104", subject,
            f"per-step VMEM working set {vmem_bytes} B exceeds budget "
            f"{vmem_budget} B",
            {"vmem_bytes": vmem_bytes, "budget": vmem_budget}))

    # enumerate the grid once; evaluate every index map at every point
    steps = list(np.ndindex(*grid)) if grid else [()]
    visits: List[dict] = [dict() for _ in launch.out_specs]
    streamed = {f"{kind}{i}": 0 for kind, i, _, _ in specs}
    prev_block = {}
    bad_maps = set()
    for lin, step in enumerate(steps):
        for kind, i, spec, (shape, dtype) in specs:
            bs = _spec_block_shape(spec)
            if bs is None or (kind, i) in bad_maps:
                continue
            try:
                coords = launch.eval_index_map(spec, step)
            except Exception as e:  # index map itself is broken
                findings.append(Finding(
                    "SL105", subject,
                    f"{kind}[{i}] index map failed at grid point "
                    f"{step}: {e}", {}))
                bad_maps.add((kind, i))
                continue
            if len(coords) != len(bs):
                findings.append(Finding(
                    "SL105", subject,
                    f"{kind}[{i}] index map returned {len(coords)} coords "
                    f"for rank-{len(bs)} block", {"coords": coords}))
                bad_maps.add((kind, i))
                continue
            oob = [d for d, (c, blk, dim) in
                   enumerate(zip(coords, bs, shape))
                   if c < 0 or (c * blk + blk) > dim + (blk - dim % blk) % blk]
            if oob:
                findings.append(Finding(
                    "SL105", subject,
                    f"{kind}[{i}] block {coords} out of range for shape "
                    f"{shape} at grid point {step}",
                    {"dims": oob, "block": bs}))
                bad_maps.add((kind, i))
                continue
            key = f"{kind}{i}"
            if prev_block.get(key) != coords:
                streamed[key] += int(np.prod(bs)) * np.dtype(dtype).itemsize
                prev_block[key] = coords
            if kind == "out":
                visits[i].setdefault(coords, []).append(lin)

    # -- SL101: contiguous-visit (race) check -----------------------------
    for i, vmap in enumerate(visits):
        for coords, lins in vmap.items():
            if lins[-1] - lins[0] + 1 != len(lins):
                findings.append(Finding(
                    "SL101", subject,
                    f"out[{i}] tile {coords} written at non-consecutive "
                    f"grid steps {lins[0]}..{lins[-1]} ({len(lins)} "
                    f"visits): the tile leaves VMEM between visits and "
                    f"the revisit clobbers the partial sum",
                    {"tile": coords, "first": lins[0], "last": lins[-1],
                     "visits": len(lins)}))

    # -- SL103: epilogue-on-last-fan-in-slot ------------------------------
    if case.epilogue_axis is not None and not any(
            f.code in ("SL101", "SL105") for f in findings):
        ax = case.epilogue_axis
        n_ax = grid[ax]
        outs = case.epilogue_outputs or tuple(range(len(launch.out_specs)))
        for i in outs:
            for coords, lins in visits[i].items():
                last_step = steps[lins[-1]]
                if last_step[ax] != n_ax - 1:
                    findings.append(Finding(
                        "SL103", subject,
                        f"out[{i}] tile {coords}: final visit has "
                        f"grid[{ax}]={last_step[ax]}, epilogue (fires at "
                        f"{n_ax - 1}) would be skipped or non-final",
                        {"tile": coords, "last_step": last_step}))
                elif len(lins) != n_ax:
                    findings.append(Finding(
                        "SL103", subject,
                        f"out[{i}] tile {coords} visited {len(lins)} "
                        f"times, expected one visit per fan-in slot "
                        f"({n_ax})", {"tile": coords}))

    cost = {
        "grid": tuple(grid),
        "steps": len(steps),
        "vmem_bytes_per_step": vmem_bytes,
        "hbm_bytes_streamed": sum(streamed.values()),
        "hbm_bytes_naive": sum(
            len(steps) * int(np.prod(_spec_block_shape(s)))
            * np.dtype(dt).itemsize
            for _, _, s, (_, dt) in specs
            if _spec_block_shape(s) is not None),
    }
    return findings, cost


# ---------------------------------------------------------------------------
# Kernel case registry: every shipped Pallas kernel family, captured with
# representative shapes (the production block aspect, small counts — the
# checks are per-block-structure, so small grids prove the same invariants
# the production grids rely on).
# ---------------------------------------------------------------------------


def _demo_pattern(block_in=128, block_out=128, n_lb=4, n_rb=4, rho=0.5,
                  seed=0):
    from ..core.block_pattern import make_block_pattern
    return make_block_pattern(
        n_lb * block_in, n_rb * block_out, rho,
        block_in=block_in, block_out=block_out, seed=seed)


def _shard_pattern():
    from ..core.block_pattern import partition_pattern
    bp = _demo_pattern()
    return partition_pattern(bp, 2).shards[0]


def _fwd_case(batched: bool, activation: Optional[str], name: str,
              save_preact: bool = False, pattern=None, m: int = 256,
              dtype: str = "float32", block_m: Optional[int] = 128,
              fan_in_block: Optional[int] = 2) -> KernelCase:
    """A forward launch; the fan-in chunk axis (3) fires the epilogue.
    By default two row blocks of a fan-in of 4 in chunks of 2 slots, so
    each output tile is visited twice; ``block_m`` and ``fan_in_block``
    None derive the tiling as production does (``csd_spmm.fwd_tiling``)."""
    def build():
        import jax.numpy as jnp
        from ..kernels import csd_spmm
        bp = pattern() if pattern is not None else _demo_pattern(n_lb=8)
        lead = (2,) if batched else ()
        x = jnp.zeros(lead + (m, bp.n_in), dtype)
        w = jnp.zeros(
            lead + (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out), dtype)
        bias = jnp.zeros(lead + (bp.n_out,), dtype)
        return capture_launch(
            csd_spmm.csd_spmm_fwd, x, w, bp.block_idx, bias=bias,
            activation=activation, save_preact=save_preact,
            block_m=block_m, fan_in_block=fan_in_block, name=name)
    return KernelCase(name, build, epilogue_axis=3)


def _qwen_down_pattern():
    """qwen2_7b's down junction's fan-in (d_in_b 111: 148 left blocks at
    rho 0.75) over four right blocks: the widest forward step."""
    return _demo_pattern(n_lb=148, n_rb=4, rho=0.75)


def _dx_case(batched: bool, name: str, shard_local: bool = False
             ) -> KernelCase:
    def build():
        import jax.numpy as jnp
        from ..kernels import csd_spmm
        bp = _shard_pattern() if shard_local else _demo_pattern()
        m, bm = 256, 128
        dy = jnp.zeros(((2,) if batched else ()) + (m, bp.n_out),
                       jnp.float32)
        w = jnp.zeros(
            ((2,) if batched else ())
            + (bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out), jnp.float32)
        return capture_launch(
            csd_spmm.csd_spmm_dx, dy, w, bp.out_idx, bp.out_slot,
            out_valid=bp.out_valid, aux=dy, activation="relu", block_m=bm,
            name=name)
    return KernelCase(name, build)


def _dw_case(batched: bool, name: str) -> KernelCase:
    def build():
        import jax.numpy as jnp
        from ..kernels import csd_spmm
        bp = _demo_pattern()
        m, bm = 256, 128
        x = jnp.zeros(((2,) if batched else ()) + (m, bp.n_in), jnp.float32)
        dy = jnp.zeros(((2,) if batched else ()) + (m, bp.n_out),
                       jnp.float32)
        return capture_launch(
            csd_spmm.csd_spmm_dw, x, dy, bp.block_idx,
            block_in=bp.block_in, block_out=bp.block_out, aux=dy,
            activation="relu", want_db=True, block_m=bm, name=name)
    return KernelCase(name, build)


def _flash_case() -> KernelCase:
    def build():
        import jax.numpy as jnp
        from ..kernels.flash_attention import flash_attention
        q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
        k = jnp.zeros((2, 256, 2, 64), jnp.bfloat16)
        return capture_launch(
            flash_attention, q, k, k, causal=True, window=128,
            name="flash_attention_fwd")
    return KernelCase("flash_attention_fwd", build, epilogue_axis=3)


def _paged_decode_case() -> KernelCase:
    def build():
        import jax.numpy as jnp
        import numpy as _np
        from ..kernels.flash_attention import _paged_decode_pallas
        # the production page (16 tokens, bf16); 37 pages take the derived
        # 32 a step and a 5-page tail block. Row 0 ends in block 0 (block
        # 1 repeats its pages), row 1 fills the table
        b, hkv, g, dh, page, npg, pool = 2, 2, 2, 128, 16, 37, 40
        q = jnp.zeros((b, hkv, g, dh), jnp.bfloat16)
        kp = jnp.zeros((pool, page, hkv, dh), jnp.bfloat16)
        table = _np.full((b, npg), -1, _np.int32)
        table[0, :2] = [1, 4]
        table[1] = _np.arange(2, 2 + npg)
        lengths = _np.array([19, npg * page], _np.int32)
        return capture_launch(
            _paged_decode_pallas, q, kp, kp, jnp.asarray(table),
            jnp.asarray(lengths), window=None, softcap=None, scale=1.0,
            interpret=True, name="paged_decode_attention")
    # the online-softmax finalize fires on the last page block of each row
    return KernelCase("paged_decode_attention", build, epilogue_axis=1)


def kernel_cases() -> List[KernelCase]:
    """Every shipped Pallas kernel family (ISSUE 6 pass-1 scope)."""
    return [
        _fwd_case(False, "relu", "csd_spmm_fwd_4d_relu"),
        _fwd_case(False, "gelu", "csd_spmm_fwd_4d_gelu_preact",
                  save_preact=True),
        _fwd_case(False, None, "csd_spmm_fwd_4d_plain"),
        _fwd_case(True, "relu", "csd_spmm_fwd_5d_batched"),
        # qwen2_7b down's fan-in in bf16 at the derived tiling: decode's
        # rows take the whole fan-in in one step (the widest step),
        # prefill's three chunks
        _fwd_case(False, None, "csd_spmm_fwd_4d_fanin111_decode",
                  pattern=_qwen_down_pattern, m=16, dtype="bfloat16",
                  block_m=None, fan_in_block=None),
        _fwd_case(False, None, "csd_spmm_fwd_4d_fanin111_prefill",
                  pattern=_qwen_down_pattern, m=512, dtype="bfloat16",
                  block_m=None, fan_in_block=None),
        _dx_case(False, "csd_spmm_dx_4d"),
        _dx_case(False, "csd_spmm_dx_4d_shardlocal", shard_local=True),
        _dx_case(True, "csd_spmm_dx_5d_batched"),
        _dw_case(False, "csd_spmm_dw_4d_db"),
        _dw_case(True, "csd_spmm_dw_5d_batched"),
        _flash_case(),
        _paged_decode_case(),
    ]


# ---------------------------------------------------------------------------
# Self-test injection: a deliberately broken copy of csd_spmm_fwd with the
# accumulation (fan-in) dimension hoisted OUTERMOST — every output tile is
# then revisited non-consecutively, the exact race SL101 certifies against.
# Used by `lint --selftest-inject` and the linter's own test suite to prove
# the pass catches the bug class, never by production code.
# ---------------------------------------------------------------------------


def _aliased_fwd_copy(x, w, block_idx, *, block_m=128):
    import functools
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl  # noqa: F811 — patched copy
    from jax.experimental.pallas import tpu as pltpu
    from ..kernels.csd_spmm import _fwd_kernel
    m, n_in = x.shape
    n_rb, d_in_b, bl, br = w.shape
    k = 1  # one fan-in slot a chunk
    grid = (d_in_b, 1, m // block_m, n_rb)  # BUG: fan-in chunk outermost
    kernel = functools.partial(_fwd_kernel, k=k, d_in_b=d_in_b, quant=False,
                               activation=None, has_bias=False,
                               save_preact=False)
    x_specs = [pl.BlockSpec((1, block_m, bl),
                            lambda f, e, i, r, idx, j=j:
                            (e, i, idx[r, f * k + j]))
               for j in range(k)]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=x_specs + [
                pl.BlockSpec((1, 1, k, bl, br),
                             lambda f, e, i, r, idx: (e, r, f, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, block_m, br),
                                   lambda f, e, i, r, idx: (e, i, r)),
        ),
        out_shape=jax.ShapeDtypeStruct((1, m, n_rb * br), jnp.float32),
        interpret=True,
    )(jnp.asarray(block_idx), *[x[None]] * k, w[None])
    return out[0]


def injected_alias_case() -> KernelCase:
    def build():
        import jax.numpy as jnp
        bp = _demo_pattern()
        x = jnp.zeros((256, bp.n_in), jnp.float32)
        w = jnp.zeros((bp.n_rb, bp.d_in_b, bp.block_in, bp.block_out),
                      jnp.float32)
        return capture_launch(_aliased_fwd_copy, x, w, bp.block_idx,
                              name="csd_spmm_fwd_injected_alias")
    return KernelCase("csd_spmm_fwd_injected_alias", build, epilogue_axis=0)


def run(vmem_budget: int = DEFAULT_VMEM_BUDGET,
        cases: Optional[Sequence[KernelCase]] = None,
        inject: bool = False) -> Tuple[List[Finding], dict, List[str]]:
    """Run the grid pass over the kernel registry.

    Returns (findings, cost-by-kernel, covered subjects).
    """
    findings: List[Finding] = []
    cost = {}
    covered = []
    cs = list(cases) if cases is not None else kernel_cases()
    if inject:
        cs.append(injected_alias_case())
    for case in cs:
        try:
            launch = case.build()
        except Exception as e:
            findings.append(Finding(
                "SL105", case.name,
                f"kernel capture failed: {type(e).__name__}: {e}", {}))
            continue
        f, c = analyze_launch(launch, case, vmem_budget)
        findings.extend(f)
        cost[case.name] = c
        covered.append(case.name)
    return findings, cost, covered
