"""Legality gate for tuned Pallas candidates — sparselint pass 1, pre-bench.

Every Pallas candidate configuration the tuner wants to benchmark is first
captured (``analysis.capture.capture_launch`` — records the launch without
executing it) and proven against the grid pass's SL101–SL105 checks
(``analysis.grid_pass.analyze_launch``): contiguous output-tile visits (no
VMEM race), BlockSpec divisibility, epilogue-on-last-slot, VMEM budget,
index-map range. An illegal candidate is *rejected before it is ever
benchmarked or cached* — a config that happens to run fast in interpret
mode but races on real hardware must never become a cached winner.

``certify_injected()`` is the self-test hook: it presents sparselint's
deliberately race-broken kernel copy (fan-in slot hoisted outermost) as if
it were a tuned candidate; the gate must reject it. ``python -m repro.tune
--selftest-inject`` exits non-zero exactly when the rejection fires, the
same has-teeth contract as ``lint --selftest-inject``.
"""
from __future__ import annotations

from typing import List, Optional, Tuple


def certify_junction(bp, m: int, block_m: Optional[int], *, E: int = 0,
                     activation: str = "relu",
                     dtype=None) -> Tuple[bool, List]:
    """Certify one Pallas ``csd_spmm_fwd`` candidate (SL101–SL105).

    Returns ``(ok, findings)``. ``m`` is the logical row count; the entry
    point pads M to ``block_m`` (to ``csd_spmm.fwd_rows`` when it is None,
    the derived tiling), so the capture sees post-pad shapes — exactly
    what the grid pass certifies against.
    """
    import jax.numpy as jnp

    from ..analysis import grid_pass
    from ..analysis.capture import capture_launch
    from ..analysis.findings import Finding
    from ..kernels import csd_spmm

    batched = E > 0
    dt = jnp.float32 if dtype is None else dtype
    mp = m + (-m) % block_m if block_m else csd_spmm.fwd_rows(m, dt)
    name = f"tune:csd_spmm_fwd_bm{block_m or 'derived'}" \
        + ("_5d" if batched else "")

    def build():
        lead = (E,) if batched else ()
        x = jnp.zeros(lead + (mp, bp.n_in), dt)
        w = jnp.zeros(lead + (bp.n_rb, bp.d_in_b, bp.block_in,
                              bp.block_out), dt)
        bias = jnp.zeros(lead + (bp.n_out,), dt)
        return capture_launch(
            csd_spmm.csd_spmm_fwd, x, w, bp.block_idx, bias=bias,
            activation=activation, block_m=block_m, name=name)

    case = grid_pass.KernelCase(name, build, epilogue_axis=3)
    try:
        launch = case.build()
    except Exception as e:  # unlaunchable config = rejected, not fatal
        return False, [Finding(
            "SL105", name,
            f"candidate capture failed: {type(e).__name__}: {e}", {})]
    findings, _ = grid_pass.analyze_launch(launch, case)
    return (not findings), findings


def certify_injected() -> Tuple[bool, List]:
    """Present the race-broken selftest kernel as a tuned candidate.

    Returns ``(ok, findings)`` — ``ok`` must come back ``False`` (the gate
    rejected it) for the selftest to pass.
    """
    from ..analysis import grid_pass

    case = grid_pass.injected_alias_case()
    launch = case.build()
    findings, _ = grid_pass.analyze_launch(launch, case)
    return (not findings), findings
