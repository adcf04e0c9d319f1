"""sparselint tune pass — SL4xx: audit a persisted autotuner cache.

The tuner's pre-bench gate (``repro.tune.certify``) proves SL101–SL105
for every Pallas candidate *before* it is ever measured, so an illegal
configuration cannot be cached by this repo's tuner. This pass closes
the remaining hole: a cache file is plain JSON on disk — hand-edited,
copied from another checkout, or written by a future buggy tuner — so
CI re-audits whatever file the run will actually consult:

* every cached ``csd_spmm`` Pallas entry is re-certified through the
  grid pass (the SL101–SL105 findings re-surface here, subject = the
  cache key);
* every entry's dispatch fields must be legal for its key's form —
  no dense winner for a quant/sharded regime, no unknown dataflow
  (SL401);
* unparseable keys / an unreadable cache file are reported (SL402)
  rather than silently skipped — runtime lookups tolerate corruption by
  design (graceful heuristic fallback), the *audit* must not.

Keys are parsed from their string form (``cache.junction_key`` et al.);
entries tuned on another device class are still audited — certification
is static capture, it never executes the kernel.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from .findings import Finding


def _parse_key(key: str) -> Optional[dict]:
    parts = key.split("|")
    try:
        if parts[0] == "csd_spmm" and len(parts) == 10:
            return dict(
                op="csd_spmm", form=parts[1], m=int(parts[2][1:]),
                n_in=int(parts[3][2:]), n_out=int(parts[4][3:]),
                rho=float(parts[5][3:]), E=int(parts[6][1:]),
                dtype=parts[7], quant=parts[8] == "q1", device=parts[9])
        if parts[0] == "paged_decode" and len(parts) == 11:
            return dict(op="paged_decode", device=parts[10])
        if parts[0] == "fit_blocks" and len(parts) == 7:
            return dict(op="fit_blocks", device=parts[6])
    except (ValueError, IndexError):
        return None
    return None


def _audit_junction(key: str, parsed: dict, ent: dict) -> List[Finding]:
    allowed = {"pallas", "xla"} if (parsed["quant"]
                                    or "sharded" in parsed["form"]) \
        else {"pallas", "xla", "dense"}
    be = ent.get("backend")
    df = ent.get("dataflow", "gather")
    if be not in allowed or df not in ("gather", "scatter"):
        return [Finding(
            "SL401", key,
            f"illegal tuned entry: backend={be!r} dataflow={df!r} "
            f"(allowed backends for form {parsed['form']!r}, "
            f"quant={parsed['quant']}: {sorted(allowed)})")]
    if be != "pallas":
        return []
    from ..core.block_pattern import make_block_pattern
    from ..tune import certify
    from ..tune.tuner import bp_rho_cap
    bi = int(ent.get("block_in", 128))
    bo = int(ent.get("block_out", 128))
    try:
        bp = make_block_pattern(parsed["n_in"], parsed["n_out"],
                                bp_rho_cap(parsed["rho"]), block_in=bi,
                                block_out=bo, seed=0)
        bm = ent.get("block_m")
        ok, fs = certify.certify_junction(
            bp, parsed["m"], None if bm is None else int(bm),
            E=parsed["E"])
    except Exception as e:
        return [Finding("SL401", key,
                        f"cached pallas entry cannot be re-certified: "
                        f"{type(e).__name__}: {e}")]
    if ok:
        return []
    return [dataclasses.replace(f, subject=key,
                                detail=dict(f.detail, case=f.subject))
            for f in fs]


def run(cache_path: Optional[str] = None
        ) -> Tuple[List[Finding], List[str]]:
    """Audit the tune cache at ``cache_path`` (default: the path runtime
    lookups resolve — ``REPRO_TUNE_CACHE`` or the XDG default). Returns
    ``(findings, covered_keys)``; a missing file is an empty, clean
    audit."""
    from ..tune import cache as tcache

    findings: List[Finding] = []
    covered: List[str] = []
    c = tcache.TuneCache(cache_path or tcache.default_path()).load()
    if c.load_error is not None:
        findings.append(Finding(
            "SL402", c.path,
            f"tune cache unreadable (runtime falls back to the "
            f"heuristic; the audit does not): {c.load_error}"))
        return findings, covered
    for key, ent in sorted(c.entries.items()):
        parsed = _parse_key(key)
        if parsed is None:
            findings.append(Finding("SL402", key,
                                    "unparseable tune-cache key"))
            continue
        covered.append(key)
        if parsed["op"] == "csd_spmm":
            findings.extend(_audit_junction(key, parsed, ent))
        elif parsed["op"] == "paged_decode":
            if ent.get("backend") not in ("pallas", "xla"):
                findings.append(Finding(
                    "SL401", key,
                    f"illegal tuned entry: backend="
                    f"{ent.get('backend')!r} (decode allows pallas/xla)"))
        elif parsed["op"] == "fit_blocks":
            bi, bo = ent.get("block_in"), ent.get("block_out")
            if not (isinstance(bi, int) and isinstance(bo, int)
                    and bi >= 32 and bo >= 32):
                findings.append(Finding(
                    "SL401", key,
                    f"illegal tile entry: block_in={bi!r} "
                    f"block_out={bo!r} (need ints >= 32)"))
    return findings, covered
