"""From a profiler trace (``.xplane.pb``) to the numbers per-layer
metrics read. Kept with the benchmark so that every change is measured by
the same reduction.

* window: the host annotation ``bench/window`` that the harness opens
  when the trace starts and closes when it stops (the same clock as the
  device events);
* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), clipped to the
  window, averaged over the devices;
* per-op time: device seconds per operation name (numeric suffixes
  stripped), and per kernel family (``FAMILIES``: a family matches the
  op's name or any of its stats);
* idle gaps: each stretch of the window in which no device op ran,
  attributed to the innermost named host span (a name with a ``/``: the
  program's ``engine/...``, ``train/...`` and the harness's ``bench/...``)
  that covers its middle.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

# kernel families, matched (case-insensitively) against an op's name and
# its stats, first match wins. In the compiled engine step every Pallas
# call carries the same op_name (``.../closed_call/pallas_call``), so the
# kernel names may not reach the trace; ``pallas`` then holds them all.
FAMILIES = {
    "csd_spmm": ("_fwd_kernel", "_dx_kernel", "_dw_kernel", "csd_spmm"),
    "paged_decode": ("_paged_decode", "paged_decode"),
    "pallas": ("pallas_call", "tpu_custom_call"),
}

WINDOW = "bench/window"
_SUFFIX = re.compile(r"[._]\d+$")


def _stats(ev) -> Dict[str, str]:
    try:
        return {str(k): str(v) for k, v in ev.stats}
    except Exception:
        return {}


def family_of(name: str, stats: Dict[str, str]) -> str:
    text = (name + " " + " ".join(stats.values())).lower()
    for fam, keys in FAMILIES.items():
        if any(k.lower() in text for k in keys):
            return fam
    return ""


def _union(iv: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def reduce(path: str) -> dict:
    import jax

    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes,
                         path)


def reduce_planes(planes, what: str = "trace") -> dict:
    """The reduction over planes with ``name`` and ``lines``; lines with
    ``name`` and ``events``; events with ``name``, ``start_ns``,
    ``duration_ns`` and ``stats``."""
    win = None
    host_spans = []
    dev_lines = []
    for plane in planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev_lines.append(list(line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif "/" in ev.name:
                        # the program's and the harness's named spans
                        # (engine/step, train/step, bench/...)
                        host_spans.append((ev.start_ns,
                                           ev.start_ns + ev.duration_ns,
                                           ev.name))
    if win is None:
        raise ValueError(f"no {WINDOW!r} annotation in {what}")
    if not dev_lines:
        raise ValueError(f"no device 'XLA Ops' line in {what}")
    w0, w1 = win
    busy = 0
    per_op = collections.Counter()
    per_fam = collections.Counter()
    n_fam = collections.Counter()
    first_busy = []
    for events in dev_lines:
        iv = []
        for ev in events:
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            s, e = max(s, w0), min(e, w1)
            if e <= s:
                continue
            iv.append((s, e))
            name = _SUFFIX.sub("", ev.name)
            per_op[name] += (e - s) * 1e-9
            fam = family_of(ev.name, _stats(ev))
            if fam:
                per_fam[fam] += (e - s) * 1e-9
                n_fam[fam] += 1
        u = _union(iv)
        busy += sum(e - s for s, e in u)
        first_busy.append(u)
    n_dev = len(dev_lines)
    # idle gaps of the first device, attributed to host spans
    gaps = collections.Counter()
    u = first_busy[0]
    edges = [w0] + [x for s, e in u for x in (s, e)] + [w1]
    host_spans.sort()
    starts = [s for s, _, _ in host_spans]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) // 2
        best = None
        # nested spans: look back from the last span that starts before
        # the gap's middle, for the shortest one that covers it
        i = bisect.bisect_right(starts, mid)
        for s, e, name in host_spans[max(0, i - 64):i][::-1]:
            if e >= mid and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        gaps[_SUFFIX.sub("", best[2]) if best else "no host span"] += \
            (b - a) * 1e-9
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * 1e-9 / n_dev,
        "devices": n_dev,
        "ops": dict(per_op),
        "families": dict(per_fam),
        "family_events": dict(n_fam),
        "device_ops": [[k, v] for k, v in per_op.most_common()],
        "idle_gaps": [[k, v] for k, v in gaps.most_common()],
    }
