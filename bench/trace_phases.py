"""Two reductions of a profiler trace that split what ``trace_reduce``
reports whole. Not yet read by a cell's metric (see PERF.md, section 7);
run by hand, from the root of the checkout, on a kept trace:

    python3 -m bench.trace_phases <trace.xplane.pb> [<compiled HLO> ...]

* ``idle_by_span``: the idle time of the first device in the window, cut
  at every boundary of a named host span, each piece given to the
  innermost span that covers it (``trace_reduce``'s ``idle_gaps`` gives a
  whole gap to the span at its middle, though one gap may run through
  several host phases);
* ``scopes``: device seconds per program scope (the ``jax.named_scope``
  names of ``repro.nn``): each op's self time (its time not covered by an
  op nested in it on the same line) goes to the innermost scope on its
  op_name path, through transform wrappers such as
  ``transpose(jvp(moe/dispatch))``; ops with no scope are not counted.

A TPU trace names each device op by its HLO text and carries no op_name,
so ``scopes`` reads the op_names from the compiled programs' HLO text
(``op_names``), matched to an op by its program (the ``XLA Modules`` line)
and its instruction name.
"""
from __future__ import annotations

import bisect
import collections
import re
import sys
from typing import Dict, Iterable, Tuple

from bench.trace_reduce import _SUFFIX, _union, WINDOW

# the program's scopes, as repro.nn names them
SCOPES = ("attn/proj", "attn/core", "attn/decode", "ffn", "moe/router",
          "moe/dispatch", "moe/experts", "moe/combine", "lm_head", "loss")
NO_SPAN = "no host span"
_WRAP = re.compile(r"[\w.-]+\(|\)")


def _read(planes) -> tuple:
    """One pass over the planes (the profiler's planes can be walked only
    once): the window, the named host spans (a name with a ``/``, as
    ``trace_reduce`` takes them), and the ``XLA Ops`` and ``XLA Modules``
    events of the first device."""
    win, spans, lines = None, [], None
    for plane in planes:
        if plane.name.startswith("/device:TPU:") and lines is None:
            lines = {line.name: list(line.events) for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW:
                        win = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif "/" in ev.name:
                        spans.append((ev.start_ns,
                                      ev.start_ns + ev.duration_ns, ev.name))
    if win is None:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    if not lines or "XLA Ops" not in lines:
        raise ValueError("no device 'XLA Ops' line in the trace")
    return win, sorted(spans), lines["XLA Ops"], lines.get("XLA Modules", [])


def idle_by_span(planes) -> Dict[str, float]:
    """Idle seconds of the first device per innermost host span."""
    (w0, w1), spans, ops, _ = _read(planes)
    busy = _union([(max(ev.start_ns, w0),
                    min(ev.start_ns + ev.duration_ns, w1))
                   for ev in ops
                   if ev.start_ns < w1 and ev.start_ns + ev.duration_ns > w0])
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    cuts = sorted({x for s, e, _ in spans for x in (s, e) if w0 < x < w1})
    out = collections.Counter()
    active: list = []
    nxt = 0
    for a, b in zip(edges[0::2], edges[1::2]):
        bounds = [a] + cuts[bisect.bisect_right(cuts, a):
                            bisect.bisect_left(cuts, b)] + [b]
        for p0, p1 in zip(bounds, bounds[1:]):
            if p1 <= p0:
                continue
            # the spans that cover the piece [p0, p1), inside which no
            # span starts or ends
            while nxt < len(spans) and spans[nxt][0] <= p0:
                active.append(spans[nxt])
                nxt += 1
            active = [sp for sp in active if sp[1] >= p1]
            inner = min(active, key=lambda sp: sp[1] - sp[0], default=None)
            name = _SUFFIX.sub("", inner[2]) if inner else NO_SPAN
            out[name] += (p1 - p0) * 1e-9
    return dict(out)


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """(program name, {instruction name: op_name}) of a compiled
    program's HLO text (``compiled.as_text()``)."""
    m = re.match(r"\s*HloModule ([\w.-]+)", hlo_text)
    table, ins = {}, None
    # an instruction's text runs on over the lines of its kernel metadata
    for line in hlo_text.splitlines() + [""]:
        if re.match(r"\s*(ROOT )?%[\w.-]+ = ", line) or not line.strip():
            found = ins and re.match(
                r'\s*(?:ROOT )?%([\w.-]+) = .*?metadata=\{op_name="([^"]*)"',
                ins, re.S)
            if found:
                table[found.group(1)] = found.group(2)
            ins = line if line.strip() else None
        elif ins is not None:
            ins += line
    return (m.group(1) if m else ""), table


def scope_of(op_name: str) -> str:
    """The innermost scope among the path segments of ``op_name``."""
    segs = _WRAP.sub("", op_name).split("/")
    found = [(i + len(p), len(p), sc)
             for sc in SCOPES for p in [sc.split("/")]
             for i in range(len(segs) - len(p) + 1) if segs[i:i + len(p)] == p]
    return max(found)[2] if found else ""


def _self_times(events) -> Iterable[Tuple[object, int]]:
    """(event, self time in ns): its duration less that of the events
    nested directly in it."""
    evs = sorted(events, key=lambda ev: (ev.start_ns, -ev.duration_ns))
    inner = [0] * len(evs)
    stack: list = []
    for i, ev in enumerate(evs):
        end = ev.start_ns + ev.duration_ns
        while stack and stack[-1][0] <= ev.start_ns:
            stack.pop()
        if stack and end <= stack[-1][0]:
            inner[stack[-1][1]] += ev.duration_ns
        stack.append((end, i))
    return ((ev, ev.duration_ns - inner[i]) for i, ev in enumerate(evs))


def scopes(planes, tables: Dict[str, Dict[str, str]]) -> Dict[str, float]:
    """Device seconds of the first device per scope, for the ops that
    start in the window. ``tables``: program name -> ``op_names``'s
    table."""
    (w0, w1), _, ops, modules = _read(planes)
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   ev.name.split("(")[0]) for ev in modules)
    starts = [m[0] for m in mods]
    out = collections.Counter()
    for ev, self_ns in _self_times(ops):
        if not w0 <= ev.start_ns < w1:
            continue
        k = bisect.bisect_right(starts, ev.start_ns) - 1
        if k < 0 or ev.start_ns >= mods[k][1]:
            continue
        ins = ev.name.split(" = ")[0].lstrip("%").strip()
        sc = scope_of(tables.get(mods[k][2], {}).get(ins, ""))
        if sc:
            out[sc] += self_ns * 1e-9
    return dict(out)


def main(argv) -> int:
    import json

    import jax

    def planes():
        return jax.profiler.ProfileData.from_file(argv[0]).planes

    out = {"idle_by_span": idle_by_span(planes())}
    if argv[1:]:
        tables = dict(op_names(open(p).read()) for p in argv[1:])
        out["scopes"] = scopes(planes(), tables)
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
