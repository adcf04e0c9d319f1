"""What every kind of cell shares: the device rule, the compile counter,
the set-up breakdown, the trace window and the result line."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

clock = time.perf_counter


def log(msg: str) -> None:
    print(msg, flush=True)


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(chips: int):
    """The devices this cell runs on. No TPU, or too few: exit non-zero
    with no result (a CPU run is never reported as a device number)."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: no TPU: JAX found {devs[0].platform} "
                     f"({len(devs)} device(s)); not running")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    d = devs[0]
    peak = 0
    for x in devs:
        st = x.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts executables compiled or loaded from the persistent cache
    while ``active``: inside the measured window there should be none."""

    def __init__(self):
        import jax

        self.active = False
        self.n = 0          # programs compiled or loaded from the cache
        self.hits = 0       # of them, loaded from the persistent cache
        self.names = []

        def on_duration(event, duration, **kw):
            if self.active and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.names.append(kw.get("fun_name", "?"))

        def on_event(event, **kw):
            if self.active and event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def report_and_reset(self, phase: str) -> None:
        log(f"[{phase}] {self.n} programs compiled or loaded, "
            f"{self.hits} of them from the persistent cache")
        self.n = self.hits = 0
        self.names = []


class Trace:
    """A profiler trace of part of the window, in a directory of its own
    under TMPDIR, read and removed once the run is done."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.on = False
        self.t0 = self.t1 = None
        self._ann = None

    def start(self):
        import jax

        # no Python tracer: every Python call would be an event, and
        # the host would slow; named spans come from the host tracer
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation("bench/window")
        self._ann.__enter__()
        self.t0 = clock()
        self.on = True

    def stop(self):
        import jax

        self.t1 = clock()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.on = False

    def file(self) -> str:
        for root, _, files in os.walk(self.dir):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        raise FileNotFoundError("the profiler wrote no .xplane.pb")

    def remove(self):
        shutil.rmtree(self.dir, ignore_errors=True)


@contextlib.contextmanager
def highest_precision():
    import jax

    with jax.default_matmul_precision("highest"):
        yield


def per_layer_metrics(cell, ctx: dict) -> dict:
    """Each per-layer metric of this cell, from its own reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        v = cell.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def judge(checks: dict) -> bool:
    """``correct``: every compared number at or under its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def with_values(checks: dict, values: dict) -> dict:
    """The same checks with other readings (a control's) in place."""
    return {k: dict(c, value=values.get(k, c["value"]))
            for k, c in checks.items()}


def emit(result: dict, checks: dict) -> None:
    """Print the compared numbers beside their limits as the last lines
    of stderr, and the result as the last line of stdout, with the
    checks under a key of their own that comes last."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r}, "
              f"{c['rule']})", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
