"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix. Each lives in a file of
its own, found by name:

* ``bench/configs/<config>.json``   model sizes, source, cuts;
* ``bench/traffic/<traffic>.json``  the mix (lengths, arrivals, job);
* ``bench/cells/<workload>.json``   what is sized for this pairing on the
  chip (engine pool, arrival rate, batch) and the limits of ``correct``;
* ``bench/metrics/<metric>.py``     a reader per per-layer metric, or
  ``bench/metrics/<family>.py``     one per family (``mfu`` for
  ``mfu.chat``) where the cells' readers do not differ;
* ``bench/reference/<config>.py``   the plain float32 reference.

Adding a cell or a metric adds files and entries; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(path: Path, name: str):
    """Import a file found by name (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "bench_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict
    traffic: dict
    cell: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def kind(self) -> str:
        return self.traffic["kind"]

    def reference(self):
        return load_module(BENCH / "reference" / f"{self.config_name}.py",
                           self.config_name)

    def metric_reader(self, name: str):
        """``bench/metrics/<name>.py``, or else the family's reader,
        ``bench/metrics/<family>.py`` (the name before its first dot)."""
        path = BENCH / "metrics" / f"{name}.py"
        if not path.is_file():
            path = BENCH / "metrics" / f"{name.split('.')[0]}.py"
        return load_module(path, path.stem)


def _applies(metric: dict, workload: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", None) in e2e_names if "moves" in metric \
        else True


def find_cell(name: str, benchmark: dict = None) -> Cell:
    bm = benchmark if benchmark is not None else \
        _load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    c = configs[w["config"]]
    e2e = [m for m in bm["end_to_end"] if _applies(m, name, set())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _applies(m, name, e2e_names)]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        traffic_name=w["traffic"],
        config=_load_json(ROOT / c["file"]),
        traffic=_load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        cell=_load_json(BENCH / "cells" / f"{name}.json"),
        end_to_end=e2e, per_layer=per_layer)


def model_config(model: dict):
    """The program's ``ModelConfig`` for a configuration file's ``model``."""
    from repro.nn.common import ModelConfig, MoEConfig, SparsityConfig

    kw = dict(model)
    sp = dict(kw.pop("sparsity"))
    sp["rho_ffn"] = tuple(sp["rho_ffn"])
    kw["sparsity"] = SparsityConfig(**sp)
    if kw.get("moe") is not None:
        kw["moe"] = MoEConfig(**kw["moe"])
    return ModelConfig(**kw)
