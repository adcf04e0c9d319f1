"""The committed limits of ``correct`` against the chip readings they
were set from (``readings`` in ``bench/cells/<cell>.json``): each limit
lies above the largest reading of sound runs, and the control and every
planted fault recorded there come out not correct through the run's own
comparison (``common.judge``) at the committed limits. A control that
crashed on the chip gave no reading; the cell file says so in words."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench.harness import common

ROOT = Path(__file__).resolve().parents[2]
BM = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BM["workloads"]]


def _cell(name):
    return json.loads((ROOT / "bench/cells" / f"{name}.json").read_text())


def _checks(cell, pick):
    """The cell's compared numbers at their limits, valued by ``pick``
    over each number's readings of one kind (None: no reading)."""
    lim, rd = cell["correct"], cell["readings"]
    out = {}
    for k, kinds in rd["numbers"].items():
        v = pick(kinds)
        if v is not None:
            out[k] = {"value": v, "limit": float(lim[k]), "rule": "<="}
    return out


@pytest.mark.parametrize("name", CELLS)
def test_sound_runs_pass(name):
    cell = _cell(name)
    checks = _checks(cell, lambda kinds: max(kinds["program"]))
    assert checks and common.judge(checks), checks
    for k, c in checks.items():
        assert c["value"] < c["limit"], (k, c)


@pytest.mark.parametrize("name", CELLS)
def test_control_and_faults_fail(name):
    cell = _cell(name)
    kinds = {kind for v in cell["readings"]["numbers"].values()
             for kind in v if kind != "program"}
    assert "control" in kinds or isinstance(
        cell["readings"].get("control"), str), "no control recorded"
    for kind in sorted(kinds):
        checks = _checks(cell, lambda ks: min(ks[kind]) if kind in ks
                         else None)
        assert not common.judge(checks), (kind, checks)
