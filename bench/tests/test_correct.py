"""``correct`` comes out false when the timed path is broken, and the
controls fail what they must: tiny cells on the CPU, driving the rest of
a run with the harness's look for a chip skipped."""
from __future__ import annotations

import time

import jax
import pytest

from bench.harness import faults, serve, train
from bench.tests import tiny


def _run(mod, kind, **kw):
    c = tiny.cell(kind)
    return mod.run(c, tiny.args(seconds=1.5), jax.devices(),
                   time.perf_counter(), **kw)


def test_serve_sound_run_is_correct():
    result, checks = _run(serve, "chat")
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0


def test_serve_altered_token_is_not_correct():
    result, checks = _run(serve, "chat", fault=faults.altered_token)
    assert not result["correct"]
    assert checks["served_logit_gap"]["value"] > \
        checks["served_logit_gap"]["limit"]


def test_backlog_sound_run_is_correct():
    result, _ = _run(serve, "backlog")
    assert result["correct"]


@pytest.fixture(scope="module")
def train_runs():
    return _run(train, "train"), _run(train, "train", control=True)


def test_train_sound_run_is_correct(train_runs):
    (sound, checks), _ = train_runs
    assert sound["correct"], checks


def test_train_control_fails(train_runs):
    """The program with bfloat16 master weights (the control) fails the
    parameters' change."""
    (_, checks), (ctrl, ctrl_checks) = train_runs
    assert not ctrl["correct"], ctrl_checks
    assert ctrl_checks["change3_leaf_gap"]["value"] > \
        3 * checks["change3_leaf_gap"]["value"]


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch"])
def test_train_fault_is_not_correct(fault):
    result, checks = _run(train, "train", fault=faults.TRAIN[fault])
    assert not result["correct"], checks

