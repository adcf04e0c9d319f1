"""Compile the benchmark's junctions at 128 x 128 tiles and published
widths for a described TPU v5e (no chip): qwen2_7b's plain junctions at
the chat cell's prefill (16 slots x 128-token chunks) and decode rows,
with their gradient, and granite_moe's expert-batched junctions with
their gradient at the training cell's capacity.

The topology is described inside a module-scoped fixture, never at
import time (only one process may load the TPU library)."""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

from bench.harness import lookup
from bench.harness.serve import junction_patterns

ROOT = Path(__file__).resolve().parents[2]
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _patterns(config):
    from repro.nn import build_model

    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    return junction_patterns(build_model(lookup.model_config(cfg["model"])))


def _spec(shape, sharding, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


@pytest.mark.parametrize("rows", [16, 2048], ids=["decode", "prefill"])
@pytest.mark.parametrize("junction", ["up", "down"])
def test_qwen2_junction_compiles(one_chip, junction, rows):
    bp = _patterns("qwen2_7b")[junction]
    x = _spec((rows, bp.n_in), one_chip)
    w = _spec((bp.n_rb, bp.d_in_b, 128, 128), one_chip)
    _compile(lambda x, w: ops.csd_matmul(x, w, bp, backend="pallas"), x, w)


def test_qwen2_junction_gradient_compiles(one_chip):
    bp = _patterns("qwen2_7b")["up"]
    x = _spec((2048, bp.n_in), one_chip)
    w = _spec((bp.n_rb, bp.d_in_b, 128, 128), one_chip)

    def loss(x, w):
        return jnp.sum(ops.csd_matmul(x, w, bp, backend="pallas")
                       .astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1)), x, w)
    # dx and dw each lower to their own kernel
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("junction", ["up", "down"])
def test_granite_expert_junction_gradient_compiles(one_chip, junction):
    bp = _patterns("granite_moe_1b_a400m")[junction]
    experts, capacity = 32, 5120      # 4 x 4096 tokens, top-8, factor 1.25
    x = _spec((experts, capacity, bp.n_in), one_chip)
    w = _spec((experts, bp.n_rb, bp.d_in_b, 128, 128), one_chip)

    def loss(x, w):
        return jnp.sum(ops.csd_matmul(x, w, bp, backend="pallas")
                       .astype(jnp.float32))

    _compile(jax.grad(loss, argnums=(0, 1)), x, w)
