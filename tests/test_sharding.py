"""Sharding policy + distributed-lowering tests.

The multi-device cases run in a subprocess (XLA device count is locked at
first jax init, and the main test process must keep the real 1-CPU view).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding import policy

REPO = os.path.join(os.path.dirname(__file__), "..")


def run_sub(code: str, devices: int = 8) -> str:
    prog = (
        "import os\n"
        f"os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        "from repro.launch.mesh import make_mesh\n"
        + textwrap.dedent(code))
    # the child runs on the CPU's virtual devices, never on an attached
    # accelerator (a chip belongs to one process at a time)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


class FakeMesh:
    def __init__(self, names, sizes):
        self.axis_names = tuple(names)
        self.shape = dict(zip(names, sizes))
        import numpy as _np
        self.devices = _np.empty(sizes)


def test_rules_prune_missing_axes():
    mesh = FakeMesh(("data", "model"), (16, 16))
    r = policy.rules_for("train", 256, mesh)
    assert r["batch"] == ("data",)   # 'pod' pruned
    assert r["seq"] == "model"


def test_decode_rules_switch_to_long_for_small_batch():
    mesh = FakeMesh(("data", "model"), (16, 16))
    r = policy.rules_for("decode", 128, mesh)
    assert r["kv_seq"] == "model" and r["batch"] == ("data",)
    r1 = policy.rules_for("decode", 1, mesh)
    assert r1["batch"] is None
    assert r1["kv_seq"] == ("data", "model")


def test_mamba_rules_fold_model_into_batch():
    from repro.configs import get_config
    mesh = FakeMesh(("data", "model"), (16, 16))
    cfg = get_config("mamba2_130m")
    r = policy.rules_for("train", 256, mesh, cfg)
    assert r["batch"] == ("data", "model")
    assert r["seq"] is None
    # multi-pod: 256 % 512 != 0 -> model not folded
    mesh2 = FakeMesh(("pod", "data", "model"), (2, 16, 16))
    r2 = policy.rules_for("train", 256, mesh2, cfg)
    assert r2["batch"] == ("pod", "data")


def test_sanitize_drops_indivisible_dims():
    mesh = make_mesh((1,), ("model",))

    class S:
        shape = (37, 64)
    fixed = policy.sanitize(P("model", None), S(), mesh)
    assert fixed == P("model", None)  # 37 % 1 == 0

    mesh_names = FakeMesh(("model",), (16,))
    # emulate: use the real function against a fake 16-wide mesh
    sizes = {"model": 16}

    def fix_one(spec, shape):
        out = []
        for dim, ax in zip(shape, list(spec) + [None] * (len(shape) - len(spec))):
            if ax is None:
                out.append(None)
                continue
            n = int(np.prod([sizes[a] for a in
                             (ax if isinstance(ax, tuple) else (ax,))]))
            out.append(ax if dim % n == 0 else None)
        return P(*out)

    assert fix_one(P("model", None), (37, 64)) == P(None, None)
    assert fix_one(P("model", None), (64, 37)) == P("model", None)


def test_param_pspecs_resolve_logical_axes():
    from repro.configs import get_config
    from repro.nn import build_model
    cfg = get_config("qwen2_7b", smoke=True)
    model = build_model(cfg)
    rules = {"embed": "data", "mlp": "model", "qheads": "model",
             "kvheads": "model", "vocab": "model", "layers": None,
             "mlp_act": None, "batch": ("data",), "seq": "model",
             "kv_seq": None, "expert": "model"}
    specs = policy.param_pspecs(model.spec(), rules)
    leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert all(isinstance(l, P) for l in leaves)


@pytest.mark.slow
def test_distributed_train_step_runs_and_matches_single_device():
    """4-device (2x2) sharded train step == unsharded step (same math)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.nn import ModelConfig, build_model
        from repro.nn.common import mesh_context
        from repro.optim import AdamWConfig
        from repro.launch import specs
        from repro.sharding import policy

        cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab_size=128, attn_chunk=8,
                          loss_chunk=8, dtype="float32", remat=False)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        from repro.optim import adam
        opt = adam.init(params)
        tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, 128)
        batch = {"tokens": tokens, "labels": tokens}
        step = specs.make_train_step(model, AdamWConfig(lr=1e-3,
                                                        warmup_steps=0))
        p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = policy.rules_for("train", 8, mesh, cfg)
        pspec = policy.param_pspecs(model.spec(), rules)
        p_sh = policy.named(mesh, pspec, params)
        o_sh = policy.named(mesh, policy.opt_pspecs(pspec), opt)
        b_sh = policy.named(mesh, policy.batch_pspecs(batch, rules), batch)
        with mesh, mesh_context(mesh, rules):
            p2, o2, m2 = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                                 out_shardings=(p_sh, o_sh, None))(
                params, opt, batch)
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(p_ref), jax.tree.leaves(p2)))
        print("MAXERR", err)
        print("LOSSDIFF", abs(float(m_ref["loss"]) - float(m2["loss"])))
    """, devices=4)
    maxerr = float(out.split("MAXERR")[1].split()[0])
    lossdiff = float(out.split("LOSSDIFF")[1].split()[0])
    assert maxerr < 2e-3, out
    assert lossdiff < 1e-4, out


@pytest.mark.slow
def test_moe_shardmap_matches_local():
    """Expert-parallel shard_map MoE == local MoE on the same inputs."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.nn import ModelConfig, MoEConfig
        from repro.nn.common import mesh_context
        from repro.nn.ffn import MoE
        from repro.sharding import policy

        cfg = ModelConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          d_ff=64, vocab_size=64, dtype="float32",
                          moe=MoEConfig(n_routed=8, top_k=2, n_shared=0,
                                        d_expert=16,
                                        capacity_factor=100.0))
        moe = MoE(cfg)
        params = moe.init(jax.random.key(0))
        x = jax.random.normal(jax.random.key(1), (4, 8, 32))
        y_local, _ = moe(params, x)   # no mesh -> local path

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = policy.rules_for("train", 4, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            y_sm, aux = jax.jit(lambda p, x: moe(p, x))(params, x)
        print("ERR", float(jnp.abs(y_local - y_sm).max()))
    """, devices=4)
    err = float(out.split("ERR")[1].split()[0])
    assert err < 1e-3, out


@pytest.mark.slow
def test_sparse_moe_shardmap_matches_local_and_dense_oracle():
    """Cross-mode certification of the batched block-sparse expert path:
    with ``moe_sparsity`` on, the shard_map (expert-parallel) mode, the
    gshard-style local mode, and the dense ``kernels.ref`` expert oracle
    all agree — forward and expert-weight gradients."""
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.core import block_weights_to_dense
        from repro.kernels import ref
        from repro.nn import ModelConfig, MoEConfig
        from repro.nn.common import SparsityConfig, mesh_context
        from repro.nn.ffn import MoE
        from repro.sharding import policy

        cfg = ModelConfig(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                          d_ff=64, vocab_size=64, dtype="float32",
                          moe=MoEConfig(n_routed=8, top_k=2, n_shared=0,
                                        d_expert=16,
                                        capacity_factor=100.0),
                          sparsity=SparsityConfig(
                              enabled=True, rho_ffn=(0.5, 0.75),
                              block_in=8, block_out=8, moe_sparsity=True,
                              backend="xla"))
        moe = MoE(cfg)
        assert moe.up_pat is not None
        params = moe.init(jax.random.key(0))
        assert params["up"].ndim == 5  # batched junction slabs
        x = jax.random.normal(jax.random.key(1), (4, 8, 32))
        y_local, _ = moe(params, x)   # no mesh -> gshard-style local path

        # dense-oracle MoE on the expanded weights (routing identical)
        E = 8
        expand = lambda n, pat: jnp.stack(
            [block_weights_to_dense(params[n][e], pat) for e in range(E)])
        params_d = dict(params, up=expand("up", moe.up_pat),
                        gate=expand("gate", moe.gate_pat),
                        down=expand("down", moe.down_pat))
        moe_d = MoE(cfg.with_(sparsity=SparsityConfig()))
        y_dense, _ = moe_d(params_d, x)
        print("ERRDENSE", float(jnp.abs(y_local - y_dense).max()))

        def loss(p, m=moe):
            y, aux = m(p, x)
            return jnp.sum(y ** 2)
        g_s = jax.grad(loss)(params)

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = policy.rules_for("train", 4, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            y_sm, aux = jax.jit(lambda p, x: moe(p, x))(params, x)
            g_sm = jax.jit(jax.grad(loss))(params)
        print("ERRSM", float(jnp.abs(y_local - y_sm).max()))
        gerr = max(float(jnp.abs(g_s[n] - g_sm[n]).max())
                   for n in ("up", "gate", "down", "router"))
        print("ERRGRAD", gerr)
    """, devices=4)
    assert float(out.split("ERRDENSE")[1].split()[0]) < 1e-4, out
    assert float(out.split("ERRSM")[1].split()[0]) < 1e-3, out
    assert float(out.split("ERRGRAD")[1].split()[0]) < 1e-3, out


@pytest.mark.slow
def test_seq_parallel_attention_matches_unsharded():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from repro.nn import ModelConfig, build_model
        from repro.nn.common import mesh_context
        from repro.sharding import policy

        cfg = ModelConfig(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                          d_ff=128, vocab_size=128, attn_chunk=8,
                          loss_chunk=8, dtype="float32", remat=False,
                          local_global_ratio=1, attn_window=16)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        tokens = jax.random.randint(jax.random.key(1), (4, 64), 0, 128)
        batch = {"tokens": tokens, "labels": tokens}
        h_ref, _, _ = model.forward(params, batch)

        mesh = make_mesh((2, 2), ("data", "model"))
        rules = policy.rules_for("train", 4, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            h_sh, _, _ = jax.jit(
                lambda p, b: model.forward(p, b))(params, batch)
        print("ERR", float(jnp.abs(h_ref - h_sh).max()))
    """, devices=4)
    err = float(out.split("ERR")[1].split()[0])
    assert err < 2e-3, out
