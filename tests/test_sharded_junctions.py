"""Sharded (model-parallel) sparse junction certification.

The tentpole contract: partitioning a junction's BlockPattern + weight
slab across a mesh axis — the jax_pallas analogue of the paper's
size-flexible ``z`` (more parallel block-rows per cycle) — must be
numerically invisible. Coverage:

* host-side partition properties (disjoint cover, slot balance, padded
  local scatter forms, slab split/merge round-trip);
* 8-forced-host-device parity of the sharded ``csd_matmul`` (fwd + VJP,
  4-D and 5-D slabs, both backends) vs the single-device path;
* sharded train step == single-device train step (loss + params), with
  slab weights and Adam state actually chunked over the slab axis;
* sharded ``ServingEngine`` greedy decode token-identical to the
  single-device engine on a mixed-length sparse batch;
* checkpoint save/restore round-trip of sharded params + opt state.

Multi-device cases run in subprocesses (XLA device count is locked at
first jax init; the main test process keeps the real 1-CPU view).
"""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import (make_block_pattern, merge_slab, partition_pattern,
                        split_slab)

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


# ---------------------------------------------------------------------------
# host-side partition properties (fast, tier-1)
# ---------------------------------------------------------------------------


def _pat(n_lb=8, n_rb=16, bl=4, br=4, rho=0.5, seed=0):
    return make_block_pattern(n_lb * bl, n_rb * br, rho, block_in=bl,
                              block_out=br, seed=seed)


def test_partition_covers_rows_disjointly_and_balances_slots():
    bp = _pat()
    for k in (2, 4, 8):
        part = partition_pattern(bp, k)
        rows = np.sort(np.concatenate(
            [s.meta["rows"] for s in part.shards]))
        assert rows.tolist() == list(range(bp.n_rb))
        slot_counts = [s.block_idx.size for s in part.shards]
        assert len(set(slot_counts)) == 1  # balanced by slot count
        assert part.contiguous
        # inverse permutation really inverts
        assert (part.perm[part.inv_perm] == np.arange(bp.n_rb)).all()


def test_partition_local_patterns_preserve_adjacency():
    bp = _pat()
    part = partition_pattern(bp, 4)
    for s, shard in enumerate(part.shards):
        rows = part.parent.block_idx[np.asarray(part.shards[s].meta["rows"])]
        assert (shard.block_idx == rows).all()
        # padded scatter form: valid entries reproduce every edge exactly
        edges = set()
        for lb in range(bp.n_lb):
            for g in range(part.out_idx.shape[2]):
                if part.out_valid[s, lb, g]:
                    r = part.out_idx[s, lb, g]
                    f = part.out_slot[s, lb, g]
                    assert shard.block_idx[r, f] == lb
                    edges.add((int(r), int(f)))
        assert len(edges) == shard.block_idx.size  # all edges, no dupes


def test_partition_rejects_indivisible_row_counts():
    bp = _pat(n_rb=6)
    with pytest.raises(ValueError):
        partition_pattern(bp, 4)


def test_slab_split_merge_roundtrip_4d_and_5d():
    bp = _pat()
    part = partition_pattern(bp, 4)
    rng = np.random.default_rng(0)
    w4 = rng.normal(size=(bp.n_rb, bp.d_in_b, 4, 4)).astype(np.float32)
    ws = split_slab(w4, part)
    assert ws.shape == (4, bp.n_rb // 4, bp.d_in_b, 4, 4)
    np.testing.assert_array_equal(merge_slab(ws, part), w4)
    w5 = rng.normal(size=(3, bp.n_rb, bp.d_in_b, 4, 4)).astype(np.float32)
    ws5 = split_slab(w5, part)
    assert ws5.shape == (4, 3, bp.n_rb // 4, bp.d_in_b, 4, 4)
    np.testing.assert_array_equal(merge_slab(ws5, part), w5)


def test_shard_pattern_is_a_full_csd_matmul_citizen():
    """A shard-local BlockPattern (padded, validity-masked scatter form)
    must behave correctly through the PUBLIC csd_matmul API — scatter
    dataflow forward and gradients — matching the corresponding slice of
    the full junction."""
    import jax.numpy as jnp
    from repro.kernels import ops
    bp = _pat()
    k = 4
    part = partition_pattern(bp, k)
    rng = np.random.default_rng(5)
    m, q = 6, part.n_rb_local
    x = jnp.asarray(rng.normal(size=(m, bp.n_in)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(bp.n_rb, bp.d_in_b, 4, 4)),
                    jnp.float32)
    ws = split_slab(np.asarray(w), part)
    y_full = ops.csd_matmul(x, w, bp, backend="xla")
    for s in (0, k - 1):
        shard = part.shards[s]
        assert shard.out_valid is not None
        for dataflow in ("gather", "scatter"):
            y_s = ops.csd_matmul(x, jnp.asarray(ws[s]), shard,
                                 backend="xla", dataflow=dataflow)
            ref = y_full[:, s * q * 4:(s + 1) * q * 4]
            np.testing.assert_allclose(y_s, ref, atol=1e-4, rtol=1e-4,
                                       err_msg=f"s={s} {dataflow}")
        # grads through the shard pattern's (masked) BP/UP
        g_s = jax.grad(lambda xx: jnp.sum(jnp.sin(
            ops.csd_matmul(xx, jnp.asarray(ws[s]), shard,
                           backend="xla"))))(x)
        g_ref = jax.grad(lambda xx: jnp.sum(jnp.sin(
            ops.csd_matmul(xx, w, bp, backend="xla")
            [:, s * q * 4:(s + 1) * q * 4])))(x)
        np.testing.assert_allclose(g_s, g_ref, atol=1e-4, rtol=1e-4)


def test_permutation_plumbing_inverts_on_synthetic_noncontiguous():
    """perm/inv_perm + the slab helpers + reassemble_outputs honor a
    general (non-identity) assignment: fixed-degree patterns never
    produce one, so pin the machinery with a synthetic shuffle."""
    import dataclasses as dc
    bp = _pat()
    part = partition_pattern(bp, 4)
    rng = np.random.default_rng(9)
    perm = rng.permutation(bp.n_rb).astype(np.int32)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(bp.n_rb, dtype=np.int32)
    shuffled = dc.replace(part, perm=perm, inv_perm=inv)
    assert not shuffled.contiguous
    w = rng.normal(size=(bp.n_rb, bp.d_in_b, 4, 4)).astype(np.float32)
    np.testing.assert_array_equal(
        merge_slab(split_slab(w, shuffled), shuffled), w)
    # shard-major feature order -> logical order at block granularity
    y_logical = rng.normal(size=(3, bp.n_out)).astype(np.float32)
    yb = y_logical.reshape(3, bp.n_rb, bp.block_out)
    y_shard_major = yb[:, perm].reshape(3, bp.n_out)
    from repro.core import reassemble_outputs
    np.testing.assert_array_equal(
        reassemble_outputs(y_shard_major, shuffled), y_logical)


def test_partitioned_dx_partials_sum_to_full():
    """Each shard's validity-masked BP over its padded local scatter form
    contributes exactly its share: the partials sum to the full-pattern
    dx (this is what the sharded VJP psums)."""
    from repro.kernels import ops
    from repro.kernels.csd_spmm import csd_spmm_dx
    bp = _pat()
    k = 4
    part = partition_pattern(bp, k)
    rng = np.random.default_rng(1)
    m = 6
    w = rng.normal(size=(bp.n_rb, bp.d_in_b, 4, 4)).astype(np.float32)
    dy = rng.normal(size=(m, bp.n_out)).astype(np.float32)
    dx_full = np.asarray(ops._xla_dx(
        jax.numpy.asarray(dy), jax.numpy.asarray(w),
        bp.out_idx, bp.out_slot))
    ws = split_slab(w, part)
    dyb = dy.reshape(m, bp.n_rb, 4)
    acc = np.zeros((m, bp.n_in), np.float32)
    q = part.n_rb_local
    for s in range(k):
        dy_s = dyb[:, s * q:(s + 1) * q].reshape(m, -1)
        dx_s = csd_spmm_dx(
            jax.numpy.asarray(dy_s), jax.numpy.asarray(ws[s]),
            part.out_idx[s], part.out_slot[s],
            out_valid=part.out_valid[s], block_m=2, interpret=True)
        acc += np.asarray(dx_s)
    np.testing.assert_allclose(acc, dx_full, atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# 8-device parity: sharded csd_matmul fwd + VJP, 4-D and 5-D slabs
# ---------------------------------------------------------------------------

_PARITY_PRELUDE = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import make_block_pattern
    from repro.kernels import ops

    bp = make_block_pattern(8 * 4, 16 * 4, 0.5, block_in=4, block_out=4,
                            seed=0)
    mesh = make_mesh((8,), ("model",))
    ks = jax.random.split(jax.random.key(0), 3)

    def check(mk_args, backends, acts):
        worst = 0.0
        for act in acts:
            for kw in backends:
                w, x, b = mk_args()
                f0 = lambda w, x, b: ops.csd_matmul(
                    x, w, bp, bias=b, activation=act, **kw)
                f1 = lambda w, x, b: ops.csd_matmul(
                    x, w, bp, bias=b, activation=act, mesh=mesh,
                    axis="model", **kw)
                y0, y1 = f0(w, x, b), f1(w, x, b)
                worst = max(worst, float(jnp.abs(y0 - y1).max()))
                loss = lambda f: (lambda w, x, b:
                                  jnp.sum(jnp.sin(f(w, x, b))))
                g0 = jax.grad(loss(f0), argnums=(0, 1, 2))(w, x, b)
                g1 = jax.grad(loss(f1), argnums=(0, 1, 2))(w, x, b)
                for a, c in zip(g0, g1):
                    worst = max(worst, float(jnp.abs(a - c).max()))
        print("WORST", worst)
"""


def test_unsharded_pallas_kernels_run_replicated_under_a_mesh():
    """A Pallas kernel with no sharded form (a junction whose block-rows
    do not divide the axis, the paged decode) runs whole on every device
    of a mesh — XLA cannot partition a Mosaic kernel — and matches the
    single-device result, forward and gradient."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import make_block_pattern
    from repro.kernels import ops
    from repro.kernels.flash_attention import paged_decode_attention

    mesh = make_mesh((4,), ("model",))
    ks = jax.random.split(jax.random.key(0), 6)
    bp = make_block_pattern(8 * 4, 6 * 4, 0.5, block_in=4, block_out=4,
                            seed=0)        # n_rb = 6: no 4-way shard
    x = jax.random.normal(ks[0], (6, bp.n_in))
    w = jax.random.normal(ks[1], (bp.n_rb, bp.d_in_b, 4, 4))
    b = jax.random.normal(ks[2], (bp.n_out,))
    kw = dict(activation="gelu", backend="pallas", block_m=2,
              interpret=True)
    f0 = lambda x, w, b: ops.csd_matmul(x, w, bp, bias=b, **kw)
    f1 = lambda x, w, b: ops.csd_matmul(x, w, bp, bias=b, mesh=mesh, **kw)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    worst = float(jnp.abs(f0(x, w, b) - jax.jit(f1)(x, w, b)).max())
    g0 = jax.grad(loss(f0), argnums=(0, 1, 2))(x, w, b)
    g1 = jax.jit(jax.grad(loss(f1), argnums=(0, 1, 2)))(x, w, b)
    for a, c in zip(g0, g1):
        worst = max(worst, float(jnp.abs(a - c).max()))

    # paged decode with the page pool sharded over the mesh
    q = jax.random.normal(ks[3], (2, 2, 3, 8))
    kp = jax.random.normal(ks[4], (8, 4, 2, 8))
    vp = jax.random.normal(ks[5], (8, 4, 2, 8))
    table = jnp.array([[1, 4, 2, -1], [0, 3, -1, -1]], jnp.int32)
    lengths = jnp.array([11, 6], jnp.int32)
    pool = NamedSharding(mesh, P("model"))
    d0 = paged_decode_attention(q, kp, vp, table, lengths,
                                backend="pallas", interpret=True)
    d1 = jax.jit(lambda q, kp, vp: paged_decode_attention(
        q, kp, vp, table, lengths, backend="pallas", interpret=True,
        mesh=mesh))(q, jax.device_put(kp, pool), jax.device_put(vp, pool))
    worst = max(worst, float(jnp.abs(d0 - d1).max()))
    print("WORST", worst)
    """, devices=4)
    assert float(out.split("WORST")[1].split()[0]) < 1e-5, out


@pytest.mark.slow
def test_sharded_csd_matmul_parity_4d_8dev():
    out = run_sub(_PARITY_PRELUDE + """
    def mk():
        x = jax.random.normal(ks[0], (6, bp.n_in))
        w = jax.random.normal(ks[1], (bp.n_rb, bp.d_in_b, 4, 4))
        b = jax.random.normal(ks[2], (bp.n_out,))
        return w, x, b
    check(mk,
          [dict(backend="xla"),
           dict(backend="pallas", block_m=2, interpret=True)],
          [None, "relu", "gelu"])
    """)
    assert float(out.split("WORST")[1].split()[0]) < 1e-4, out


@pytest.mark.slow
def test_sharded_csd_matmul_parity_5d_8dev():
    out = run_sub(_PARITY_PRELUDE + """
    def mk():
        E = 3
        x = jax.random.normal(ks[0], (E, 6, bp.n_in))
        w = jax.random.normal(ks[1], (E, bp.n_rb, bp.d_in_b, 4, 4))
        b = jax.random.normal(ks[2], (E, bp.n_out))
        return w, x, b
    check(mk,
          [dict(backend="xla"),
           dict(backend="pallas", block_m=2, interpret=True)],
          [None, "gelu"])
    """)
    assert float(out.split("WORST")[1].split()[0]) < 1e-4, out


@pytest.mark.slow
def test_sharded_quant_matmul_parity_4d_5d_8dev():
    """Int8 junction under the 8-way shard_map (slab + per-block scales
    both chunked on the block-row dim) == the single-device int8 path,
    4-D and 5-D, both backends. Forward-only: the quant path is
    inference-only by contract."""
    out = run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import make_block_pattern
    from repro.core.quant import quantize_slab
    from repro.kernels import ops

    bp = make_block_pattern(8 * 4, 16 * 4, 0.5, block_in=4, block_out=4,
                            seed=0)
    mesh = make_mesh((8,), ("model",))
    ks = jax.random.split(jax.random.key(0), 3)
    worst = 0.0
    for batched in (False, True):
        if batched:
            x = jax.random.normal(ks[0], (3, 6, bp.n_in))
            w = jax.random.normal(ks[1], (3, bp.n_rb, bp.d_in_b, 4, 4))
            b = jax.random.normal(ks[2], (3, bp.n_out))
        else:
            x = jax.random.normal(ks[0], (6, bp.n_in))
            w = jax.random.normal(ks[1], (bp.n_rb, bp.d_in_b, 4, 4))
            b = jax.random.normal(ks[2], (bp.n_out,))
        q, s = quantize_slab(w)
        for kw in (dict(backend="xla"),
                   dict(backend="pallas", block_m=2, interpret=True)):
            y0 = ops.csd_matmul(x, q, bp, bias=b, activation="relu",
                                w_scale=s, **kw)
            y1 = ops.csd_matmul(x, q, bp, bias=b, activation="relu",
                                w_scale=s, mesh=mesh, axis="model", **kw)
            worst = max(worst, float(jnp.abs(y0 - y1).max()))
    print("WORST", worst)
    """)
    assert float(out.split("WORST")[1].split()[0]) < 1e-4, out


@pytest.mark.slow
def test_sharded_engine_int8_decode_parity_8dev():
    """ISSUE acceptance (sharded leg): the int8 engine under an 8-way
    SERVE mesh — quantized slabs + scale siblings placed by the extended
    spec, int8 KV pools + per-token scale pools partitioned on the same
    axis — decodes token-identically to the single-device int8 engine."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.quant import QuantConfig
        from repro.nn import ModelConfig, SparsityConfig, build_model
        from repro.serving import EngineConfig, ServingEngine

        sp = SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                            block_in=8, block_out=8, backend="xla")
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                          d_ff=64, vocab_size=128, attn_chunk=8,
                          loss_chunk=8, dtype="float32", remat=False,
                          sparsity=sp)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 11, 8, 3)]
        ecfg = EngineConfig(max_slots=4, page_size=4, total_pages=31,
                            max_pages_per_seq=8, token_budget=16,
                            prefill_chunk=8, backend="xla",
                            quant=QuantConfig())
        ref = ServingEngine(model, params, ecfg).run(prompts, 12)

        mesh = make_mesh((8,), ("model",))
        eng = ServingEngine(model, params, ecfg, mesh=mesh)
        slabs = [l for l in jax.tree.leaves(eng.params)
                 if l.dtype == jnp.int8]
        assert slabs, "engine did not quantize at load"
        up = eng.params["stack"]["scan"][0]["ffn"]["up"]
        wq, ws = up["w"], up["w_scale"]
        chunked = all(
            s.data.shape[1] == wq.shape[1] // 8
            for s in wq.addressable_shards) and all(
            s.data.shape[1] == ws.shape[1] // 8
            for s in ws.addressable_shards)
        print("SLABCHUNKED", chunked)
        blk = eng.cache["scan"][0]["self"]
        kp, ks = blk["k_pages"], blk["k_scale"]
        kvq = kp.dtype == jnp.int8 and all(
            s.data.shape[1] == kp.shape[1] // 8
            for s in kp.addressable_shards) and all(
            s.data.shape[1] == ks.shape[1] // 8
            for s in ks.addressable_shards)
        print("KVCHUNKED", kvq)
        got = eng.run(prompts, 12)
        same = all(a.tolist() == b.tolist() for a, b in zip(ref, got))
        print("TOKENPARITY", same)
    """, devices=8)
    assert "SLABCHUNKED True" in out, out
    assert "KVCHUNKED True" in out, out
    assert "TOKENPARITY True" in out, out


# ---------------------------------------------------------------------------
# sharded train step parity + checkpoint round-trip
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_train_step_loss_parity_and_slab_chunking():
    """(2 data x 4 model) sharded train step of a sparse LM == unsharded
    step; the slab rule must actually chunk sparse weights + Adam state
    on the block-row dim."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.nn import ModelConfig, SparsityConfig, build_model
        from repro.nn.common import mesh_context
        from repro.optim import AdamWConfig, adam
        from repro.launch import specs
        from repro.sharding import policy

        sp = SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                            block_in=8, block_out=8, backend="xla")
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                          d_ff=64, vocab_size=128, attn_chunk=8,
                          loss_chunk=8, dtype="float32", remat=False,
                          sparsity=sp)
        model = build_model(cfg)
        assert model.stack.unit_blocks[0].ffn.up.is_sparse
        params = model.init(jax.random.key(0))
        opt = adam.init(params)
        tokens = jax.random.randint(jax.random.key(1), (8, 32), 0, 128)
        batch = {"tokens": tokens, "labels": tokens}
        step = specs.make_train_step(model, AdamWConfig(lr=1e-3,
                                                        warmup_steps=0))
        p_ref, o_ref, m_ref = jax.jit(step)(params, opt, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = policy.rules_for("train", 8, mesh, cfg)
        assert rules["slab"] == "model"
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
        # the up-projection slab (n_rb=8 block-rows) and its Adam state
        # must be chunked 4-ways on the block-row dim
        up = p2["stack"]["scan"][0]["ffn"]["up"]["w"]
        assert up.ndim == 5  # (layers, n_rb, d_in_b, bL, bR)
        shard_shapes = {s.data.shape for s in up.addressable_shards}
        print("CHUNKED", all(sh[1] == up.shape[1] // 4
                             for sh in shard_shapes))
    """, devices=8)
    # one Adam step at lr=1e-3 moves params by ~lr; reduction-order noise
    # flips low bits of the update, so the budget is a few ulps of lr.
    # Keep this tight: a missing dw/db psum over the data axis (sparselint
    # SL205) produces ~lr-scale divergence that 5e-3 would let through
    assert float(out.split("MAXERR")[1].split()[0]) < 5e-4, out
    assert float(out.split("LOSSDIFF")[1].split()[0]) < 1e-4, out
    assert "CHUNKED True" in out, out


@pytest.mark.slow
def test_sharded_checkpoint_roundtrip_8dev():
    """Sharded params + Adam state survive a save/restore cycle with
    their shardings reapplied (restore device_puts per-leaf)."""
    out = run_sub("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from repro.nn import ModelConfig, SparsityConfig, build_model
        from repro.optim import adam
        from repro.sharding import policy
        from repro.train.checkpoint import CheckpointManager

        sp = SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                            block_in=8, block_out=8, backend="xla")
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                          d_ff=64, vocab_size=128, dtype="float32",
                          remat=False, sparsity=sp)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        opt = adam.init(params)
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = policy.rules_for("train", 8, mesh, cfg)
        pspec = policy.param_pspecs(model.spec(), rules)
        p_sh = policy.named(mesh, pspec, params)
        o_sh = policy.named(mesh, policy.opt_pspecs(pspec), opt)
        params = jax.device_put(params, p_sh)
        opt = jax.device_put(opt, o_sh)

        with tempfile.TemporaryDirectory() as d:
            ckpt = CheckpointManager(d, keep=1)
            ckpt.save(7, (params, opt))
            (p2, o2), _ = ckpt.restore(7, (params, opt), (p_sh, o_sh))
        err = max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
        err = max(err, max(float(jnp.abs(a - b).max()) for a, b in
                  zip(jax.tree.leaves(opt), jax.tree.leaves(o2))))
        print("MAXERR", err)
        same = all(a.sharding == b.sharding for a, b in
                   zip(jax.tree.leaves(params), jax.tree.leaves(p2)))
        print("SHARDINGS", same)
    """, devices=8)
    assert float(out.split("MAXERR")[1].split()[0]) == 0.0, out
    assert "SHARDINGS True" in out, out


# ---------------------------------------------------------------------------
# sharded engine decode parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_sharded_engine_decode_token_parity_8dev():
    """Acceptance: a ServingEngine built under an 8-way SERVE mesh (slab-
    sharded junctions + pages partitioned on the same axis) produces
    token-identical greedy decodes to the single-device engine on a
    mixed-length sparse batch."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.nn import ModelConfig, SparsityConfig, build_model
        from repro.serving import EngineConfig, ServingEngine

        sp = SparsityConfig(enabled=True, rho_ffn=(0.5, 1.0),
                            block_in=8, block_out=8, backend="xla")
        cfg = ModelConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                          d_ff=64, vocab_size=128, attn_chunk=8,
                          loss_chunk=8, dtype="float32", remat=False,
                          sparsity=sp)
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, 128, n).astype(np.int32)
                   for n in (5, 11, 8, 3)]
        # total_pages = 31 -> the (P+1)-page pools divide 8 and the KV
        # pages really partition (context-parallel KV)
        ecfg = EngineConfig(max_slots=4, page_size=4, total_pages=31,
                            max_pages_per_seq=8, token_budget=16,
                            prefill_chunk=8, backend="xla")
        ref = ServingEngine(model, params, ecfg).run(prompts, 12)

        mesh = make_mesh((8,), ("model",))
        eng = ServingEngine(model, params, ecfg, mesh=mesh)
        assert eng.rules["slab"] == "model"
        kp = eng.cache["scan"][0]["self"]["k_pages"]
        # pages dim (P+1 = 32) must really be chunked 8 ways
        chunked = all(s.data.shape[1] == kp.shape[1] // 8
                      for s in kp.addressable_shards)
        print("KVCHUNKED", chunked)
        got = eng.run(prompts, 12)
        same = all(a.tolist() == b.tolist() for a, b in zip(ref, got))
        print("TOKENPARITY", same)
    """, devices=8)
    assert "TOKENPARITY True" in out, out
    assert "KVCHUNKED True" in out, out
