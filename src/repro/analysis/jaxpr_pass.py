"""Pass 2 — jaxpr lint of the jitted hot paths.

Traces the *real* executables — the trainer's jitted step (via
``Trainer._make_step``) and the serving engine's ``raw_step`` around
``LM.paged_step`` — for every registered config (smoke variant: same
structural flags, small dims; the jaxpr's *op population* is what we lint,
and that is scale-invariant). Under a forced multi-device mesh the traces
run inside ``mesh_context``, so the sharded junction ``shard_map`` bodies
appear in the jaxpr and get the collective check.

Checks:

* **SL201** — host-sync primitives (``pure_callback``/``io_callback``/
  ``debug_callback``/infeed/outfeed) inside a step: each one stalls the
  TPU pipeline on a host round-trip every step.
* **SL202** — donation: large inputs that the lowered executable does not
  alias to an output (``tf.aliasing_output``), and the regression class
  where a step donates *nothing* (double-buffered params/optimizer state
  = 2x HBM).
* **SL203** — wide-dtype creep: any float64/complex128 value in the
  traced program (a silent 2x memory + off-MXU penalty; nothing in this
  codebase should promote past f32).
* **SL204** — large closure-captured constants baked into the traced
  program. Python-side arrays that should be arguments (a recompile +
  HBM-resident-copy hazard every time the python value changes identity).
  The pattern index arrays are *meant* to be baked in (they define the
  program, per the paper's pre-defined sparsity premise) and stay far
  under the threshold.
* **SL205** — ``shard_map`` bodies whose out-specs drop a mesh axis that
  some input is mapped over, without any collective over that axis in the
  body. With ``check_vma=False`` (which the sharded junctions need), jax
  does NOT verify this — a missing ``psum`` yields per-device partial
  sums silently passed off as the full result (the PR-4 bug class).
* **SL206** — quantization-defeating upcast: a ``convert_element_type``
  whose int8 input is a *whole* registered slab / KV page pool (exact
  shape match against the traced step's int8 inputs, plus their
  shard-local variants). Dequantizing the full tensor up front
  materializes an f32 copy in HBM and erases the 4x bandwidth win the
  int8 path exists for; healthy paths convert only per-slot / per-page
  tiles (rank-3 slices in the XLA fallback, in-register tiles in the
  Pallas kernels), which never match a full-slab shape.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from .findings import Finding

HOST_SYNC_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed"}
WIDE_DTYPES = ("float64", "complex128")
COLLECTIVE_PRIMS = {"psum", "pmax", "pmin", "all_gather", "all_to_all",
                    "reduce_scatter", "psum_scatter", "ppermute",
                    "pbroadcast"}
DEFAULT_CONST_THRESHOLD = 1 << 20   # 1 MiB
DEFAULT_DONATE_THRESHOLD = 1 << 20  # 1 MiB


# -- jaxpr walking ----------------------------------------------------------


def _sub_jaxprs(params: Dict[str, Any]):
    """Yield every jaxpr nested in an eqn's params (scan/cond/pjit/
    shard_map/custom_vjp bodies alike), as raw ``Jaxpr`` objects."""
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for x in vs:
            if hasattr(x, "jaxpr"):     # ClosedJaxpr
                yield x.jaxpr
            elif hasattr(x, "eqns"):    # raw Jaxpr
                yield x


def _iter_eqns(jaxpr, *, into_shard_map=True):
    """All eqns, depth first. ``into_shard_map=False`` stops at shard_map
    boundaries (their bodies get their own dedicated check)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "shard_map" and not into_shard_map:
            continue
        for sub in _sub_jaxprs(eqn.params):
            yield from _iter_eqns(sub, into_shard_map=into_shard_map)


def _collective_axes(jaxpr) -> Set[str]:
    """Mesh axis names any collective in ``jaxpr`` (recursively) reduces
    or permutes over."""
    axes: Set[str] = set()
    for eqn in _iter_eqns(jaxpr):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            for k in ("axes", "axis_name", "axis_index_groups_axis"):
                v = eqn.params.get(k)
                if v is None:
                    continue
                for a in (v if isinstance(v, (list, tuple)) else (v,)):
                    if isinstance(a, str):
                        axes.add(a)
    return axes


def _spec_axes(spec) -> Set[str]:
    """Flatten one shard_map in_specs/out_specs entry (a PartitionSpec
    whose dims name a mesh axis, a tuple of axes, or None) to the set of
    mesh axes it maps."""
    out: Set[str] = set()
    for axes in spec:
        if axes is not None:
            out.update(axes if isinstance(axes, (list, tuple)) else (axes,))
    return out


def lint_closed_jaxpr(closed, subject: str,
                      const_threshold: int = DEFAULT_CONST_THRESHOLD
                      ) -> List[Finding]:
    """SL201/SL203/SL204/SL205 over one traced program."""
    f: List[Finding] = []
    jaxpr = closed.jaxpr

    # SL204: large baked-in constants
    for c in getattr(closed, "consts", ()):
        nbytes = int(np.prod(getattr(c, "shape", ()) or (1,))) * \
            np.dtype(getattr(c, "dtype", np.float32)).itemsize
        if nbytes > const_threshold:
            f.append(Finding(
                "SL204", subject,
                f"closure-captured constant {getattr(c, 'shape', '?')} "
                f"{getattr(c, 'dtype', '?')} ({nbytes} B) baked into the "
                "traced program — pass it as an argument (recompile + "
                "resident-copy hazard)", {"bytes": nbytes}))

    seen_sync = set()
    seen_wide = set()
    for eqn in _iter_eqns(jaxpr):
        name = eqn.primitive.name
        # SL201: host-sync ops
        if name in HOST_SYNC_PRIMS and name not in seen_sync:
            seen_sync.add(name)
            cb = eqn.params.get("callback")
            f.append(Finding(
                "SL201", subject,
                f"host-sync primitive '{name}'"
                + (f" ({cb})" if cb is not None else "")
                + " inside the jitted step: stalls the device pipeline on "
                "a host round-trip every step", {}))
        # SL203: wide-dtype creep
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            dt = str(getattr(aval, "dtype", ""))
            if dt in WIDE_DTYPES and (name, dt) not in seen_wide:
                seen_wide.add((name, dt))
                f.append(Finding(
                    "SL203", subject,
                    f"primitive '{name}' produces {dt} "
                    f"{getattr(aval, 'shape', ())} — unintended wide-dtype "
                    "promotion", {"dtype": dt}))
        # SL205: shard_map missing-collective
        if name == "shard_map":
            f.extend(_lint_shard_map(eqn, subject))
    return f


def _int8_slab_shapes(closed, mesh) -> Set[Tuple[int, ...]]:
    """Shapes of whole int8 slabs / KV page pools entering the traced
    program (int8 inputs of rank >= 4), plus their shard-local variants:
    under the junction/cache shard_map the leading block-row (or expert /
    page) dim arrives divided by the model-axis size."""
    shapes: Set[Tuple[int, ...]] = set()
    n = int(mesh.shape["model"]) if mesh is not None \
        and "model" in mesh.axis_names else 1
    for var in closed.jaxpr.invars:
        aval = getattr(var, "aval", None)
        if aval is None or str(getattr(aval, "dtype", "")) != "int8" \
                or len(getattr(aval, "shape", ())) < 4:
            continue
        shapes.add(tuple(aval.shape))
        if n > 1:
            for d in (0, 1):
                if aval.shape[d] % n == 0:
                    local = list(aval.shape)
                    local[d] //= n
                    shapes.add(tuple(local))
    return shapes


def _lint_quant(closed, subject: str, mesh) -> List[Finding]:
    """SL206 over one traced program (no-op when it has no int8 slabs)."""
    slab_shapes = _int8_slab_shapes(closed, mesh)
    f: List[Finding] = []
    if not slab_shapes:
        return f
    seen: Set[Tuple[int, ...]] = set()
    for eqn in _iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        aval = getattr(eqn.invars[0], "aval", None)
        if aval is None or str(getattr(aval, "dtype", "")) != "int8":
            continue
        shp = tuple(getattr(aval, "shape", ()))
        if shp in slab_shapes and shp not in seen:
            seen.add(shp)
            f.append(Finding(
                "SL206", subject,
                f"whole int8 slab {shp} upcast to "
                f"{eqn.params.get('new_dtype')} — a full-width copy of "
                "the quantized tensor enters HBM traffic, erasing the "
                "int8 bandwidth win; dequantize per-slot/per-page inside "
                "the junction instead", {"shape": shp}))
    return f


def _lint_shard_map(eqn, subject: str) -> List[Finding]:
    f: List[Finding] = []
    params = eqn.params
    body = params.get("jaxpr")
    if hasattr(body, "jaxpr"):
        body = body.jaxpr
    if body is None:
        return f
    mapped_in: Set[str] = set()
    for spec in params["in_specs"]:
        mapped_in |= _spec_axes(spec)
    if not mapped_in:
        return f  # fully replicated body: no reduction obligation
    have = _collective_axes(body)
    for o, spec in enumerate(params["out_specs"]):
        missing = mapped_in - _spec_axes(spec) - have
        for ax in sorted(missing):
            f.append(Finding(
                "SL205", subject,
                f"shard_map out[{o}] is unmapped over mesh axis '{ax}' "
                f"but some input is mapped over it and the body has no "
                f"collective over '{ax}' — per-device partials would be "
                "passed off as the reduced result (check_vma=False hides "
                "this)", {"axis": ax, "out": o}))
    return f


# -- donation (SL202) -------------------------------------------------------

# the attr dict can contain quoted strings with nested braces, e.g.
# mhlo.sharding = "{devices=[2,4]<=[8]}" — consume strings atomically
_ARG_RE = re.compile(
    r"%arg(\d+): tensor<[^>]*>\s*(\{(?:[^}\"]|\"[^\"]*\")*\})?")


def lint_donation(lowered_text: str, in_avals, subject: str,
                  threshold: int = DEFAULT_DONATE_THRESHOLD
                  ) -> List[Finding]:
    """Parse the lowered StableHLO signature for ``tf.aliasing_output``
    markers and flag large non-donated inputs (``in_avals`` is the traced
    call's argument pytree of ShapeDtypeStructs)."""
    import jax

    f: List[Finding] = []
    seen: Dict[int, bool] = {}
    for m in _ARG_RE.finditer(lowered_text):
        i = int(m.group(1))
        if i not in seen:
            attrs = m.group(2) or ""
            seen[i] = ("tf.aliasing_output" in attrs
                       or "jax.buffer_donor" in attrs)
    if not seen:
        return f
    donated = {i for i, d in seen.items() if d}
    leaves = jax.tree_util.tree_flatten_with_path(in_avals)[0]
    if not donated:
        f.append(Finding(
            "SL202", subject,
            "step executable donates no input buffer at all — params/"
            "optimizer/cache state is double-buffered in HBM every step",
            {"n_args": len(seen)}))
        return f
    if len(leaves) != len(seen):
        return f  # pruned/unflattened args: index mapping unreliable
    for i, (path, aval) in enumerate(leaves):
        if i in donated:
            continue
        nbytes = int(np.prod(aval.shape or (1,))) * \
            np.dtype(aval.dtype).itemsize
        if nbytes > threshold:
            f.append(Finding(
                "SL202", subject,
                f"input {jax.tree_util.keystr(path)} "
                f"({aval.shape} {aval.dtype}, {nbytes} B) is not donated",
                {"bytes": nbytes}))
    return f


# -- tracing the registered configs ----------------------------------------


def _train_subject(name: str) -> str:
    return f"train_step[{name}]"


def _trace_train(name: str, mesh) -> Tuple[Any, Any, str]:
    """Trace the real trainer step for one config. Returns
    (traced, in_avals, subject)."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..nn.common import mesh_context
    from ..nn.model import build_model
    from ..optim import adam
    from ..train.trainer import Trainer, TrainerConfig

    cfg = get_config(name, smoke=True)
    model = build_model(cfg)
    # batch divisible by the full mesh (the batch rule shards it over
    # every data-like axis)
    b, s = 8, 32
    batch = {"tokens": np.zeros((b, s), np.int32),
             "labels": np.zeros((b, s), np.int32)}
    if cfg.input_mode == "embeddings" or cfg.enc_dec is not None:
        batch["embeds"] = np.zeros((b, s, cfg.frontend_dim), np.float32)
    trainer = Trainer(model, TrainerConfig(), mesh=mesh)
    step = trainer._make_step(batch)
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    o_avals = jax.eval_shape(adam.init, p_avals)
    b_avals = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape,
                                                          x.dtype), batch)
    args = (p_avals, o_avals, b_avals)
    if mesh is not None:
        with mesh, mesh_context(mesh, trainer.rules):
            traced = step.trace(*args)
    else:
        traced = step.trace(*args)
    return traced, args, _train_subject(name)


def _trace_paged(name: str, mesh) -> Optional[Tuple[Any, Any, str]]:
    """Trace the serving engine's step (``LM.paged_step`` under the
    engine's ``raw_step``/donation contract). None for configs that do not
    serve through the paged path (frontends / enc-dec)."""
    import jax

    from ..configs import get_config
    from ..nn.common import dtype_of, mesh_context
    from ..nn.model import build_model
    from ..sharding import policy

    cfg = get_config(name, smoke=True)
    if cfg.input_mode != "tokens" or cfg.enc_dec is not None:
        return None
    model = build_model(cfg)
    slots, pages, page_size, max_pages = 2, 8, 16, 4
    cache_avals = jax.eval_shape(
        lambda: model.stack.init_paged_cache(slots, pages, page_size,
                                             dtype_of(cfg)))
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    i32 = np.int32

    def raw_step(params, cache, page_table, tokens, pos, n_new, slot_ids):
        return model.paged_step(params, tokens, pos, n_new, cache,
                                page_table, slot_ids, backend="auto",
                                interpret=True)

    step = jax.jit(raw_step, donate_argnums=(1,))
    args = (p_avals, cache_avals,
            jax.ShapeDtypeStruct((slots, max_pages), i32),
            jax.ShapeDtypeStruct((slots, 1), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32))
    if mesh is not None:
        rules = policy.rules_for("decode", slots, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            traced = step.trace(*args)
    else:
        traced = step.trace(*args)
    return traced, args, f"paged_step[{name}]"


def _trace_verify(name: str, mesh) -> Optional[Tuple[Any, Any, str]]:
    """Trace the engine's speculative verify step: ``paged_step`` over a
    ``1 + spec_k`` token chunk with ``all_logits=True`` (greedy
    acceptance needs per-position logits, not just the last row). This
    is a distinct executable from the C==1 decode step — different token
    width, different attention path (chunk instead of paged-decode
    kernel) — so it is linted as its own subject. None for configs the
    engine never speculates on: recurrent (mamba) state cannot be rolled
    back, so ``spec_k`` is clamped to 0 there."""
    import jax

    from ..configs import get_config
    from ..nn.common import dtype_of, mesh_context
    from ..nn.model import build_model
    from ..sharding import policy

    cfg = get_config(name, smoke=True)
    if cfg.input_mode != "tokens" or cfg.enc_dec is not None:
        return None
    if "mamba" in cfg.layer_kinds:
        return None
    model = build_model(cfg)
    slots, pages, page_size, max_pages = 2, 8, 16, 4
    spec_k = 4
    cache_avals = jax.eval_shape(
        lambda: model.stack.init_paged_cache(slots, pages, page_size,
                                             dtype_of(cfg)))
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    i32 = np.int32

    def raw_verify(params, cache, page_table, tokens, pos, n_new,
                   slot_ids):
        return model.paged_step(params, tokens, pos, n_new, cache,
                                page_table, slot_ids, backend="auto",
                                interpret=True, all_logits=True)

    step = jax.jit(raw_verify, donate_argnums=(1,))
    args = (p_avals, cache_avals,
            jax.ShapeDtypeStruct((slots, max_pages), i32),
            jax.ShapeDtypeStruct((slots, 1 + spec_k), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32))
    if mesh is not None:
        rules = policy.rules_for("decode", slots, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            traced = step.trace(*args)
    else:
        traced = step.trace(*args)
    return traced, args, f"spec_verify[{name}]"


def _trace_quant(name: str, mesh) -> Optional[Tuple[Any, Any, str]]:
    """Trace the *quantized* serving step: params through
    ``quantize_tree`` (int8 slabs + per-block scales) and the paged cache
    built with ``quant_kv=True`` (int8 pages + per-token scales). The
    trace proves the executable the int8 engine actually runs keeps the
    slabs quantized end to end (SL206) on top of the standard SL20x
    checks. None for configs whose smoke variant has no block-sparse
    junction to quantize — there would be nothing int8 in the program."""
    import jax
    import jax.numpy as jnp

    from ..configs import get_config
    from ..core.quant import quantize_tree
    from ..nn.common import dtype_of, mesh_context
    from ..nn.model import build_model
    from ..sharding import policy

    cfg = get_config(name, smoke=True)
    if cfg.input_mode != "tokens" or cfg.enc_dec is not None:
        return None
    model = build_model(cfg)
    spec = model.spec()
    p_avals = jax.eval_shape(model.init, jax.random.key(0))
    q_avals = jax.eval_shape(lambda p: quantize_tree(p, spec)[0], p_avals)
    if not any(l.dtype == jnp.int8 for l in jax.tree.leaves(q_avals)):
        return None
    slots, pages, page_size, max_pages = 2, 8, 16, 4
    cache_avals = jax.eval_shape(
        lambda: model.stack.init_paged_cache(slots, pages, page_size,
                                             dtype_of(cfg), quant_kv=True))
    i32 = np.int32

    def raw_step(params, cache, page_table, tokens, pos, n_new, slot_ids):
        return model.paged_step(params, tokens, pos, n_new, cache,
                                page_table, slot_ids, backend="auto",
                                interpret=True)

    step = jax.jit(raw_step, donate_argnums=(1,))
    args = (q_avals, cache_avals,
            jax.ShapeDtypeStruct((slots, max_pages), i32),
            jax.ShapeDtypeStruct((slots, 1), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32),
            jax.ShapeDtypeStruct((slots,), i32))
    if mesh is not None:
        rules = policy.rules_for("decode", slots, mesh, cfg)
        with mesh, mesh_context(mesh, rules):
            traced = step.trace(*args)
    else:
        traced = step.trace(*args)
    return traced, args, f"quant_step[{name}]"


def _trace_quant_inject(mesh) -> Tuple[Any, Any, str]:
    """Selftest subject: a deliberately quantization-defeating junction
    that dequantizes the WHOLE int8 slab up front and feeds the f32 copy
    to ``csd_matmul``. The full-slab ``convert_element_type`` this
    produces MUST trip SL206 — CI runs it to prove the gate has teeth."""
    import jax
    import jax.numpy as jnp

    from ..core.block_pattern import make_block_pattern
    from ..core.quant import dequantize_slab
    from ..kernels import ops as kops

    bp = make_block_pattern(64, 64, 0.5, block_in=16, block_out=16, seed=0)
    x_aval = jax.ShapeDtypeStruct((4, 64), jnp.float32)
    w_aval = jax.ShapeDtypeStruct(
        (bp.n_rb, bp.d_in_b, 16, 16), jnp.int8)
    s_aval = jax.ShapeDtypeStruct((bp.n_rb, bp.d_in_b), jnp.float32)

    def bad(x, w, s):
        return kops.csd_matmul(x, dequantize_slab(w, s), bp,
                               backend="xla")

    traced = jax.jit(bad).trace(x_aval, w_aval, s_aval)
    return traced, (x_aval, w_aval, s_aval), "quant_inject[selftest]"


def run(config_names: Optional[Sequence[str]] = None,
        mesh_shape: Tuple[int, int] = (2, 4),
        const_threshold: int = DEFAULT_CONST_THRESHOLD,
        donate_threshold: int = DEFAULT_DONATE_THRESHOLD,
        inject: bool = False
        ) -> Tuple[List[Finding], List[str], List[str]]:
    """Lint the train and paged-serve steps of every registered config.

    Returns (findings, covered subjects, errors). A config that fails to
    trace is an *error* (gating): a hot path the linter cannot see is not
    a certified hot path.
    """
    import jax

    from ..configs import ARCHS

    n_dev = len(jax.devices())
    need = int(np.prod(mesh_shape))
    mesh = None
    errors: List[str] = []
    if n_dev >= need:
        from ..launch.mesh import make_mesh
        mesh = make_mesh(mesh_shape, ("data", "model"))
    else:
        errors.append(
            f"sharded-path lint skipped: {n_dev} device(s) < {need} "
            "(run via `python -m repro.analysis.lint`, which forces a "
            f"{need}-device host platform)")

    findings: List[Finding] = []
    covered: List[str] = []
    for name in (config_names or ARCHS):
        for tracer in (_trace_train, _trace_paged, _trace_verify,
                       _trace_quant):
            try:
                res = tracer(name, mesh)
            except Exception as e:
                errors.append(f"{tracer.__name__}[{name}]: "
                              f"{type(e).__name__}: {e}")
                continue
            if res is None:
                continue
            traced, in_avals, subject = res
            findings.extend(lint_closed_jaxpr(traced.jaxpr, subject,
                                              const_threshold))
            findings.extend(_lint_quant(traced.jaxpr, subject, mesh))
            try:
                text = traced.lower().as_text()
            except Exception as e:
                errors.append(f"lower[{subject}]: {type(e).__name__}: {e}")
            else:
                findings.extend(lint_donation(text, in_avals, subject,
                                              donate_threshold))
            covered.append(subject)
    if inject:
        try:
            traced, _, subject = _trace_quant_inject(mesh)
        except Exception as e:
            errors.append(f"_trace_quant_inject: {type(e).__name__}: {e}")
        else:
            findings.extend(_lint_quant(traced.jaxpr, subject, mesh))
            covered.append(subject)
    return findings, covered, errors
