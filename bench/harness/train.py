"""Training cells: the program's ``Trainer`` jitted step.

Set-up builds one object, the compiled step with its state: weights from
the seed (float32 master copy, as configured), Adam state, a pool of
distinct seeded batches on the device. It drives that step through its
first three steps on batches 0, 1, 2 (the compile happens there), reads
what ``correct`` compares, and hands the same step and state to the
window.

Window (``--seconds``): whole steps, each ending in
``block_until_ready``, until the first one that ends after the window's
length; the rate is every token of those steps over their time.

``correct``: the float32 reference follows the same three steps from the
same weights (``bench/reference/train.py``). Compared: each step's loss;
the per-leaf norm of the first (clipped) gradient, read from Adam's first
moment after step 1 (``m1 = (1 - b1) g``); the per-leaf norm of the
parameters' change after step 3. Leaves whose reference gradient is under
a thousandth of the median leaf's are left out of the change.
"""
from __future__ import annotations

import collections
import gc

import numpy as np

from . import common, lookup, weights
from .bigram import BigramLM
from .common import clock, log
from ..reference import pattern
from .serve import record_junctions, structure_checks


def leaf_gap(prog, ref, keep=None) -> tuple:
    """Worst leaf of |prog - ref| / max(ref, median ref) and its index."""
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    idx = np.arange(len(ref)) if keep is None else np.flatnonzero(keep)
    med = float(np.median(ref[idx]))
    g = np.abs(prog[idx] - ref[idx]) / np.maximum(ref[idx], med)
    i = int(np.argmax(g))
    return float(g[i]), int(idx[i])


def compare(prog: dict, ref: dict, names) -> dict:
    """The numbers ``correct`` compares, from the program's and the
    reference's readings of steps 1-3."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    g1, ig = leaf_gap(prog["g1"], ref["g1"])
    keep = ref["g1"] >= 1e-3 * np.median(ref["g1"])
    d3, idd = leaf_gap(prog["d3"], ref["d3"], keep)
    return {"loss_gap": loss, "grad1_leaf_gap": g1, "grad1_leaf": names[ig],
            "change3_leaf_gap": d3, "change3_leaf": names[idd],
            "left_out": [n for n, k in zip(names, keep) if not k]}


def run(cell, args, devs, t_start: float, fault=None,
        control: bool = False) -> tuple:
    """One run of a training cell. ``fault`` (tests and calibration) wraps
    the jitted step to break it; ``control`` (calibration only) runs the
    control in the program's place: the program's own bfloat16 path for
    the master weights, one step below the configuration's float32.
    Returns (result, checks)."""
    import jax
    import jax.numpy as jnp

    from repro.nn import build_model
    from repro.optim import AdamWConfig, adam
    from repro.train import Trainer, TrainerConfig

    setup = collections.OrderedDict()
    compiles = common.CompileCounter()
    compiles.active = True
    job = cell.traffic
    cc = cell.cell
    B, S = int(job["batch"]), int(job["seq"])

    t = clock()
    mcfg = dict(cell.config["model"])
    if control:
        mcfg["param_dtype"] = "bfloat16"
        log("[control] the program with bfloat16 master weights")
    cfg = lookup.model_config(mcfg)
    model = build_model(cfg)
    record_junctions(model, cell.config)
    tr = Trainer(model, TrainerConfig(opt=AdamWConfig(**cc["opt"])))
    setup["build_s"] = clock() - t

    t = clock()
    params = weights.make_params(model, args.seed)
    opt = jax.jit(adam.init)(params)
    jax.block_until_ready((params, opt))
    setup["weights_s"] = clock() - t

    t = clock()
    data = BigramLM(cfg.vocab_size, args.seed, int(job["branching"]),
                    float(job["noise"]))
    host_batches = [data.batch(i, B, S) for i in range(int(job["pool"]))]
    batches = [{k: jnp.asarray(v) for k, v in b.items()}
               for b in host_batches]
    jax.block_until_ready(batches)
    setup["data_s"] = clock() - t

    step = tr.step_fn(batches[0])
    if fault is not None:
        step = fault(step)
    names = weights.leaf_names(model)
    b1 = float(cc["opt"]["b1"])
    gnorm = jax.jit(lambda m: jnp.stack(
        [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(m)]))

    # the first three steps: compile, and the readings `correct` compares
    t = clock()
    losses = []
    for i in range(3):
        params, opt, m = step(params, opt, batches[i])
        losses.append(float(m["loss"]))
        if i == 0:
            g1 = np.asarray(gnorm(opt["m"])) / (1.0 - b1)
    d3 = weights.diff_norms(model, args.seed, params)
    prog = {"losses": losses, "g1": g1, "d3": d3}
    setup["first_steps_s"] = clock() - t
    setup_s = clock() - t_start
    log("[setup] " + ", ".join(f"{k} {v:.3f}" for k, v in setup.items())
        + f"; setup_s {setup_s:.3f}")
    log(f"[train] losses of steps 1-3: {losses}")

    compiles.report_and_reset("setup")
    tracer = common.Trace() if args.trace else None
    seconds = float(args.seconds)
    tr_steps = int(cc.get("trace_steps", 3))
    times = []
    compiles.active = True
    t0 = clock()
    i = 3
    traced = 0
    while True:
        if tracer is not None and not tracer.on and tracer.t0 is None \
                and clock() - t0 >= seconds / 2:
            tracer.start()
        with jax.profiler.TraceAnnotation("bench/train_step"):
            params, opt, m = step(params, opt,
                                  batches[i % len(batches)])
            jax.block_until_ready(m)
        i += 1
        times.append(clock() - t0)
        if tracer is not None and tracer.on:
            traced += 1
            if traced >= tr_steps:
                tracer.stop()
        if times[-1] >= seconds and (tracer is None or tracer.t0 is not None
                                     and not tracer.on):
            break
    window_s = clock() - t0
    compiles.active = False
    n_steps = len(times)
    log(f"[window] {n_steps} steps in {window_s:.3f} s, last loss "
        f"{float(m['loss']):.4f}, {compiles.n} compiles in the window "
        f"{compiles.names[:8]}")

    info = common.device_info(devs)
    metrics = {}
    if not args.trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        metrics["train_tokens_per_s"] = {
            "value": n_steps * B * S / window_s, "unit": "tokens/s"}

    del params, opt, m, step, tr
    gc.collect()

    if tracer is not None:
        from .. import trace_reduce
        from ..peaks import peaks_for
        from .serve import geometry

        red = trace_reduce.reduce(tracer.file())
        tracer.remove()
        log(f"[trace] kernel families: seconds {red['families']}, events "
            f"{red['family_events']}")
        info["busy_s"] = red["busy_s"]
        info["window_s"] = red["window_s"]
        result_bd = {"device_ops": red["device_ops"][:10],
                     "idle_gaps": red["idle_gaps"][:10]}
        ctx = {"trace": red, "train_steps": traced, "batch": B, "seq": S,
               "geometry": geometry(model), "model": model,
               "peaks": peaks_for(info["kind"]), "cell": cell}
        metrics.update(common.per_layer_metrics(cell, ctx))

    # the reference: three steps from the same weights, after the
    # program's state is freed
    t = clock()
    pats = pattern.tables(cell.config)
    ref_mod = cell.reference()

    with common.highest_precision():
        ref = ref_mod.three_steps(weights.make_params(model, args.seed),
                                  batches[:3], cell.config["model"], pats,
                                  cc["opt"])
    p = ref.pop("p")
    ref["d3"] = weights.diff_norms(model, args.seed, p)
    del p
    cmp = compare(prog, ref, names)
    log(f"[correct] reference {clock() - t:.3f} s; losses {ref['losses']}; "
        f"worst gradient leaf {cmp['grad1_leaf']} (gap "
        f"{cmp['grad1_leaf_gap']!r}), worst change leaf "
        f"{cmp['change3_leaf']}; left out of the change: "
        f"{cmp['left_out']}")
    lim = cc["correct"]
    checks = {k: {"value": cmp[k], "limit": float(lim[k]), "rule": "<="}
              for k in ("loss_gap", "grad1_leaf_gap", "change3_leaf_gap")
              if k in lim}
    checks.update(structure_checks(model, cell.config))
    result = {"correct": common.judge(checks), "attempted": n_steps,
              "failed": 0, "metrics": metrics, "device": info}
    if tracer is not None:
        result["breakdown"] = result_bd
    return result, checks
