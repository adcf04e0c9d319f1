"""Training driver: ``python -m repro.launch.train --arch <id> [...]``.

Single-host entry point (on a real cluster each host runs this under
``jax.distributed.initialize()``; the mesh/axis logic is identical). Smoke
scale by default so it runs on CPU; pass --full for the published config.

Fault tolerance: the step loop runs under ``RestartLoop`` — any RuntimeError
(device loss on real hardware; injectable in tests) triggers
checkpoint-restore and continue. ``--simulate-failure-at N`` demonstrates
the restart path end-to-end.
"""
from __future__ import annotations

import argparse
import os
from functools import partial

import numpy as np

from .mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="published config (needs real TPUs)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--diloco", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--mesh", default=None,
                    help="e.g. '2,2x data,model' for a local device mesh")
    ap.add_argument("--simulate-failure-at", type=int, default=0)
    ap.add_argument("--rho", type=float, default=None,
                    help="override FFN sparsity density (paper's rho)")
    ap.add_argument("--profile-dir", default=None,
                    help="capture a jax.profiler trace of the run here")
    ap.add_argument("--metrics-jsonl", default=None,
                    help="append obs registry events to this JSONL file")
    args = ap.parse_args()

    from ..configs import get_config
    from ..data import BigramLM
    from ..device import init_compile_cache
    from ..nn import build_model
    from ..nn.common import SparsityConfig
    from ..optim import AdamWConfig
    from ..train import RestartLoop, RestartPolicy, Trainer, TrainerConfig

    init_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    if args.rho is not None:
        sp = cfg.sparsity
        cfg = cfg.with_(sparsity=SparsityConfig(
            enabled=args.rho < 1.0, rho_ffn=(args.rho, min(1.0, args.rho * 1.5)),
            block_in=sp.block_in, block_out=sp.block_out))
    model = build_model(cfg)

    mesh = None
    if args.mesh:
        shape_s, axes_s = args.mesh.split("x ")
        shape = tuple(int(x) for x in shape_s.split(","))
        axes = tuple(axes_s.split(","))
        mesh = make_mesh(shape, axes)

    if args.metrics_jsonl:
        from ..obs import get_registry
        get_registry().set_jsonl(args.metrics_jsonl)

    tc = TrainerConfig(
        opt=AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 1),
                        total_steps=args.steps),
        grad_accum=args.grad_accum,
        diloco_period=args.diloco,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every,
        profile_dir=args.profile_dir,
    )
    trainer = Trainer(model, tc, mesh=mesh)
    data = BigramLM(vocab_size=cfg.vocab_size, seed=0)

    def make_iter(start):
        it = data.iterate(args.batch, args.seq, start_step=start)
        if cfg.input_mode == "embeddings" or cfg.enc_dec is not None:
            rng = np.random.default_rng(0)

            def gen():
                for b in it:
                    b["embeds"] = rng.normal(
                        size=(args.batch, args.seq, cfg.frontend_dim)
                    ).astype(np.float32)
                    yield b
            return gen()
        return it

    log = partial(print, flush=True)
    state = {"params": None, "opt": None, "failed": False}

    fail_at = args.simulate_failure_at

    def run():
        start = (trainer.ckpt.latest_step() or 0) if trainer.ckpt else 0
        it = make_iter(start)
        steps = args.steps
        if fail_at and not state["failed"] and start < fail_at <= steps:
            state["failed"] = True
            # run to the failure point, then raise like a lost device
            p, o, h = trainer.fit(it, fail_at, resume=True,
                                  on_step=lambda s, m: log(f"step {s}: {m}"))
            raise RuntimeError("simulated device loss")
        p, o, h = trainer.fit(it, steps, resume=True,
                              on_step=lambda s, m: log(f"step {s}: {m}"))
        state["params"], state["opt"] = p, o

    if args.checkpoint_dir:
        loop = RestartLoop(
            RestartPolicy(checkpoint_every=args.checkpoint_every),
            save_fn=lambda s: None,     # trainer checkpoints internally
            restore_fn=lambda: (trainer.ckpt.latest_step() or 0))
        tries = 0
        while True:
            try:
                run()
                break
            except RuntimeError as e:
                tries += 1
                log(f"[restart] {e} — resuming from checkpoint "
                    f"(attempt {tries})")
                if tries > 3:
                    raise
    else:
        run()
    log("training done")


if __name__ == "__main__":
    main()
