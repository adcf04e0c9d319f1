"""Model FLOP utilisation of training: useful FLOPs of the traced steps
(forward + backward = 3x forward; experts chosen, not capacity padding;
no recomputation: ``bench/work.py``) over the traced window times the
chip's bf16 peak, in percent."""
from bench import work


def read(ctx):
    t = ctx["trace"]
    if not ctx["train_steps"] or t["window_s"] <= 0:
        return None
    flops = ctx["train_steps"] * work.train_useful_flops(
        ctx["geometry"], ctx["batch"], ctx["seq"])
    return 100.0 * flops / (t["window_s"] * ctx["peaks"]["bf16_flops"])
