"""Model FLOP utilisation of the serving window: the useful forward FLOPs
of every device call in the traced window (real tokens at density rho,
real causal context, logits only where used: ``bench/work.py``) over the
window's length times the chip's bf16 peak, in percent."""
from bench import work


def read(ctx):
    t = ctx["trace"]
    if not ctx["steps"] or t["window_s"] <= 0:
        return None
    flops = work.serve_useful_flops(ctx["geometry"], ctx["steps"])
    return 100.0 * flops / (t["window_s"] * ctx["peaks"]["bf16_flops"])
