"""Share of their roofline that the training step's junction kernels
reach: the least time of every ``csd_spmm`` call of the traced steps
(forward, input gradient, weight gradient over each expert's capacity
rows, counted from shapes: ``bench/work.py``) over the device time of the
Pallas calls in the trace, in percent. Training runs no other Pallas
kernel (its attention is XLA's), so every Pallas call there is a junction
call, whether or not the kernel's name reaches the trace."""
from bench import work


def read(ctx):
    fam = ctx["trace"]["families"]
    t = fam.get("csd_spmm", 0.0) + fam.get("pallas", 0.0)
    if not ctx["train_steps"] or t <= 0:
        return None
    g = ctx["geometry"]
    mc = ctx["model"].cfg.moe
    rows = work.expert_rows(ctx["batch"] * ctx["seq"], mc.top_k,
                            mc.n_routed, mc.capacity_factor)
    least = work.train_junction_roofline_s(
        g, rows, bool(ctx["model"].cfg.remat), ctx["peaks"])
    return 100.0 * ctx["train_steps"] * least / t
