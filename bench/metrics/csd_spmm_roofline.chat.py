"""Share of their roofline that the serving steps' junction kernels reach:
the least time of every ``csd_spmm`` call of the traced window's steps
(``bench/work.py``) over the device time of the trace's ``csd_spmm``
family, in percent.

Rows are counted as the engine hands them to ``csd_matmul``: ``max_slots x
chunk`` for a prefill call, ``max_slots`` for a decode call. Each device
call runs, in every layer, one kernel per FFN junction (up, gate, down):
the configuration's attention projections and LM head are dense, XLA's.
The program names its kernels in the trace (``csd_spmm_fwd``); where it
does not, the family is empty and the metric is left out."""
from bench import work


def read(ctx):
    t = ctx["trace"]["families"].get("csd_spmm", 0.0)
    if not ctx["steps"] or t <= 0:
        return None
    g = ctx["geometry"]
    nb = ctx["dtype_bytes"]
    slots = ctx["engine"]["max_slots"]
    least = sum(
        work.roofline_s(*work.junction_call(j, slots * c["chunk"], nb, nb,
                                            nb), ctx["peaks"])[0]
        for st in ctx["steps"] for c in st["calls"] for j in g.junctions)
    return 100.0 * g.n_layers * least / t
