"""Share of its roofline that the paged-decode attention kernel reaches:
the least time of every decode call of the traced window's steps, in
every layer (each decoding row reads the K and V of its live context
once: ``work.paged_decode_call`` over the recorded lengths), over the
device time of the trace's ``paged_decode`` family, in percent. Where the
program does not name the kernel in the trace, the family is empty and
the metric is left out."""
from bench import work


def read(ctx):
    t = ctx["trace"]["families"].get("paged_decode", 0.0)
    if t <= 0:
        return None
    g = ctx["geometry"]
    nb = ctx["dtype_bytes"]
    least = sum(
        work.roofline_s(*work.paged_decode_call(
            c["lengths"], g.n_kv_heads, g.n_heads // g.n_kv_heads,
            g.head_dim, nb, nb), ctx["peaks"])[0]
        for st in ctx["steps"] for c in st["calls"] if c["kind"] == "decode")
    return 100.0 * g.n_layers * least / t if least else None
