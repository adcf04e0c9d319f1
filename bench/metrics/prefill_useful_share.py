"""Share of the prefill rows the engine computed that held a real token:
sum of real chunk tokens over sum of ``max_slots * chunk`` over the
prefill calls of the traced window's steps (the engine pads every
prefill call to all slots), in percent."""


def read(ctx):
    real = pad = 0
    for st in ctx["steps"]:
        for c in st["calls"]:
            if c["kind"] == "prefill":
                real += sum(n for _, n in c["runs"])
                pad += ctx["engine"]["max_slots"] * c["chunk"]
    return 100.0 * real / pad if pad else None
