"""The junction tables a configuration states, built from the paper's
definition and nothing of the program.

A junction of ``n_in -> n_out`` features on ``bl x br`` tiles has ``n_lb =
n_in / bl`` left and ``n_rb = n_out / br`` right blocks. Its density is a
multiple of ``1 / gcd(n_lb, n_rb)`` (Appendix A): ``k = round(rho * g)``
gives every right block ``d_in = k n_lb / g`` left blocks and every left
block ``k n_rb / g`` right blocks. The clash-free type-1 schedule (§III-C)
reads ``z`` banks of depth ``D = n_lb / z``, ``z`` the largest width up to
``min(n_lb, 128)`` that divides both ``n_lb`` and the ``n_rb * d_in``
edges; bank ``m`` starts at ``phi[m]`` (drawn from the junction's seed) and
steps by one address per cycle. Edge slot ``t z + m`` reads left block
``((phi[m] + t) mod D) z + m``, and slots are numbered right block by right
block, so ``table[rb, f]`` is the left block of slot ``rb d_in + f``.
"""
from __future__ import annotations

import math

import numpy as np


def clashfree_table(n_in: int, n_out: int, rho: float, bl: int, br: int,
                    seed: int, cf_type: int = 1,
                    dither: bool = False) -> np.ndarray:
    """``(n_rb, d_in)`` table of the left block feeding each fan-in slot."""
    if cf_type != 1 or dither:
        raise ValueError("only the type-1 schedule without dithering is "
                         "written here")
    if n_in % bl or n_out % br:
        raise ValueError(f"tile {bl}x{br} does not divide {n_in}x{n_out}")
    n_lb, n_rb = n_in // bl, n_out // br
    g = math.gcd(n_lb, n_rb)
    k = max(1, min(g, round(rho * g) or 1))
    d_in = k * (n_lb // g)
    edges = n_rb * d_in
    z = next(z for z in range(min(n_lb, 128), 0, -1)
             if n_lb % z == 0 and edges % z == 0)
    depth = n_lb // z
    phi = np.random.default_rng(seed).integers(0, depth, size=z)
    t = np.arange(edges // z)[:, None]
    left = ((phi[None, :] + t) % depth) * z + np.arange(z)[None, :]
    return left.reshape(n_rb, d_in).astype(np.int32)


def tables(cfgfile: dict) -> dict:
    """The FFN (or expert) junction tables of a configuration file: ``up``
    and ``gate`` at ``rho_ffn[0]``, ``down`` at ``rho_ffn[1]``, each from
    the sparsity seed plus the junction's own offset (the file's
    ``junction_seeds``)."""
    cfg = cfgfile["model"]
    sp = cfg["sparsity"]
    d_ff = cfg["moe"]["d_expert"] if cfg.get("moe") else cfg["d_ff"]
    d = cfg["d_model"]
    rho_up, rho_down = sp["rho_ffn"]
    shape = {"up": (d, d_ff, rho_up), "gate": (d, d_ff, rho_up),
             "down": (d_ff, d, rho_down)}
    return {j: clashfree_table(n_in, n_out, rho, sp["block_in"],
                               sp["block_out"],
                               sp["seed"] + cfgfile["junction_seeds"][j],
                               sp["cf_type"], sp["dither"])
            for j, (n_in, n_out, rho) in shape.items()}
