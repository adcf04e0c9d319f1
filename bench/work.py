"""Operations and bytes of the work the benchmark's cells do, from shapes.

Two counts, kept apart:

* **useful FLOPs** (``*_useful_flops``): the model's own work at its
  density rho, over real tokens only. Sparse junctions count their
  surviving blocks; MoE counts the ``top_k`` experts chosen (not capacity
  padding); the LM head counts the positions whose logits are used;
  attention counts each query's real causal context. Padded rows and
  recomputation do not count. Training counts forward + backward as 3x.
* **a call's roofline** (``roofline_s``): the least time one kernel call
  can take on the chip, from the shapes as executed (padded rows
  included): ``max(flops / peak_flops, bytes / hbm_bandwidth)``, with
  the minimal bytes an implementation must move (each operand read once,
  the output written once).

Junction work always counts the pattern's surviving blocks, so the count
reads the same whatever implements the junction. The per-junction MAC
count is ``n_rb * d_in_b * bL * bR`` per input row (the arithmetic of
``repro.obs.flops.junction_stats``, copied so it cannot move under a
later change to the program).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Sequence, Tuple


@dataclasses.dataclass(frozen=True)
class Junction:
    """One block-sparse junction: ``n_rb`` right blocks, each fed by
    ``d_in_b`` left blocks of ``bl`` x ``br`` weights; ``experts``
    stacked copies for an expert-batched junction."""
    n_in: int
    n_out: int
    n_rb: int
    d_in_b: int
    bl: int
    br: int
    experts: int = 1

    @property
    def macs_per_row(self) -> int:
        return self.n_rb * self.d_in_b * self.bl * self.br

    @property
    def density(self) -> float:
        return self.d_in_b * self.bl / self.n_in


def junction_call(j: Junction, rows: int, act_bytes: int, w_bytes: int,
                  out_bytes: int) -> Tuple[float, float]:
    """(flops, bytes) of one forward call over ``rows`` rows per expert."""
    e = j.experts
    flops = 2.0 * rows * j.macs_per_row * e
    nbytes = (e * j.macs_per_row * w_bytes
              + e * rows * j.n_in * act_bytes
              + e * rows * j.n_out * out_bytes)
    return flops, float(nbytes)


def paged_decode_call(lengths: Sequence[int], n_kv: int, groups: int,
                      head_dim: int, kv_bytes: int,
                      q_bytes: int) -> Tuple[float, float]:
    """(flops, bytes) of one paged-decode attention call: each row reads
    the K and V of its live context once; q in, o out."""
    ctx = float(sum(lengths))
    rows = len(lengths)
    flops = 2 * 2.0 * ctx * n_kv * groups * head_dim
    nbytes = (2 * ctx * n_kv * head_dim * kv_bytes
              + 2 * rows * n_kv * groups * head_dim * q_bytes)
    return flops, nbytes


def roofline_s(flops: float, nbytes: float, peaks: dict) -> Tuple[float, str]:
    """Least time of a call and which bound sets it."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """What the useful-FLOP count needs of a decoder model."""
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    vocab: int
    junctions: Tuple[Junction, ...]     # per layer (experts: one each)
    top_k: int = 0                       # MoE: experts chosen per token
    n_experts: int = 0

    @property
    def attn_proj_macs(self) -> int:
        q = self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        return self.d_model * (q + 2 * kv) + q * self.d_model

    @property
    def ffn_macs(self) -> int:
        per = sum(j.macs_per_row for j in self.junctions)
        if self.top_k:
            return self.top_k * per + self.d_model * self.n_experts
        return per

    @property
    def layer_macs(self) -> int:
        return self.attn_proj_macs + self.ffn_macs

    def attn_flops(self, ctx: float) -> float:
        """QK^T and PV of one query against ``ctx`` keys, all layers."""
        return 4.0 * ctx * self.n_heads * self.head_dim * self.n_layers

    @property
    def head_flops(self) -> float:
        return 2.0 * self.d_model * self.vocab


def forward_useful_flops(g: Geometry, spans: Iterable[Tuple[int, int]],
                         logit_rows: int) -> float:
    """Forward FLOPs of real tokens: ``spans`` are (start, n) runs of
    positions processed (a prefill chunk or one decode token); each
    position p attends to p + 1 keys. ``logit_rows`` positions had their
    logits used."""
    n_tok = 0
    ctx = 0.0
    for start, n in spans:
        n_tok += n
        # sum over p in [start, start + n) of (p + 1)
        ctx += n * start + n * (n + 1) / 2.0
    return (2.0 * g.layer_macs * g.n_layers * n_tok + g.attn_flops(ctx)
            + g.head_flops * logit_rows)


def expert_rows(tokens: int, top_k: int, n_experts: int,
                capacity_factor: float) -> int:
    """Rows each expert's junction call computes in a step that routes
    ``tokens`` together: its capacity, ``ceil(tokens k / E * factor)``."""
    return max(int(math.ceil(tokens * top_k / n_experts
                             * capacity_factor)), 1)


def train_junction_roofline_s(g: Geometry, rows: int, remat: bool,
                              peaks: dict) -> float:
    """Least time of one training step's junction kernel calls: for every
    layer and junction a forward (twice under remat, which recomputes it
    in the backward pass), an input gradient and a weight gradient, each
    over ``rows`` rows per expert. The three have the same operations and,
    at 2 bytes an element, the same least bytes (two activations and the
    slab), so each is ``junction_call``'s count."""
    e = max(g.n_experts, 1)
    calls = (2 if remat else 1) + 2
    per_layer = 0.0
    for j in g.junctions:
        jx = dataclasses.replace(j, experts=e)
        per_layer += calls * roofline_s(
            *junction_call(jx, rows, 2, 2, 2), peaks)[0]
    return g.n_layers * per_layer


def train_useful_flops(g: Geometry, batch: int, seq: int) -> float:
    """Forward + backward (3x forward) of one training step."""
    fwd = forward_useful_flops(g, [(0, seq)] * batch, batch * seq)
    return 3.0 * fwd


# -- what the readers of per-layer metrics share ------------------------------


def serve_useful_flops(g: Geometry, steps) -> float:
    """Useful forward FLOPs of the recorded serving steps' device calls."""
    return sum(forward_useful_flops(g, c["runs"], c["logit_rows"])
               for st in steps for c in st["calls"])
