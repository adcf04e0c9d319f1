"""The one request generator: every serving mix is a data file of
parameters that this module reads (``bench/traffic/<mix>.json``).

Steadiness: a run's requests are a fixed multiset drawn by quantiles of
the mix's distributions, and the seed orders them and draws the prompt
tokens. Runs with different seeds then do the same amount of work, so
their spread is the system's and not the sampler's. Where a window
serves only part of a multiset, its order decides which part: a mix
with ``"order": "fixed"`` keeps one interleaved order for every seed
(the seed draws only the tokens), so every seed's window does the same
work.

Mix keys:

* ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``;
* ``arrival``: ``"poisson"`` (open loop at the cell's ``rate_per_s``;
  inter-arrival gaps are exponential quantiles in seeded order) or
  ``"backlog"`` (no schedule: the queue is kept above the engine's slot
  count);
* ``block``: how many requests one quantile multiset holds (backlog
  draws block after block);
* ``order``: ``"seeded"`` (default: a seeded permutation) or ``"fixed"``
  (prompt quantile ``19 i mod n``, output quantile ``13 i + 7 mod n``
  for the i-th request of a block of n = 32: long and short requests
  alternate, and every stretch of a few requests spans the mix).
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    due: float = 0.0        # seconds after the window opens (open loop)


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n lengths at quantiles (i + 0.5) / n of the spec's distribution."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Generator:
    """Seeded requests of one mix. ``vocab`` bounds the token ids."""

    def __init__(self, mix: dict, seed: int, vocab: int, salt: int = 0):
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng([int(seed), int(salt), 7])
        self.next_id = 0

    def _sizes(self, n: int):
        p = _quantiles(self.mix["prompt_len"], n)
        o = _quantiles(self.mix["output_len"], n)
        if self.mix.get("order", "seeded") == "fixed":
            if n != 32:
                raise ValueError("a fixed order is defined for blocks of 32")
            i = np.arange(n)
            return p[(19 * i) % n], o[(13 * i + 7) % n]
        return p[self.rng.permutation(n)], o[self.rng.permutation(n)]

    def _make(self, plen: int, olen: int, due: float = 0.0) -> Req:
        r = Req(self.next_id,
                self.rng.integers(0, self.vocab, int(plen), dtype=np.int32),
                int(olen), due)
        self.next_id += 1
        return r

    def block(self) -> List[Req]:
        """One multiset of ``mix['block']`` requests in seeded order
        (backlog mixes)."""
        n = int(self.mix["block"])
        p, o = self._sizes(n)
        return [self._make(a, b) for a, b in zip(p, o)]

    def schedule(self, rate: float, seconds: float) -> List[Req]:
        """Open-loop requests due over ``seconds`` at ``rate`` per second:
        n = round(rate * seconds) requests, gaps at exponential quantiles
        (mean 1 / rate) in seeded order, so the last one is due at about
        ``seconds``."""
        n = max(1, int(round(rate * seconds)))
        p, o = self._sizes(n)
        gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n) / rate
        gaps = gaps[self.rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
        return [self._make(a, b, float(t)) for a, b, t in zip(p, o, due)]
