"""The one request generator of the serving traffic mixes.

A mix (``bench/traffic/<mix>.json``) gives distributions; this turns them
into a request stream from the seed.  Every seed gets the same sizes in
another order: requests come in blocks of ``strata``, and each block
holds one draw from each of ``strata`` equal-probability strata of each
length distribution (the stratum's median quantile), permuted by the
seed.  The seed also draws every token id.  So two seeds differ in the
order of the work and in its tokens, not in its amount.

Length distributions: ``{"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}``, clipped to [lo, hi].

Arrivals: ``{"kind": "closed", "clients": n}``: n clients, each sending
its next request as soon as its last one finished.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantile(dist: dict, u: float) -> float:
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))


def strata_values(dist: dict, n: int) -> list[int]:
    """The median quantile of each of n equal-probability strata,
    rounded and clipped."""
    lo, hi = dist.get("min", 1), dist.get("max", 1 << 30)
    return [int(min(hi, max(lo, round(_quantile(dist, (i + 0.5) / n)))))
            for i in range(n)]


class RequestStream:
    """Request k of a run: its prompt tokens and its number of output
    tokens."""

    def __init__(self, traffic: dict, vocab: int, seed: int):
        self.vocab = vocab
        self.seed = seed
        self.n = traffic["strata"]
        self.prompt_strata = strata_values(traffic["prompt"], self.n)
        self.output_strata = strata_values(traffic["output"], self.n)
        self._blocks: dict[int, tuple] = {}

    def _block(self, b: int):
        if b not in self._blocks:
            rng = np.random.Generator(np.random.PCG64([self.seed, b, 0]))
            self._blocks[b] = (rng.permutation(self.prompt_strata),
                               rng.permutation(self.output_strata))
        return self._blocks[b]

    def lengths(self, k: int) -> tuple[int, int]:
        p, o = self._block(k // self.n)
        return int(p[k % self.n]), int(o[k % self.n])

    def request(self, k: int) -> tuple[list[int], int]:
        plen, olen = self.lengths(k)
        rng = np.random.Generator(np.random.PCG64([self.seed, k, 1]))
        return rng.integers(1, self.vocab, size=plen).tolist(), olen
