"""Scrambled Zipfian start vertices (YCSB): a Zipf law over ranks, and the
vertex that holds each rank a permutation of all vertex ids drawn from the
seed.

Spec: ``{"kind": "zipf_scrambled", "theta": t}``; params ``{"src": v}``.
Each stream's ranks are the stratified quantiles of Zipf(theta), shuffled
by the seed, so every seed asks for the same multiset of ranks (YCSB's
scrambled Zipfian, without its hash collisions). The warm-up draws from a
stream of its own.
"""
from __future__ import annotations

import numpy as np


def ranks(n: int, theta: float, size: int, rng) -> np.ndarray:
    """``size`` stratified quantiles of Zipf(theta) over ranks 0..n-1, shuffled."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)
    cdf /= cdf[-1]
    q = (np.arange(size) + 0.5) / size
    return rng.permutation(np.minimum(np.searchsorted(cdf, q), n - 1))


def draw(spec, query, dep, rng, warm_rng, n_streams: int, length: int, n_warm: int):
    """(``n_streams`` lists of ``length`` params, the warm-up's params)."""
    holder = rng.permutation(dep.n_vertices)  # rank -> vertex
    theta = float(spec["theta"])

    def starts(size, r):
        return [{"src": int(v)} for v in holder[ranks(dep.n_vertices, theta, size, r)]]

    return [starts(length, rng) for _ in range(n_streams)], starts(n_warm, warm_rng)
