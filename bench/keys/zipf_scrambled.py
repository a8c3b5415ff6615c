"""Scrambled Zipfian start vertices (YCSB): a Zipf law over ranks, and the
vertex that holds each rank drawn from the seed, with every rank's answer
size the same for every seed.

Spec: ``{"kind": "zipf_scrambled", "theta": t}``; params ``{"src": v}``.
Each stream's ranks are the stratified quantiles of Zipf(theta), shuffled
by the seed, so every seed asks for the same multiset of ranks (YCSB's
scrambled Zipfian, without its hash collisions). The warm-up draws from a
stream of its own.

Which vertex holds a rank: the vertices sorted by the size of their
answer (``answer_sizes``), ties in an order drawn from the seed; rank r
goes to the vertex at position ``SIZE_ORDER``-permutation[r] of that list.
A vertex's answer is its work, and the hot ranks take a large share of the
requests, so a holder drawn uniformly let the seed decide how much work a
window holds. Now every seed gives each rank a vertex of the same size
quantile, and the seed decides which of the graph's vertices of that size
it is.
"""
from __future__ import annotations

import numpy as np

from bench.graphdata import OPS

SIZE_ORDER = 20261019  # which size quantile each rank holds, for every seed


def ranks(n: int, theta: float, size: int, rng) -> np.ndarray:
    """``size`` stratified quantiles of Zipf(theta) over ranks 0..n-1, shuffled."""
    cdf = np.cumsum(np.arange(1, n + 1, dtype=np.float64) ** -theta)
    cdf /= cdf[-1]
    q = (np.arange(size) + 0.5) / size
    return rng.permutation(np.minimum(np.searchsorted(cdf, q), n - 1))


def answer_sizes(dep, query) -> np.ndarray:
    """Per vertex: its passing edges (both ways if undirected, self-loops
    left out) plus those of each neighbour they reach, the rows of a 1..2
    hop answer up to the paths that come back to the start."""
    V = dep.n_vertices
    attr, op, value = query["edge_predicate"]
    src, dst = dep.edge["src"], dep.edge["dst"]
    ok = OPS[op](dep.edge[attr], value) & (src != dst)
    src, dst = src[ok], dst[ok]
    if not dep.directed:
        src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
    first = np.bincount(src, minlength=V)
    return first + np.bincount(src, weights=first[dst], minlength=V).astype(np.int64)


def holders(dep, query, rng) -> np.ndarray:
    """rank -> vertex: the size-sorted vertices, ties in the seed's order,
    taken in the fixed ``SIZE_ORDER`` order."""
    V = dep.n_vertices
    by_size = np.argsort(answer_sizes(dep, query) * V + rng.permutation(V))
    return by_size[np.random.default_rng(SIZE_ORDER).permutation(dep.n_vertices)]


def draw(spec, query, dep, rng, warm_rng, n_streams: int, length: int, n_warm: int):
    """(``n_streams`` lists of ``length`` params, the warm-up's params)."""
    holder = holders(dep, query, rng)  # rank -> vertex
    theta = float(spec["theta"])

    def starts(size, r):
        return [{"src": int(v)} for v in holder[ranks(dep.n_vertices, theta, size, r)]]

    return [starts(length, rng) for _ in range(n_streams)], starts(n_warm, warm_rng)
