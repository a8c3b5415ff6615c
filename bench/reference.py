"""What the plain numpy reference of every query kind shares.

It imports nothing of the program and reads only the deployment the
benchmark generated. Each query kind (``queries/<kind>.py``) answers its
requests with these pieces; ``same`` compares a served answer with the
reference's rows. Every reference answer is exact and never overflows.
"""
from __future__ import annotations

import numpy as np

from bench.graphdata import Deployment


class Adjacency:
    """Out-edges grouped by source vertex (both directions if undirected),
    each with the edge-table row it came from; ``reverse`` groups in-edges
    by destination instead."""

    def __init__(self, dep: Deployment, *, reverse: bool = False):
        V = dep.n_vertices
        src, dst = dep.edge["src"], dep.edge["dst"]
        if reverse:
            src, dst = dst, src
        row = np.arange(src.shape[0], dtype=np.int64)
        if not dep.directed:
            src, dst, row = (np.concatenate([src, dst]),
                             np.concatenate([dst, src]),
                             np.concatenate([row, row]))
        order = np.argsort(src)
        self.dst = dst[order].astype(np.int64)
        self.row = row[order]
        self.offsets = np.zeros(V + 1, np.int64)
        np.cumsum(np.bincount(src, minlength=V), out=self.offsets[1:])

    def expand(self, vertices: np.ndarray):
        """(index into ``vertices``, neighbour, edge row) of every out-edge."""
        lo = self.offsets[vertices]
        cnt = self.offsets[vertices + 1] - lo
        parent = np.repeat(np.arange(vertices.shape[0]), cnt)
        first = np.cumsum(cnt) - cnt
        slot = np.repeat(lo - first, cnt) + np.arange(int(cnt.sum()))
        return parent, self.dst[slot], self.row[slot]


def hop_distances(adj: Adjacency, n_vertices: int, roots, max_hops: int) -> np.ndarray:
    """int8 ``[len(roots), V]`` hop distance from each root to every vertex
    (-1 beyond ``max_hops``). A level-synchronous BFS, 64 roots to a word:
    a vertex joins a root's frontier when any neighbour it is pulled from is
    in that root's frontier. ``adj`` holds each vertex's in-neighbours (for
    an undirected graph, its neighbours)."""
    roots = np.asarray(roots, np.int64)
    dist = np.full((len(roots), n_vertices), -1, np.int8)
    has_in = np.flatnonzero(np.diff(adj.offsets) > 0)
    starts = adj.offsets[has_in]
    for lo in range(0, len(roots), 64):
        chunk = roots[lo:lo + 64]
        lanes = np.uint64(1) << np.arange(len(chunk), dtype=np.uint64)
        frontier = np.zeros(n_vertices, np.uint64)
        np.bitwise_or.at(frontier, chunk, lanes)
        seen = frontier.copy()
        dist[lo + np.arange(len(chunk)), chunk] = 0
        for hop in range(1, max_hops + 1):
            if not frontier.any():
                break
            pulled = np.zeros(n_vertices, np.uint64)
            if starts.size:
                pulled[has_in] = np.bitwise_or.reduceat(frontier[adj.dst], starts)
            frontier = pulled & ~seen
            seen |= frontier
            hit = np.flatnonzero(frontier)
            bits = (frontier[hit, None] >> np.arange(len(chunk), dtype=np.uint64)) & 1
            lane, col = np.nonzero(bits.T.astype(bool))
            dist[lo + lane, hit[col]] = hop
    return dist


def same(kind, q, result, expected: np.ndarray) -> bool:
    """Rows, count and overflow flag of a served answer against the
    reference's rows; ``kind`` is the query kind's module."""
    if result is None or bool(result.overflow):
        return False
    got = kind.served(q, result)
    return int(result.count) == expected.shape[0] and np.array_equal(got, expected)
