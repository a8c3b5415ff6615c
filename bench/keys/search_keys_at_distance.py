"""Graph500 search keys as roots, each with targets at given hop distances.

Spec: ``{"kind": "search_keys_at_distance", "min_degree": d, "pattern":
[k, ...]}``; params ``{"src": root, "dst": target}``. Stream ``c`` has one
root, drawn in the seed's order among the vertices of degree at least
``d`` from which every distance of the pattern is reachable; its ``j``-th
target lies exactly ``pattern[(c + j) % len(pattern)]`` hops from the root,
drawn uniformly among those vertices (the paper's query generation, §7.2:
endpoints connected at given path lengths). So every seed asks for the
same number of hops. The warm-up asks stream 0's root for targets one hop
out.
"""
from __future__ import annotations

import numpy as np

from bench.reference import Adjacency, hop_distances


def degrees(dep) -> np.ndarray:
    """Out-degree (directed) or degree (undirected) of every vertex."""
    deg = np.bincount(dep.edge["src"], minlength=dep.n_vertices)
    if not dep.directed:
        deg = deg + np.bincount(dep.edge["dst"], minlength=dep.n_vertices)
    return deg


def draw(spec, query, dep, rng, warm_rng, n_streams: int, length: int, n_warm: int):
    """(``n_streams`` lists of ``length`` params, the warm-up's params)."""
    pattern = [int(d) for d in spec["pattern"]]
    keys = rng.permutation(np.flatnonzero(degrees(dep) >= int(spec["min_degree"])))
    adj = Adjacency(dep, reverse=True)
    roots, dists = [], []
    for lo in range(0, len(keys), n_streams):
        cand = keys[lo:lo + n_streams]
        d = hop_distances(adj, dep.n_vertices, cand, max(pattern))
        ok = np.all([(d == k).any(axis=1) for k in set(pattern) | {1}], axis=0)
        roots += list(cand[ok])
        dists += list(d[ok])
        if len(roots) >= n_streams:
            break
    if len(roots) < n_streams:
        raise ValueError("too few roots reach every distance of the pattern")

    def at(d_row, k, size, r):
        return r.choice(np.flatnonzero(d_row == k), size)

    streams = []
    for c in range(n_streams):
        hops = [pattern[(c + j) % len(pattern)] for j in range(length)]
        tgt = {k: iter(at(dists[c], k, hops.count(k), rng)) for k in set(hops)}
        streams.append([{"src": int(roots[c]), "dst": int(next(tgt[k]))} for k in hops])
    warm = [{"src": int(roots[0]), "dst": int(t)} for t in at(dists[0], 1, n_warm, warm_rng)]
    return streams, warm
