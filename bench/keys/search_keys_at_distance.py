"""Graph500 search keys as roots, stratified by their 2-hop edge count and
asked in a Latin schedule, one root a request, each with a target at a
given hop distance.

Spec: ``{"kind": "search_keys_at_distance", "min_degree": d, "roots": n,
"pattern": [k, ...]}``; params ``{"src": root, "dst": target}``.

The keys are the first ``n`` vertices, in the seed's order, of degree at
least ``d`` from which every distance of the pattern (and 1, for the
warm-up) is reachable: a uniform draw, as Graph500's 64 search keys are.
They are sorted by the edges incident to the vertices within 2 hops of the
key (the sum of degree, counted both ways, over distance 0..2; ties in the
seed's order), and key ``k`` of that order lies in stratum ``k // m`` at
rank ``k % m``, with one stratum per stream and ``m = n / streams`` keys
in each. Request ``j`` of stream ``c`` (round ``j``) asks stratum
``s = (c + j) % streams`` for its key of rank ``(s + j) % m``, with a
target exactly ``pattern[(s + 3 j) % len(pattern)]`` hops out, drawn
uniformly among those vertices (the paper's query generation, §7.2:
endpoints connected at given path lengths).

With 8 streams, 64 keys and a pattern of 8, every round asks each stratum,
each rank and each pattern entry once, and any 8 consecutive rounds ask
every key once and pair every stratum with every pattern entry once. So
every seed puts the same mix of cheap and costly roots, and the same
number of hops, into any stretch of the window; the seed decides only
which keys of the graph fill the strata. The warm-up asks the first key in
the seed's order for targets one hop out.
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


def search_keys(spec, dep, rng):
    """(the first ``spec["roots"]`` eligible keys in the seed's order, their
    hop distances ``[roots, V]`` out to the pattern's longest)."""
    n = int(spec["roots"])
    pattern = [int(d) for d in spec["pattern"]]
    cand = rng.permutation(np.flatnonzero(degrees(dep) >= int(spec["min_degree"])))
    adj = Adjacency(dep, reverse=True)
    keys, dists, found = [], [], 0
    for lo in range(0, len(cand), 64):  # one word of ``hop_distances``
        chunk = cand[lo:lo + 64]
        d = hop_distances(adj, dep.n_vertices, chunk, max(pattern))
        ok = np.all([(d == k).any(axis=1) for k in set(pattern) | {1}], axis=0)
        take = np.flatnonzero(ok)[:n - found]
        keys.append(chunk[take])
        dists.append(d[take])
        found += len(take)
        if found == n:
            return np.concatenate(keys), np.concatenate(dists)
    raise ValueError("too few roots reach every distance of the pattern")


def two_hop_edges(dep, dists: np.ndarray) -> np.ndarray:
    """Per key, the sum of degree (counted both ways) over the vertices
    within 2 hops of it."""
    both = (np.bincount(dep.edge["src"], minlength=dep.n_vertices)
            + np.bincount(dep.edge["dst"], minlength=dep.n_vertices))
    return np.asarray([int(both[(d >= 0) & (d <= 2)].sum()) for d in dists])


def draw(spec, query, dep, rng, warm_rng, n_streams: int, length: int, n_warm: int):
    """(``n_streams`` lists of ``length`` params, the warm-up's params)."""
    pattern = [int(d) for d in spec["pattern"]]
    if int(spec["roots"]) % n_streams:
        raise ValueError("the search keys do not fill one stratum per stream")
    keys, dists = search_keys(spec, dep, rng)
    order = np.argsort(two_hop_edges(dep, dists), kind="stable")
    m = len(keys) // n_streams
    at = {}  # (key, distance) -> the vertices that far out

    def target(i, k):
        if (i, k) not in at:
            at[i, k] = np.flatnonzero(dists[i] == k)
        return int(rng.choice(at[i, k]))

    streams = []
    for c in range(n_streams):
        stream = []
        for j in range(length):
            s = (c + j) % n_streams
            i = order[s * m + (s + j) % m]
            stream.append({"src": int(keys[i]),
                           "dst": target(i, pattern[(s + 3 * j) % len(pattern)])})
        streams.append(stream)
    warm = [{"src": int(keys[0]), "dst": int(t)}
            for t in warm_rng.choice(np.flatnonzero(dists[0] == 1), n_warm)]
    return streams, warm
