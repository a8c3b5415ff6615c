"""Graph500 search keys of a fixed shortest-path work, stratified by it and
asked in a Latin schedule as ``search_keys_at_distance`` asks its keys, one
root a request, each with a target at a given hop distance: the key draw
of the weighted (Kernel 3) cells.

Spec: ``{"kind": "search_keys_by_work", "min_degree": d, "roots": n,
"pool": p, "pattern": [k, ...], "round_cost": c, "round_cap": f, "work":
[w_0, ..., w_(n-1)], "rounds": [r_0, ..., r_(n-1)]}``; params ``{"src":
root, "dst": target}``. The query's ``weight`` weighs the edges.

The pool is the first ``p`` vertices, in the seed's order, of degree at
least ``d`` from which every distance of the pattern (and 1, for the
warm-up) is reachable: the draw of ``search_keys_at_distance``. A pool
vertex's rounds and work are those of the reference's SSSP from it
(``reference_sssp``, a frontier Bellman-Ford): its rounds, and in sweeps
of the ``E`` edge slots (both ways if undirected) ``c`` a round plus the
slots the round relaxes over ``E``, at most ``f``. A round costs a fixed
set-up, and one that relaxes more than a fraction ``f`` of the slots costs
a sweep of them all in an engine that chooses between relaxing a
frontier's edges and sweeping the stream; one that always sweeps pays
by the round.

Key ``i`` is the unused pool vertex nearest ``(w_i, r_i)``, each measured
in the targets' own standard deviation, the targets farthest from their
median choosing first. The targets are the same for every seed (the
cell's: the pooled pools of 16 seeds sorted by work, read at 64
quantiles), so every seed asks the same work in the same rounds, and the
seed decides which of its graph's vertices carry it. With uniform keys
the graph each seed draws set the work: the mean work of a seed's keys
moved about 12% from seed to seed.

Key ``k`` in order of work lies in stratum ``k // m`` at rank ``k % m``,
``m = n / streams``, and request ``j`` of stream ``c`` asks stratum ``s =
(c + j) % streams`` for its key of rank ``(s + j) % m``, with a target
exactly ``pattern[(s + 3 j) % len(pattern)]`` hops out, drawn uniformly
among those vertices: every round of requests asks each stratum once. The
warm-up asks the pool's first vertex for targets one hop out.
"""
from __future__ import annotations

import numpy as np

from bench import reference_sssp
from bench.reference import Adjacency, hop_distances


def candidates(spec, dep, rng):
    """(the pool, its hop distances ``[pool, V]`` out to the pattern's
    longest): at most ``spec["pool"]`` vertices, at least ``spec["roots"]``."""
    n, p = int(spec["roots"]), int(spec["pool"])
    pattern = [int(d) for d in spec["pattern"]]
    deg = np.bincount(dep.edge["src"], minlength=dep.n_vertices)
    if not dep.directed:
        deg = deg + np.bincount(dep.edge["dst"], minlength=dep.n_vertices)
    cand = rng.permutation(np.flatnonzero(deg >= int(spec["min_degree"])))
    adj = Adjacency(dep, reverse=True)
    keys, dists, found = [], [], 0
    for lo in range(0, len(cand), 64):  # one word of ``hop_distances``
        chunk = cand[lo:lo + 64]
        d = hop_distances(adj, dep.n_vertices, chunk, max(pattern))
        ok = np.all([(d == k).any(axis=1) for k in set(pattern) | {1}], axis=0)
        take = np.flatnonzero(ok)[:p - found]
        keys.append(chunk[take])
        dists.append(d[take])
        found += len(take)
        if found == p:
            break
    if found < n:
        raise ValueError("too few roots reach every distance of the pattern")
    return np.concatenate(keys), np.concatenate(dists)


def work(spec, graph: reference_sssp.Weighted, root: int):
    """(the SSSP work from ``root`` in sweeps of ``graph``'s edge slots,
    its rounds)."""
    slots = max(graph.dst.shape[0], 1)
    c, f = float(spec["round_cost"]), float(spec["round_cap"])
    fired = graph.search(int(root))[1]
    return sum(c + min(m / slots, f) for m in fired), len(fired)


def nearest(points: np.ndarray, targets: np.ndarray) -> np.ndarray:
    """Per target (a row of ``targets``), the index of the unused row of
    ``points`` nearest it, each column in the targets' standard deviation;
    the targets farthest from their median choose first."""
    scale = np.where(targets.std(axis=0) > 0, targets.std(axis=0), 1.0)
    p, t = points / scale, targets / scale
    used = np.zeros(p.shape[0], bool)
    out = np.empty(t.shape[0], np.int64)
    far = ((t - np.median(t, axis=0)) ** 2).sum(axis=1)
    for i in np.argsort(-far, kind="stable"):
        d = np.where(used, np.inf, ((p - t[i]) ** 2).sum(axis=1))
        out[i] = j = int(np.argmin(d))
        used[j] = True
    return out


def draw(spec, query, dep, rng, warm_rng, n_streams: int, length: int, n_warm: int):
    """(``n_streams`` lists of ``length`` params, the warm-up's params)."""
    pattern = [int(d) for d in spec["pattern"]]
    targets = np.stack([np.asarray(spec["work"], np.float64),
                        np.asarray(spec["rounds"], np.float64)], axis=1)
    if targets.shape[0] != int(spec["roots"]) or int(spec["roots"]) % n_streams:
        raise ValueError("the work targets do not fill one stratum per stream")
    pool, dists = candidates(spec, dep, rng)
    graph = reference_sssp.weighted(dep, query["weight"])
    points = np.asarray([work(spec, graph, v) for v in pool], np.float64)
    picked = nearest(points, targets)
    order = picked[np.argsort(points[picked, 0], kind="stable")]
    m = len(order) // n_streams
    at = {}  # (pool index, distance) -> the vertices that far out

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
            stream.append({"src": int(pool[i]),
                           "dst": target(i, pattern[(s + 3 * j) % len(pattern)])})
        streams.append(stream)
    warm = [{"src": int(pool[0]), "dst": int(t)}
            for t in warm_rng.choice(np.flatnonzero(dists[0] == 1), n_warm)]
    return streams, warm
