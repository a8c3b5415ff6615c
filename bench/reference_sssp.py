"""Plain float32 single-source shortest paths for the weighted query kinds.

It imports nothing of the program. The distance of a vertex is the least,
over the walks from the root, of the walk's weights summed in walk order
with every sum rounded to float32: ``d[v] = min(fl32(d[u] + w))``, the
fixpoint every exact float32 SSSP reaches, whatever its order of work (the
weights are non-negative, so rounding never lets a longer walk win).

The search is a frontier Bellman-Ford: each round pushes out of the
vertices whose distance fell in the round before, through their edges of
``Adjacency``, and keeps the least candidate of each vertex
(``np.minimum.at``). An f64 library routine would round differently and
could not serve.

``weighted(dep, attr)`` keeps one ``Weighted`` a deployment and weight,
while the deployment lives, and each ``Weighted`` keeps the distances it
computed: the key draw's searches (``keys/search_keys_by_work.py``) are
the check's answers.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Tuple

import numpy as np

from bench.reference import Adjacency


class Weighted:
    """The out-edges of ``adj`` with their float32 weights (edge row ``r``
    weighing ``weight[r]``), laid out once for many searches."""

    def __init__(self, adj: Adjacency, weight: np.ndarray):
        self.n_vertices = adj.offsets.shape[0] - 1
        self.offsets = adj.offsets.astype(np.int32)
        self.dst = adj.dst.astype(np.int32)
        self.w = np.asarray(weight, np.float32)[adj.row]
        self._dist: Dict[int, np.ndarray] = {}
        self._fired: Dict[int, List[int]] = {}

    def search(self, root: int) -> Tuple[np.ndarray, List[int]]:
        """(float32 ``[V]`` distance from ``root``, inf where unreached; the
        edges each round relaxed), computed once a root."""
        if root not in self._dist:
            dist = np.full(self.n_vertices, np.inf, np.float32)
            dist[root] = 0.0
            changed = np.asarray([root], np.int32)
            fired = []
            while changed.size:
                lo = self.offsets[changed]
                cnt = self.offsets[changed + 1] - lo
                first = np.cumsum(cnt, dtype=np.int32) - cnt
                fired.append(int(cnt.sum()))
                slot = np.repeat(lo - first, cnt) + np.arange(fired[-1], dtype=np.int32)
                new = dist.copy()
                np.minimum.at(new, self.dst[slot], np.repeat(dist[changed], cnt) + self.w[slot])
                changed = np.flatnonzero(new < dist).astype(np.int32)
                dist = new
            self._dist[root], self._fired[root] = dist, fired
        return self._dist[root], self._fired[root]

    def distances(self, root: int) -> np.ndarray:
        """float32 ``[V]`` distance from ``root`` (inf where unreached)."""
        return self.search(root)[0]


_graphs: Dict[Tuple[int, str], Tuple[weakref.ref, Weighted]] = {}


def weighted(dep, attr: str) -> Weighted:
    """The ``Weighted`` of ``dep``'s edges by ``attr``, one while ``dep`` lives."""
    for k in [k for k, (ref, _) in _graphs.items() if ref() is None]:
        del _graphs[k]
    key = (id(dep), attr)
    if key not in _graphs or _graphs[key][0]() is not dep:
        _graphs[key] = (weakref.ref(dep), Weighted(Adjacency(dep), dep.edge[attr]))
    return _graphs[key][1]
