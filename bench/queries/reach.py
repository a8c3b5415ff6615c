"""Point-to-point reachability, both ends bound.

Spec: ``{"kind": "reach", "max_len": n}``; params ``{"src": a, "dst": b}``.
The answer is one row (exists, hop distance) when ``b`` lies within ``n``
hops of ``a``, else no row.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.reference import Adjacency, hop_distances


def build(q: Dict):
    """The query through the engine's public builder."""
    from repro.core.query import P, Query, col, param

    PS = P("PS")
    return (Query().from_paths("G", "PS")
            .where((PS.start.id == param("src")) & (PS.end.id == param("dst")))
            .hint_max_length(int(q["max_len"]))
            .select(exists=col("PS.exists"), length=col("PS.length")))


def answers(dep, q: Dict, params: List[Dict[str, int]]) -> List[np.ndarray]:
    """The reference's rows for each request: one BFS per distinct root."""
    max_len = int(q["max_len"])
    roots = sorted({p["src"] for p in params})
    lane = {r: i for i, r in enumerate(roots)}
    dist = hop_distances(Adjacency(dep, reverse=True), dep.n_vertices, roots, max_len)
    out = []
    for p in params:
        d = int(dist[lane[p["src"]], p["dst"]])
        out.append(np.asarray([[1, d]], np.int64) if 1 <= d <= max_len
                   else np.zeros((0, 2), np.int64))
    return out


def served(q: Dict, result) -> np.ndarray:
    """A served ``QueryResult`` in the reference's row form."""
    exists = np.asarray(result.columns["exists"], bool)
    length = np.asarray(result.columns["length"], np.int64)
    return np.stack([exists.astype(np.int64), length], 1)
