"""Bounded path enumeration from one bound start vertex.

Spec: ``{"kind": "paths_from", "min_len": a, "max_len": b,
"edge_predicate": [attr, op, value]}``; params ``{"src": v}``. The query
selects the end id and length of every simple path (no vertex twice) of
``a`` to ``b`` edges from ``src``, each edge passing the predicate; parallel
edges give distinct paths. The answer is the multiset of (end id, length)
rows.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench.graphdata import OPS
from bench.reference import Adjacency


def build(q: Dict):
    """The query through the engine's public builder."""
    from repro.core.query import P, Query, param

    PS = P("PS")
    attr, op, value = q["edge_predicate"]
    where = (PS.start.id == param("src")) & OPS[op](PS.edges[0:"*"].attr(attr), value)
    if int(q["min_len"]) != 1:
        where = where & (PS.length >= int(q["min_len"]))
    return (Query().from_paths("G", "PS").where(where)
            .hint_max_length(int(q["max_len"]))
            .select(end=PS.end.id, length=PS.length))


def paths(adj: Adjacency, q: Dict, start: int, ok_row: np.ndarray) -> np.ndarray:
    """Sorted ``[n, 2]`` array of (end id, length) rows."""
    walk = np.asarray([[start]], np.int64)
    rows = []
    for length in range(1, int(q["max_len"]) + 1):
        parent, nbr, erow = adj.expand(walk[:, -1])
        prev = walk[parent]
        keep = ok_row[erow] & ~np.any(prev == nbr[:, None], axis=1)
        walk = np.concatenate([prev[keep], nbr[keep, None]], axis=1)
        if length >= int(q["min_len"]):
            rows.append(np.stack([walk[:, -1], np.full(len(walk), length)], 1))
    out = np.concatenate(rows) if rows else np.zeros((0, 2), np.int64)
    return out[np.lexsort((out[:, 1], out[:, 0]))]


def answers(dep, q: Dict, params: List[Dict[str, int]]) -> List[np.ndarray]:
    """The reference's rows for each request."""
    attr, op, value = q["edge_predicate"]
    adj, ok = Adjacency(dep), OPS[op](dep.edge[attr], value)
    return [paths(adj, q, p["src"], ok) for p in params]


def served(q: Dict, result) -> np.ndarray:
    """A served ``QueryResult`` in the reference's row form."""
    rows = np.stack([np.asarray(result.columns["end"], np.int64),
                     np.asarray(result.columns["length"], np.int64)], 1)
    return rows[np.lexsort((rows[:, 1], rows[:, 0]))]
