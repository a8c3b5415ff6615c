"""Weighted point-to-point shortest paths, both ends bound (Graph500
Kernel 3 through the engine's SPScan).

Spec: ``{"kind": "sssp", "weight": attr, "max_len": n}``; params ``{"src":
a, "dst": b}``. The query asks for the shortest path from ``a`` to ``b`` by
``attr`` (``hint_shortest_path``): the engine computes ``a``'s whole
shortest-path tree, and the answer is one row when ``b`` is reachable: the
distance, and a witness path of at most ``n`` edges.

A served row is right when its distance equals the float32 reference's
(``reference_sssp``) bit for bit, and its witness runs from ``a`` to ``b``
over edges of the deployment, each hop ``u -> v`` of weight ``w`` tight
under the engine's parent rule: ``fl32(d[u] + w)`` within a relative and
absolute 1e-6 of ``d[v]``, ``d`` the reference's distances from ``a``.

The check compares ``served`` rows with ``answers`` rows and hands
``served`` the result alone, so ``answers`` notes each deployment it
answered (``_checked``, by identity, while the deployment lives) for
``served`` to walk the witness against: the one whose reference distance
from the start to the end is the served distance, bit for bit. A served
answer that two such deployments judge differently raises.
"""
from __future__ import annotations

import weakref
from typing import Dict, List, Tuple

import numpy as np

from bench import reference_sssp

TIE_RTOL = TIE_ATOL = 1e-6  # the engine's parent rule
_checked: Dict[int, Tuple[weakref.ref, str]] = {}  # id(dep) -> (dep, weight)


def build(q: Dict):
    """The query through the engine's public builder."""
    from repro.core.query import P, Query, col, param

    PS = P("PS")
    return (Query().from_paths("G", "PS")
            .where((PS.start.id == param("src")) & (PS.end.id == param("dst")))
            .hint_shortest_path(q["weight"])
            .hint_max_length(int(q["max_len"]))
            .select(start=PS.start.id, end=PS.end.id, distance=col("PS.distance"),
                    length=col("PS.length"), edges=col("PS._edges")))


def _row(src: int, dst: int, dist: np.float32, witness_ok: bool) -> np.ndarray:
    """(start, end, distance's float32 bits, 1 if the witness holds)."""
    bits = int(np.float32(dist).view(np.int32))
    return np.asarray([[src, dst, bits, int(witness_ok)]], np.int64)


def answers(dep, q: Dict, params: List[Dict[str, int]]) -> List[np.ndarray]:
    """The reference's rows for each request: one SSSP per distinct root."""
    graph = reference_sssp.weighted(dep, q["weight"])
    dist = {r: graph.distances(r) for r in sorted({p["src"] for p in params})}
    _checked[id(dep)] = (weakref.ref(dep), q["weight"])
    out = []
    for p in params:
        d = dist[p["src"]][p["dst"]]
        out.append(_row(p["src"], p["dst"], d, True) if np.isfinite(d)
                   else np.zeros((0, 4), np.int64))
    return out


def witness(dep, weight: str, dist: np.ndarray, end: int, edges) -> int:
    """Where the witness ``edges`` (edge rows, from ``end`` back towards the
    start) begins, or -1 where a row is no edge of the deployment, does not
    continue the path, or is not tight under the parent rule in ``dist``."""
    src, dst = dep.edge["src"], dep.edge["dst"]
    w = dep.edge[weight]
    v = end
    for r in (int(e) for e in edges):
        if not 0 <= r < dep.n_edges:
            return -1
        if dst[r] == v:
            u = int(src[r])
        elif src[r] == v and not dep.directed:
            u = int(dst[r])
        else:
            return -1
        if not np.isclose(np.float32(dist[u] + w[r]), dist[v],
                          rtol=TIE_RTOL, atol=TIE_ATOL):
            return -1
        v = u
    return v


def served(q: Dict, result) -> np.ndarray:
    """A served ``QueryResult`` in the reference's row form; its witness is
    walked against each answered deployment whose reference distance from
    its start to its end is the served one (any answered one, if none is)."""
    if result.count == 0:
        return np.zeros((0, 4), np.int64)
    start = int(result.columns["start"][0])
    end = int(result.columns["end"][0])
    length = int(result.columns["length"][0])
    edges = np.asarray(result.columns["edges"][0])[:length]
    distance = np.float32(result.columns["distance"][0])
    for k in [k for k, (ref, _) in _checked.items() if ref() is None]:
        del _checked[k]
    live = [(ref(), weight) for ref, weight in _checked.values()]
    dists = [reference_sssp.weighted(dep, weight).distances(start)
             if start < dep.n_vertices else None for dep, weight in live]
    fits = [i for i, d in enumerate(dists)
            if d is not None and end < d.shape[0] and d[end].tobytes() == distance.tobytes()]
    judged = {witness(*live[i], dists[i], end, edges) == start
              for i in (fits or [i for i, d in enumerate(dists) if d is not None])}
    if len(judged) != 1:
        raise RuntimeError(f"no single answered deployment judges the answer from {start}"
                           f" ({len(live)} answered): call answers for its deployment")
    return _row(start, end, distance, judged.pop())
