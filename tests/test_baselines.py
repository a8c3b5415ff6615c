"""Baseline equivalence: the engine, SQLGraph-joins, and Grail must agree."""
import heapq

import jax.numpy as jnp
import numpy as np
import pytest

from repro.baselines.grail import grail_sssp
from repro.baselines.sqlgraph import reachability_joins, triangle_count_joins
from repro.core import traversal as T
from repro.core.graphview import build_graph_view
from repro.core.table import Table
from repro.data.synthetic import graph_tables, random_graph


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reachability_equivalence(seed):
    g = random_graph(150, 600, seed=seed)
    vd, ed = graph_tables(g)
    vt, et = Table.create("V", vd), Table.create("E", ed)
    gv = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")
    rng = np.random.default_rng(seed)
    S = 12
    srcs = rng.integers(0, 150, S).astype(np.int32)
    tgts = rng.integers(0, 150, S).astype(np.int32)
    dist = T.bfs(gv, jnp.asarray(srcs), max_hops=5)
    native = np.asarray(dist[np.arange(S), tgts] >= 0) | (srcs == tgts)
    joined, ovf = reachability_joins(
        et, "src", "dst", jnp.asarray(srcs), jnp.asarray(tgts),
        n_hops=5, frontier_capacity=1 << 13,
    )
    assert not bool(ovf)
    assert (native == np.asarray(joined)).all()


@pytest.mark.parametrize("seed", [0, 3])
def test_triangle_equivalence(seed):
    g = random_graph(120, 700, seed=seed)
    vd, ed = graph_tables(g)
    vt, et = Table.create("V", vd), Table.create("E", ed)
    gv = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")
    masks = tuple(jnp.asarray(ed["label"] == i) for i in range(3))
    tn, ovf = T.count_closed_triangles(gv, list(masks), work_capacity=1 << 15)
    tj = triangle_count_joins(et, "src", "dst", masks, capacity=1 << 16)
    assert not bool(ovf)
    assert int(tn) == int(tj)


@pytest.mark.parametrize("seed", [0, 1])
def test_sssp_equivalence_with_dijkstra(seed):
    g = random_graph(150, 600, seed=seed)
    vd, ed = graph_tables(g)
    vt, et = Table.create("V", vd), Table.create("E", ed)
    gv = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")
    d_g = np.asarray(grail_sssp(et, "src", "dst", "weight", jnp.int32(0),
                                n_vertices=150, n_iters=160, capacity=1 << 13))
    d_n = np.asarray(T.sssp(gv, jnp.array([0], jnp.int32),
                            weight_by_row=jnp.asarray(ed["weight"]))[0][0])
    adj = {}
    for a, b, w in zip(ed["src"], ed["dst"], ed["weight"]):
        adj.setdefault(int(a), []).append((int(b), float(w)))
    ref = np.full(150, np.inf)
    ref[0] = 0
    pq = [(0.0, 0)]
    while pq:
        du, u = heapq.heappop(pq)
        if du > ref[u]:
            continue
        for v, w in adj.get(u, ()):  # noqa: B905
            if du + w < ref[v] - 1e-9:
                ref[v] = du + w
                heapq.heappush(pq, (du + w, v))
    fin = np.isfinite(ref)
    for d in (d_g, d_n):
        assert (np.isfinite(d) == fin).all()
        assert np.abs(d[fin] - ref[fin]).max() < 1e-3
