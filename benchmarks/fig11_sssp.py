"""Fig 11 (Appendix D): SSSP with filtering predicates — native SPScan
(frontier Bellman-Ford) vs. Grail-style vertex-centric iterative SQL.
Distances are cross-checked for equality on the selected sub-graph.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.baselines.grail import grail_sssp
from repro.core import traversal as T
from repro.core.engine import GRFusion
from repro.core.graphview import build_graph_view
from repro.core.query import Query, P, col
from repro.core.table import Table
from repro.data.synthetic import graph_tables, random_graph

from .common import time_call


def run(quick: bool = False):
    # road-network-like: low, near-uniform degree
    V, E = (2_000, 6_000) if quick else (10_000, 30_000)
    sels = [25] if quick else [10, 25, 50]
    g = random_graph(V, E, kind="uniform", seed=13)
    vd, ed = graph_tables(g)
    vt, et = Table.create("V", vd), Table.create("E", ed)
    view = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")
    w = jnp.asarray(ed["weight"])
    sel = jnp.asarray(ed["sel"])

    # plan-IR path: SHORTESTPATH hint -> physical SPScan over the predicate
    # sub-graph, planned once per selectivity and re-executed
    eng = GRFusion()
    eng.create_table("V", vd)
    eng.create_table("E", ed)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")

    rows = []
    for s in sels:
        mask = sel < s
        native = functools.partial(
            T.sssp, view, jnp.array([0], jnp.int32), weight_by_row=w,
            edge_mask_by_row=mask, block_size=1 << 15,
        )
        us_nat = time_call(native)
        # Grail runs a fixed number of rounds: as many as SPScan needed
        rounds = int(jax.jit(T.sssp_rounds, static_argnames=T.SSSP_STATIC_ARGNAMES)(
            view, jnp.array([0], jnp.int32), w, mask, block_size=1 << 15)[2])
        base = functools.partial(
            grail_sssp, et, "src", "dst", "weight", jnp.int32(0), mask,
            n_vertices=V, n_iters=rounds, capacity=1 << 16,
        )
        us_grail = time_call(base)

        dn = np.asarray(native()[0][0])
        dg = np.asarray(base())
        fin = np.isfinite(dn) & np.isfinite(dg)
        assert (np.isfinite(dn) == np.isfinite(dg)).all()
        assert np.abs(dn[fin] - dg[fin]).max() < 1e-3

        rows.append((f"fig11/native_spscan/sel={s}%", us_nat, "sssp-us"))
        rows.append(
            (f"fig11/grail_iterative/sel={s}%", us_grail, f"speedup={us_grail/us_nat:.1f}x")
        )

        RS = P("RS")
        prepared = eng.prepare(
            Query().from_paths("G", "RS")
            .hint_shortest_path("weight")
            .where((RS.start.id == 0) & (RS.edges[0:"*"].attr("sel") < s))
            .select(dist=col("RS.distance"), end=col("RS.endvertexid"))
        )
        us_plan = time_call(prepared.run)
        r = prepared.run()
        # both run to convergence: the plan reaches what the native sweep does
        assert r.count >= int(np.isfinite(dn).sum()), "plan-IR SPScan lost vertices"
        rows.append(
            (f"fig11/planned_spscan/sel={s}%", us_plan, f"reached={r.count}")
        )
    return rows
