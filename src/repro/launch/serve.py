"""Serving launcher: ``python -m repro.launch.serve --arch <id> [--smoke]``.

LM family: slot-based continuous-batching decode demo.
Engine (grfusion): batched reachability query serving over a synthetic
power-law social graph — the paper-side serving path. ``--vertices`` and
``--edges`` size the graph; the paper's Twitter-scale cell is
``--vertices 4194304 --edges 33554432`` (``configs/grfusion.py``).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro import configs
from repro.launch.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="grfusion")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument(
        "--backend", default=None,
        choices=["auto", "xla_coo", "pallas_frontier", "reference", "sharded"],
        help="traversal backend for the graph-query serving path",
    )
    ap.add_argument("--vertices", type=int, default=5000,
                    help="graph size (engine only)")
    ap.add_argument("--edges", type=int, default=25000,
                    help="edge count (engine only)")
    args = ap.parse_args()
    enable_compile_cache()

    module = configs.get(args.arch)
    rng = jax.random.PRNGKey(0)

    if module.FAMILY == "lm":
        from repro.models import transformer as TF
        from repro.serve.engine import LMServer, Request

        cfg = module.smoke_config()
        params = TF.init_params(rng, cfg)
        srv = LMServer(params, cfg, n_slots=4, max_len=64)
        done = []
        rid = 0
        rnp = np.random.default_rng(0)
        while len(done) < args.requests:
            while rid < args.requests and srv.submit(
                Request(rid, rnp.integers(0, cfg.vocab, 4).astype(np.int32), max_new=8)
            ):
                rid += 1
            done += srv.step()
        print(f"served {len(done)} requests; sample output: {done[0].out}")
        return

    # graph-relational query serving (the paper's workload)
    from repro.core.engine import GRFusion
    from repro.data.synthetic import graph_tables, random_graph
    from repro.serve.engine import QueryServer

    V = args.vertices
    g = random_graph(V, args.edges, kind="powerlaw", seed=0)
    vd, ed = graph_tables(g)
    eng = GRFusion(traversal_backend=args.backend or "auto")
    eng.create_table("V", vd)
    eng.create_table("E", ed, capacity=len(ed["src"]) + 1024)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    srv = QueryServer(eng, "G", lane_width=32, max_hops=12,
                      backend=args.backend)
    rnp = np.random.default_rng(1)
    for _ in range(args.requests):
        srv.submit(int(rnp.integers(0, V)), int(rnp.integers(0, V)))
    results = srv.flush()
    reach = sum(r["reachable"] for r in results)
    stats = dict(eng.traversal.stats)
    print(f"answered {len(results)} reachability queries; {reach} reachable")
    print(f"traversal stats: {stats}")

    # pre-optimized plan admission: the rule pipeline runs once, the
    # physical tree is re-walked per request (repeated parameterized
    # queries skip re-planning on the serving hot path)
    from repro.core.query import Query, P, col

    PS = P("PS")
    prepared = srv.prepare(
        Query().from_paths("G", "PS")
        .where((PS.start.id == 0) & (PS.length <= 3))
        .select_count("n")
    )
    for _ in range(4):
        srv.submit_plan(prepared)
    outs = srv.flush_plans()
    print(f"prepared plan served {len(outs)} times; "
          f"paths from vertex 0 (<=3 hops): {int(outs[0].columns['n'])}")
    print(prepared.pretty())


if __name__ == "__main__":
    main()
