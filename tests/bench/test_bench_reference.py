"""The plain reference: against a loop version of its semantics, against the
engine's served answers, and the check's refusal of a perturbed answer."""
import numpy as np
import pytest

import tiny_tree
from bench import reference
from bench.registry import Registry


@pytest.fixture(scope="module")
def served():
    """A tiny GAP-urand and a tiny Graph500 deployment, each loaded into the
    engine and asked its cell's queries through the serving loop."""
    from repro.core.engine import GRFusion

    reg = Registry(tiny_tree.REPO)
    out = {}
    for config, mix, sizes in (
        ("gap-urand-s22", "nbr2_open", {"scale": 9}),
        ("graph500-s21", "reach_p2p8", {"scale": 9}),
    ):
        cfg = dict(reg.config(config), **sizes)
        dep = reg.generator(cfg["generator"]).generate(cfg, 2**35 + 1)
        eng = GRFusion(**cfg["engine"])
        eng.create_table("V", dep.vertex)
        eng.create_table("E", dep.edge)
        eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                              e_src="src", e_dst="dst", directed=dep.directed)
        mix = reg.traffic(mix)
        q = mix["query"]
        kind = reg.query(q["kind"])
        rng = np.random.default_rng(0)
        if q["kind"] == "paths_from":
            params = [{"src": int(v)} for v in rng.integers(0, dep.n_vertices, 24)]
        else:
            params = [{"src": int(a), "dst": int(b)}
                      for a, b in rng.integers(0, dep.n_vertices, (24, 2)) if a != b]
        loop = eng.serving_loop()
        tickets = [loop.submit(kind.build(q), **p) for p in params]
        loop.drain()
        out[q["kind"]] = (kind, dep, q, params, tickets)
    return out


def _expected(kind, dep, q, p):
    return kind.answers(dep, q, [p])[0]


def test_paths_from_matches_a_plain_loop():
    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config("gap-urand-s22"), scale=6, edgefactor=4)
    dep = reg.generator("uniform").generate(cfg, 3)
    q = {"kind": "paths_from", "min_len": 1, "max_len": 3,
         "edge_predicate": ["sel", "<", 50]}
    out = {}
    for s, d, sel in zip(dep.edge["src"], dep.edge["dst"], dep.edge["sel"]):
        if sel < 50:  # undirected: each edge both ways
            out.setdefault(int(s), []).append(int(d))
            out.setdefault(int(d), []).append(int(s))
    kind = reg.query("paths_from")
    adj, ok = reference.Adjacency(dep), dep.edge["sel"] < 50
    for start in range(64):
        rows, paths = [], [[start]]
        for n in range(1, 4):
            paths = [p + [d] for p in paths for d in out.get(p[-1], []) if d not in p]
            rows += [(p[-1], n) for p in paths]
        np.testing.assert_array_equal(
            kind.paths(adj, q, start, ok).reshape(-1, 2),
            np.asarray(sorted(rows), np.int64).reshape(-1, 2))


def test_hop_distances_match_a_plain_bfs():
    reg = Registry(tiny_tree.REPO)
    for config, sizes in (("graph500-s21", {"scale": 8}),
                          ("gap-urand-s22", {"scale": 8, "directed": True})):
        cfg = dict(reg.config(config), **sizes)
        dep = reg.generator(cfg["generator"]).generate(cfg, 4)
        fwd = reference.Adjacency(dep)
        roots = list(range(0, dep.n_vertices, 3))  # more than one 64-root word
        got = reference.hop_distances(reference.Adjacency(dep, reverse=True),
                                      dep.n_vertices, roots, 32)
        for i, root in enumerate(roots):
            dist = np.full(dep.n_vertices, -1)
            dist[root], frontier, hop = 0, [root], 0
            while frontier:
                hop += 1
                nxt = {int(v) for u in frontier for v in fwd.expand(np.asarray([u]))[1]
                       if dist[v] < 0}
                for v in nxt:
                    dist[v] = hop
                frontier = sorted(nxt)
            np.testing.assert_array_equal(got[i], dist)


def test_reference_equals_served_answers(served):
    for kind, dep, q, params, tickets in served.values():
        for p, t in zip(params, tickets):
            assert t.status == "done"
            assert reference.same(kind, q, t.result, _expected(kind, dep, q, p)), p


def test_one_perturbed_answer_fails_the_check(served):
    kind, dep, q, params, tickets = served["paths_from"]
    i = next(i for i, t in enumerate(tickets) if t.result.count > 0)
    res, exp = tickets[i].result, _expected(kind, dep, q, params[i])
    assert reference.same(kind, q, res, exp)
    ends = np.array(res.columns["end"])
    ends[-1] = (ends[-1] + 1) % dep.n_vertices
    bad = type(res)(columns={**res.columns, "end": ends}, count=res.count,
                    explain=res.explain, overflow=res.overflow)
    assert not reference.same(kind, q, bad, exp)
    assert not reference.same(kind, q, type(res)(columns=res.columns, count=res.count,
                                                 explain=res.explain, overflow=True), exp)
    kind, dep, q, params, tickets = served["reach"]
    i = next(i for i, t in enumerate(tickets) if t.result.count > 0)
    res = tickets[i].result
    longer = type(res)(columns={**res.columns, "length": res.columns["length"] + 1},
                       count=res.count, explain=res.explain, overflow=False)
    assert not reference.same(kind, q, longer, _expected(kind, dep, q, params[i]))
