"""Differential traversal harness: every backend vs. the reference oracle.

This is the safety net every future perf PR runs under: a seeded corpus of
graph-shape families, and for each one the assertion that `xla_coo`,
`pallas_frontier`, and `reference` produce **bit-identical** BFS distances,
SSSP distances, and SSSP parent slots (parents always come from the
canonical blocked-COO parent pass, so distance identity implies parent
identity — both are asserted anyway). Path counts from the single
enumeration implementation are checked against an independent numpy brute
force. Run just this suite with:

    python -m pytest -q -m differential
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import GRFusion
from repro.core.graphview import build_graph_view
from repro.core.table import Table
from repro.core.traversal_engine import (
    BACKENDS,
    TraversalEngine,
    count_paths_reference,
)

pytestmark = pytest.mark.differential

FAMILIES = [
    "erdos_renyi",
    "powerlaw",
    "chain",
    "self_loops",
    "isolated_vertices",
    "duplicate_edges",
    "tombstoned_edges",
    "delta_buffer",
    "undirected",
]


def _raw_edges(family: str, seed: int):
    """(n_vertices, src, dst) for the structural families."""
    rng = np.random.default_rng((zlib.crc32(family.encode()), seed))
    if family == "erdos_renyi":
        n, e = 28, 90
        return n, rng.integers(0, n, e), rng.integers(0, n, e)
    if family == "powerlaw":
        n, e = 30, 80
        ranks = np.arange(1, n + 1)
        p = 1.0 / ranks**0.9
        p /= p.sum()
        return n, rng.choice(n, e, p=p), rng.choice(n, e, p=p)
    if family == "chain":
        n = 24
        return n, np.arange(n - 1), np.arange(1, n)
    if family == "self_loops":
        n, e = 20, 50
        src = rng.integers(0, n, e)
        dst = rng.integers(0, n, e)
        loops = rng.integers(0, n, 6)
        return n, np.concatenate([src, loops]), np.concatenate([dst, loops])
    if family == "isolated_vertices":
        n, e = 32, 40
        live = rng.permutation(n)[: n // 2]  # half the vertices get no edges
        return n, rng.choice(live, e), rng.choice(live, e)
    if family == "duplicate_edges":
        n = 16
        src = rng.integers(0, n, 30)
        dst = rng.integers(0, n, 30)
        dup = rng.integers(0, 30, 12)  # repeat some edges verbatim
        return n, np.concatenate([src, src[dup]]), np.concatenate([dst, dst[dup]])
    raise ValueError(family)


def build_case(family: str, seed: int):
    """Returns (view, weight_by_row, edge_mask_by_row_or_None)."""
    rng = np.random.default_rng((zlib.crc32(family.encode()), seed, 1))
    if family == "tombstoned_edges":
        n, src, dst = _raw_edges("erdos_renyi", seed)
        w = rng.uniform(0.1, 5.0, len(src)).astype(np.float32)
        vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
        et = Table.create(
            "E", {"src": src.astype(np.int32), "dst": dst.astype(np.int32), "w": w}
        )
        view = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")
        # tombstone ~1/4 of the rows AFTER construction: the view keeps the
        # stale topology; traversals must honor the validity mask gather
        dead = jnp.asarray(rng.random(et.capacity) < 0.25)
        et = et.delete(dead)
        return view, jnp.asarray(w), et.valid
    if family == "delta_buffer":
        n, src, dst = _raw_edges("erdos_renyi", seed)
        k = 12  # last k edges arrive through the online-insert delta path
        w = rng.uniform(0.1, 5.0, len(src)).astype(np.float32)
        eng = GRFusion()
        eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
        eng.create_table(
            "E",
            {"src": src[:-k].astype(np.int32), "dst": dst[:-k].astype(np.int32),
             "w": w[:-k]},
            capacity=len(src),
        )
        eng.create_graph_view(
            "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst"
        )
        eng.insert(
            "E",
            {"src": src[-k:].astype(np.int32), "dst": dst[-k:].astype(np.int32),
             "w": w[-k:]},
        )
        vb = eng.views["G"]
        assert bool(jnp.any(vb.view.delta_valid)), "delta buffer must be live"
        return vb.view, eng.tables["E"].col("w"), eng.tables["E"].valid
    directed = family != "undirected"
    n, src, dst = _raw_edges("erdos_renyi" if not directed else family, seed)
    w = rng.uniform(0.1, 5.0, len(src)).astype(np.float32)
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    et = Table.create(
        "E", {"src": src.astype(np.int32), "dst": dst.astype(np.int32), "w": w}
    )
    view = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst",
                            directed=directed)
    return view, jnp.asarray(w), None


def _sources(view, seed, s=8):
    rng = np.random.default_rng(seed + 17)
    return jnp.asarray(rng.integers(0, view.n_vertices, s), jnp.int32)


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("family", FAMILIES)
def test_bfs_bit_identical_across_backends(family, seed):
    view, _, emask = build_case(family, seed)
    te = TraversalEngine()
    srcs = _sources(view, seed)
    dists = {
        b: np.asarray(
            te.bfs(view, srcs, edge_mask_by_row=emask, max_hops=24, backend=b)
        )
        for b in BACKENDS
    }
    ref = dists["reference"]
    assert ref.dtype == np.int32
    for b in BACKENDS:
        assert (dists[b] == ref).all(), (
            family, b, np.argwhere(dists[b] != ref)[:5],
        )


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("family", FAMILIES)
def test_sssp_bit_identical_across_backends(family, seed):
    view, w, emask = build_case(family, seed)
    te = TraversalEngine()
    srcs = _sources(view, seed, s=4)
    out = {
        b: te.sssp(
            view, srcs, w, edge_mask_by_row=emask, backend=b
        )
        for b in BACKENDS
    }
    dref, pref = (np.asarray(x) for x in out["reference"])
    for b in BACKENDS:
        d, p = (np.asarray(x) for x in out[b])
        # bit-identical: float32 fixpoint distances AND canonical parents
        assert d.tobytes() == dref.tobytes(), (family, b)
        assert (p == pref).all(), (family, b)
    _check_parents_consistent(view, w, emask, dref, pref, srcs)


def _check_parents_consistent(view, w, emask, dist, parent, srcs):
    """Semantic check: each parent slot is a live edge that achieves the
    destination's distance (guards against all backends sharing a bug)."""
    src_a, dst_a, eid_a = (np.asarray(a) for a in view.all_coo())
    w_rows = np.asarray(w)
    ok_rows = np.ones(w_rows.shape[0], bool) if emask is None else np.asarray(emask)
    S, V = dist.shape
    for s in range(S):
        for v in range(V):
            slot = parent[s, v]
            if slot < 0:
                continue
            assert slot < len(src_a)
            e = eid_a[slot]
            assert e >= 0 and ok_rows[e]
            assert dst_a[slot] == v
            cand = np.float32(dist[s, src_a[slot]] + w_rows[e])
            assert np.isclose(cand, dist[s, v], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("family", ["erdos_renyi", "chain", "self_loops",
                                    "duplicate_edges", "tombstoned_edges"])
def test_path_counts_match_bruteforce(family):
    view, _, emask = build_case(family, 0)
    te = TraversalEngine()
    starts = jnp.arange(min(view.n_vertices, 6), dtype=jnp.int32)
    masks = None if emask is None else [emask] * 3
    out = te.enumerate_paths(
        view, starts, min_len=1, max_len=3,
        hop_edge_masks=masks,
        work_capacity=1 << 14, result_capacity=1, count_only=True,
    )
    cnt, ovf = out
    assert not bool(ovf)
    expect = count_paths_reference(
        view, starts, min_len=1, max_len=3, edge_mask_by_row=emask
    )
    assert int(cnt) == expect, family


@pytest.mark.parametrize("family", ["erdos_renyi", "powerlaw", "undirected"])
def test_bfs_with_vertex_mask_bit_identical(family):
    view, _, emask = build_case(family, 1)
    rng = np.random.default_rng(21)
    vm = jnp.asarray(rng.random(view.n_vertices) < 0.7)
    te = TraversalEngine()
    srcs = _sources(view, 1)
    dists = {
        b: np.asarray(
            te.bfs(view, srcs, edge_mask_by_row=emask, vertex_mask=vm,
                   max_hops=24, backend=b)
        )
        for b in BACKENDS
    }
    assert (dists["reference"] >= -1).all()
    for b in BACKENDS:
        assert (dists[b] == dists["reference"]).all(), (family, b)


def test_bfs_with_targets_bit_identical():
    # the pallas host loop and numpy oracle mirror the XLA while-loop's stop
    # conditions exactly, so even the partially-swept dist matrices under
    # target early-exit match bit-for-bit
    view, _, _ = build_case("powerlaw", 4)
    te = TraversalEngine()
    srcs = _sources(view, 4)
    rng = np.random.default_rng(5)
    tgts = jnp.asarray(
        rng.integers(0, view.n_vertices, srcs.shape[0]), jnp.int32
    )
    dists = {
        b: np.asarray(
            te.bfs(view, srcs, target_pos=tgts, max_hops=24, backend=b)
        )
        for b in BACKENDS
    }
    for b in BACKENDS:
        assert (dists[b] == dists["reference"]).all(), b


def test_sssp_with_vertex_mask_bit_identical():
    view, w, emask = build_case("tombstoned_edges", 1)
    rng = np.random.default_rng(31)
    vm = jnp.asarray(rng.random(view.n_vertices) < 0.8)
    te = TraversalEngine()
    srcs = _sources(view, 1, s=4)
    out = {
        b: te.sssp(view, srcs, w, edge_mask_by_row=emask, vertex_mask=vm,
                   backend=b)
        for b in BACKENDS
    }
    dref, pref = (np.asarray(x) for x in out["reference"])
    for b in BACKENDS:
        d, p = (np.asarray(x) for x in out[b])
        assert d.tobytes() == dref.tobytes(), b
        assert (p == pref).all(), b


def test_packing_cache_hit_on_repeated_query():
    """Acceptance: the second query over the same topology re-sorts and
    re-traces nothing — pack built once, then pure cache hits."""
    view, w, _ = build_case("erdos_renyi", 3)
    te = TraversalEngine()
    srcs = _sources(view, 3)
    te.bfs(view, srcs, max_hops=16, backend="pallas_frontier")
    assert te.stats["pack_builds"] == 1 and te.stats["pack_hits"] == 0
    te.bfs(view, srcs, max_hops=16, backend="pallas_frontier")
    te.sssp(view, srcs, w, backend="pallas_frontier")
    assert te.stats["pack_builds"] == 1  # no re-sort
    assert te.stats["pack_hits"] == 2
    # xla_coo plan cache: same shapes => one trace across repeated queries
    te.bfs(view, srcs, max_hops=16, backend="xla_coo")
    t1 = te.stats["traces_bfs_xla"]
    te.bfs(view, srcs, max_hops=16, backend="xla_coo")
    assert te.stats["traces_bfs_xla"] == t1  # no re-trace


def test_delta_insert_keeps_pack_warm_compaction_invalidates():
    """Epoch split: a delta-only insert must be visible to queries WITHOUT
    rebuilding the dst-sort pack (backends consult the delta stream at
    query time); only compaction bumps the packing epoch and re-packs."""
    eng = GRFusion()
    n = 16
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {"src": np.arange(n - 1, dtype=np.int32),
         "dst": np.arange(1, n, dtype=np.int32),
         "w": np.ones(n - 1, np.float32)},
        capacity=n + 8,
    )
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    te = eng.traversal
    view = eng.views["G"].view
    srcs = jnp.zeros((4,), jnp.int32)
    d0 = np.asarray(te.bfs(view, srcs, max_hops=20,
                           backend="pallas_frontier", graph="G"))
    assert d0[0, n - 1] == n - 1
    assert te.stats["pack_builds"] == 1
    # shortcut edge 0 -> n-1 lands in the delta buffer; the pack stays warm
    eng.insert("E", {"src": np.array([0], np.int32),
                     "dst": np.array([n - 1], np.int32),
                     "w": np.array([1.0], np.float32)})
    view2 = eng.views["G"].view
    assert bool(jnp.any(view2.delta_valid))  # still uncompacted
    d1 = np.asarray(te.bfs(view2, srcs, max_hops=20,
                           backend="pallas_frontier", graph="G"))
    assert d1[0, n - 1] == 1  # new edge visible from the delta stream...
    assert te.stats["pack_builds"] == 1  # ...with ZERO re-packs
    # compaction folds the delta into main and DOES invalidate the pack
    eng.compact("G")
    view3 = eng.views["G"].view
    assert not bool(jnp.any(view3.delta_valid))
    d2 = np.asarray(te.bfs(view3, srcs, max_hops=20,
                           backend="pallas_frontier", graph="G"))
    assert d2[0, n - 1] == 1
    assert te.stats["pack_builds"] == 2


def test_batched_admission_merges_into_one_sweep():
    view, w, _ = build_case("powerlaw", 5)
    te = TraversalEngine(lane_width=16)
    rng = np.random.default_rng(9)
    pairs = [(int(a), int(b)) for a, b in
             rng.integers(0, view.n_vertices, (10, 2))]
    handles = [te.submit_reachability(view, a, b) for a, b in pairs]
    done = te.flush(max_hops=24, backend="xla_coo")
    assert len(done) == len(pairs)
    assert te.stats["queries_bfs"] == 1  # all ten merged into one [S, V] sweep
    for (a, b), h in zip(pairs, handles):
        d = np.asarray(te.bfs(view, jnp.asarray([a], jnp.int32),
                              max_hops=24, backend="reference"))[0, b]
        assert h.result["reachable"] == (d >= 0)
        if d >= 0:
            assert h.result["hops"] == int(d)
