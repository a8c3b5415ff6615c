"""Traversal physical operators vs. independent oracles (hypothesis)."""
import heapq

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st

from differential.test_backends import FAMILIES, _sources, build_case
from repro.core import traversal as T
from repro.core.graphview import build_graph_view
from repro.core.table import Table
from repro.core.traversal_engine import TraversalEngine


def make_view(n, src, dst, extra_cols=None, directed=True):
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    ed = {"src": np.asarray(src, np.int32), "dst": np.asarray(dst, np.int32)}
    ed.update(extra_cols or {})
    et = Table.create("E", ed)
    return build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst",
                            directed=directed), et


graphs = st.integers(2, 24).flatmap(
    lambda n: st.tuples(
        st.just(n),
        st.lists(st.tuples(st.integers(0, n - 1), st.integers(0, n - 1)),
                 min_size=1, max_size=60),
    )
)


@settings(max_examples=25, deadline=None)
@given(graphs)
def test_bfs_matches_matrix_power_closure(g):
    n, edges = g
    src = [a for a, b in edges]
    dst = [b for a, b in edges]
    view, _ = make_view(n, src, dst)
    dist = np.asarray(T.bfs(view, jnp.arange(n, dtype=jnp.int32), max_hops=n))
    # oracle: boolean adjacency powers
    A = np.zeros((n, n), bool)
    A[src, dst] = True
    reach = np.eye(n, dtype=bool)
    expect = np.full((n, n), -1)
    np.fill_diagonal(expect, 0)
    frontier = np.eye(n, dtype=bool)
    for h in range(1, n + 1):
        frontier = (frontier @ A) & ~reach
        expect[frontier & (expect == -1)] = h
        reach |= frontier
    assert (dist == expect).all()


@settings(max_examples=20, deadline=None)
@given(graphs, st.integers(0, 2**31 - 1))
def test_sssp_matches_dijkstra(g, seed):
    n, edges = g
    src = np.array([a for a, b in edges])
    dst = np.array([b for a, b in edges])
    w = np.random.default_rng(seed).uniform(0.1, 5.0, len(edges)).astype(np.float32)
    view, _ = make_view(n, src, dst, {"w": w})
    d = np.asarray(
        T.sssp(view, jnp.array([0], jnp.int32), weight_by_row=jnp.asarray(w))[0][0]
    )
    adj = {}
    for a, b, ww in zip(src, dst, w):
        adj.setdefault(int(a), []).append((int(b), float(ww)))
    ref = np.full(n, np.inf)
    ref[0] = 0.0
    pq = [(0.0, 0)]
    while pq:
        du, u = heapq.heappop(pq)
        if du > ref[u]:
            continue
        for v_, ww in adj.get(u, ()):  # noqa: B905
            nd = du + ww
            if nd < ref[v_] - 1e-9:
                ref[v_] = nd
                heapq.heappush(pq, (nd, v_))
    assert (np.isfinite(d) == np.isfinite(ref)).all()
    fin = np.isfinite(ref)
    assert np.abs(d[fin] - ref[fin]).max() < 1e-3


def _brute_paths(n, edges, start, min_len, max_len, close_loop=False):
    adj = {}
    for i, (a, b) in enumerate(edges):
        adj.setdefault(a, []).append((b, i))
    out = []

    def rec(path_v, path_e):
        L = len(path_e)
        if min_len <= L <= max_len:
            if not close_loop or (L == max_len and path_v[-1] == path_v[0]):
                out.append(tuple(path_e))
        if L == max_len:
            return
        for (nb, ei) in adj.get(path_v[-1], ()):  # noqa: B905
            closing = close_loop and L == max_len - 1 and nb == path_v[0]
            if nb in path_v and not closing:
                continue
            if not close_loop or L < max_len - 1 or closing:
                rec(path_v + [nb], path_e + [ei])

    rec([start], [])
    return set(out)


@settings(max_examples=20, deadline=None)
@given(graphs)
def test_enumeration_matches_bruteforce(g):
    n, edges = g
    src = [a for a, b in edges]
    dst = [b for a, b in edges]
    view, _ = make_view(n, src, dst)
    ps = T.enumerate_paths_jit(
        view, jnp.array([0], jnp.int32), min_len=1, max_len=3,
        work_capacity=1 << 12, result_capacity=1 << 12,
    )
    got = set()
    cnt = int(ps.count)
    for i in range(cnt):
        L = int(ps.length[i])
        got.add(tuple(int(e) for e in np.asarray(ps.edges[i][:L])))
    expect = _brute_paths(n, edges, 0, 1, 3)
    assert got == expect, (got ^ expect)


@settings(max_examples=20, deadline=None)
@given(graphs)
def test_triangle_count_matches_bruteforce(g):
    n, edges = g
    src = [a for a, b in edges]
    dst = [b for a, b in edges]
    view, et = make_view(n, src, dst)
    masks = [jnp.ones((et.capacity,), bool)] * 3
    cnt, ovf = T.count_closed_triangles(view, masks, work_capacity=1 << 14)
    assert not bool(ovf)
    expect = 0
    for s in range(n):
        expect += len(_brute_paths(n, edges, s, 3, 3, close_loop=True))
    assert int(cnt) == expect


def test_path_reconstruction():
    # chain 0->1->2->3 with a costly shortcut 0->3
    view, et = make_view(4, [0, 1, 2, 0], [1, 2, 3, 3],
                         {"w": np.array([1.0, 1.0, 1.0, 10.0], np.float32)})
    dist, parent = T.sssp(view, jnp.array([0], jnp.int32),
                          weight_by_row=jnp.asarray(et.col("w")))
    edges, verts, length = T.reconstruct_paths(
        view, parent, jnp.array([3], jnp.int32), max_len=8
    )
    assert int(length[0]) == 3
    assert [int(v) for v in verts[0][:4]] == [3, 2, 1, 0]


def test_bfs_respects_edge_and_vertex_masks():
    view, et = make_view(4, [0, 1, 0], [1, 2, 2], {"sel": np.array([1, 1, 0])})
    emask = jnp.asarray(np.array([1, 1, 0], bool))
    d = np.asarray(T.bfs(view, jnp.array([0], jnp.int32),
                         edge_mask_by_row=emask, max_hops=4))[0]
    assert d[2] == 2  # direct edge masked out; path through 1
    vmask = jnp.asarray(np.array([True, False, True, True]))
    d2 = np.asarray(T.bfs(view, jnp.array([0], jnp.int32),
                          edge_mask_by_row=emask, vertex_mask=vmask, max_hops=4))[0]
    assert d2[2] == -1  # vertex 1 excluded => unreachable


# --------------------------------------------------------------------------
# frontier-sparse hops: the same distances and hop counts as the dense sweep
# --------------------------------------------------------------------------
_bfs_hops = jax.jit(T.bfs_hops, static_argnames=T.BFS_STATIC_ARGNAMES)


def _reference(view, srcs, emask=None, vmask=None, tgt=None, max_hops=24):
    return TraversalEngine._bfs_reference(view, srcs, emask, vmask, tgt,
                                          max_hops=max_hops)


def _check_forms(view, srcs, emask=None, vmask=None, tgt=None, *, block,
                 max_hops=24):
    """dist and hops of the while loop (sparse and dense hops mixed) against
    the numpy reference on the active lanes and the dense-only unrolled
    sweep on every lane; returns (reference dist, hops, sparse hops)."""
    dist, hops, sparse = _bfs_hops(view, srcs, emask, vmask, tgt,
                                   max_hops=max_hops, block_size=block)
    dist = np.asarray(dist)
    ref = _reference(view, srcs, emask, vmask, tgt, max_hops)
    active = np.asarray(srcs) >= 0  # the sweeps start lane -1 at vertex V - 1
    assert np.array_equal(dist[active], ref[active])
    if tgt is None:  # the unrolled sweep has no early exit
        dense = T.bfs(view, srcs, emask, vmask, max_hops=max_hops,
                      block_size=1024, unroll_hops=True)
        assert np.array_equal(dist, np.asarray(dense))
    return ref, int(hops), int(sparse)


@pytest.mark.parametrize("block", [16, 1024])
@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("family", FAMILIES)
def test_sparse_hops_match_reference_and_dense(family, lanes, block):
    # block 16: chunks split frontier rows and forms mix; 1024: all sparse
    view, _, emask = build_case(family, 0)
    srcs = _sources(view, 0, s=lanes)
    ref, hops, sparse = _check_forms(view, srcs, emask, block=block)
    assert hops == min(24, int(ref.max()) + 1)  # the last hop finds nothing
    assert 0 <= sparse <= hops
    if block == 1024:
        assert sparse == hops


def _hub_view(k=200, delta_capacity=16):
    """0 -> 1 -> {2..k+1} (the hub), 2 -> k+2 -> k+3: hop 2 fires k edges,
    more than a quarter of the stream, every other hop at most one."""
    n = k + 4
    src = [0] + [1] * k + [2, k + 2]
    dst = [1] + list(range(2, k + 2)) + [k + 2, k + 3]
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    et = Table.create("E", {"src": np.asarray(src, np.int32),
                            "dst": np.asarray(dst, np.int32)})
    view = build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst",
                            delta_capacity=delta_capacity)
    return view, et


@pytest.mark.parametrize("lanes, dense", [
    ([0], 1),  # hop 2 expands the hub
    ([0, -1, 1], 2),  # the hub's lane expands it on hop 1, lane 0 on hop 2
])
def test_hub_runs_sparse_then_dense_then_sparse(lanes, dense):
    view, _ = _hub_view()
    srcs = jnp.asarray(lanes, jnp.int32)
    ref, hops, sparse = _check_forms(view, srcs, block=16)
    assert hops == 5 and ref[0, 203] == 4
    assert 0 < sparse < hops
    assert sparse == hops - dense


def _hub_case(case):
    """(view, sources, edge mask, vertex mask, targets) of the hub graph with
    one more feature: delta edges, a tombstone, a mask or a target."""
    view, et = _hub_view()
    srcs = jnp.asarray([0], jnp.int32)
    emask = vmask = tgt = None
    if case == "delta":  # k+3 -> k+4 -> k+5 arrive through the delta buffer
        view = build_graph_view(
            "G", Table.create("V", {"vid": np.arange(210, dtype=np.int32)}),
            et, v_id="vid", e_src="src", e_dst="dst", delta_capacity=16)
        view, dropped = view.insert_delta(
            jnp.asarray([203, 204], jnp.int32), jnp.asarray([204, 205], jnp.int32),
            jnp.asarray([0, 1], jnp.int32), jnp.asarray([True, True]))
        assert int(dropped) == 0
    elif case == "tombstone":  # row 201 (2 -> k+2) deleted after the build
        emask = et.delete_rows(jnp.asarray([201], jnp.int32)).valid
    elif case == "edge_mask":  # rows 2, 4, .., 200: 1 -> 3, 5, .., 201
        m = np.ones(et.capacity, bool)
        m[2:201:2] = False
        emask = jnp.asarray(m)
    elif case == "vertex_mask":  # vertex 2 excluded: k+2 unreachable
        vmask = jnp.asarray(np.arange(view.n_vertices) != 2)
    elif case == "target":  # stops on hop 3, before the tail's hops
        tgt = jnp.asarray([202], jnp.int32)
    return view, srcs, emask, vmask, tgt


@pytest.mark.parametrize("case, hops, reached", [
    ("delta", 7, {205: 6}),
    ("tombstone", 3, {202: -1, 203: -1, 2: 2}),
    ("edge_mask", 5, {3: -1, 4: 2, 203: 4}),
    ("vertex_mask", 3, {2: -1, 202: -1, 3: 2}),
    ("target", 3, {202: 3, 203: -1}),
])
def test_sparse_hops_with_delta_masks_and_targets(case, hops, reached):
    view, srcs, emask, vmask, tgt = _hub_case(case)
    ref, got_hops, sparse = _check_forms(view, srcs, emask, vmask, tgt, block=16)
    assert got_hops == hops
    assert 0 < sparse < hops  # the hub's hop stays dense
    for v, d in reached.items():
        assert ref[0, v] == d


# --------------------------------------------------------------------------
# frontier-restricted SSSP rounds: the same distances as an f32 Dijkstra
# --------------------------------------------------------------------------
_sssp_rounds = jax.jit(T.sssp_rounds, static_argnames=T.SSSP_STATIC_ARGNAMES)


def _dijkstra_f32(view, w, srcs, emask=None, vmask=None):
    """Dijkstra over the view's live slots, every sum rounded to float32:
    the least fixpoint of ``d[v] = min(fl32(d[u] + w))``, lane by lane."""
    V = view.n_vertices
    src, dst, eid = (np.asarray(a) for a in view.all_coo())
    ok = (eid >= 0) & (src < V) & (dst < V)
    if emask is not None:
        ok &= np.asarray(emask)[np.clip(eid, 0, None)]
    live = np.asarray(view.v_valid) & (True if vmask is None else np.asarray(vmask))
    w = np.asarray(w, np.float32)
    adj = {}
    for a, b, e in zip(src[ok], dst[ok], eid[ok]):
        adj.setdefault(int(a), []).append((int(b), w[e]))
    out = np.full((len(srcs), V), np.inf, np.float32)
    for lane, s in enumerate(np.asarray(srcs)):
        if s < 0 or not live[s]:
            continue
        d = out[lane]
        d[s] = 0.0
        pq = [(np.float32(0.0), int(s))]
        while pq:
            du, u = heapq.heappop(pq)
            if du > d[u]:
                continue
            for v, ww in adj.get(u, ()):
                nd = np.float32(du + ww)
                if live[v] and nd < d[v]:
                    d[v] = nd
                    heapq.heappush(pq, (nd, v))
    return out


def _check_sssp(view, w, srcs, emask=None, vmask=None, *, block):
    """dist of the frontier-restricted rounds against the f32 Dijkstra and
    the reference backend's Jacobi rounds, bit for bit, and the counts;
    returns (dist, rounds, sparse rounds, reached vertices, reached slots)."""
    dist, parent, rounds, sparse, n_vert, n_slot = _sssp_rounds(
        view, srcs, w, emask, vmask, block_size=block)
    dist = np.asarray(dist)
    jacobi = TraversalEngine._sssp_reference_dist(view, srcs, w, emask, vmask)
    assert dist.tobytes() == np.asarray(jacobi, np.float32).tobytes()
    assert dist.tobytes() == _dijkstra_f32(view, w, srcs, emask, vmask).tobytes()
    reached = np.isfinite(dist) & (np.asarray(srcs) >= 0)[:, None]
    fan = np.asarray(view.fan_out)
    assert np.array_equal(np.asarray(n_vert), reached.sum(1))
    assert np.array_equal(np.asarray(n_slot), (reached * fan).sum(1))
    assert 0 <= int(sparse) <= int(rounds)
    return dist, int(rounds), int(sparse), np.asarray(n_vert), np.asarray(n_slot)


@pytest.mark.parametrize("block", [16, 1024])
@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("family", FAMILIES)
def test_frontier_sssp_matches_f32_dijkstra(family, lanes, block):
    view, w, emask = build_case(family, 0)
    srcs = _sources(view, 0, s=lanes)
    _, rounds, sparse, *_ = _check_sssp(view, w, srcs, emask, block=block)
    assert rounds >= 1
    if block == 1024:  # the padded stream is large beside any round's edges
        assert sparse == rounds


def _weighted_hub(case, seed=0):
    """The hub graph of the BFS tests with U[0, 1) float32 weights (a
    quarter of them 0 in the ``zero_weights`` case) and one more feature."""
    view, srcs, emask, vmask, _ = _hub_case(case)  # other names: the plain hub
    rng = np.random.default_rng(seed)
    w = rng.random(view.n_slots).astype(np.float32)
    if case == "zero_weights":
        w[rng.random(w.shape[0]) < 0.25] = 0.0
    return view, srcs, jnp.asarray(w), emask, vmask


@pytest.mark.parametrize("lanes, dense", [
    ([0], 1),  # round 2 relaxes out of the hub
    ([0, -1, 1], 2),  # the hub's lane relaxes it in round 1, lane 0 in round 2
])
def test_sssp_hub_runs_sparse_and_dense_rounds(lanes, dense):
    view, _, w, _, _ = _weighted_hub("plain")
    srcs = jnp.asarray(lanes, jnp.int32)
    dist, rounds, sparse, n_vert, n_slot = _check_sssp(view, w, srcs, block=16)
    assert 0 < sparse < rounds and sparse == rounds - dense
    assert rounds == 5 and np.isfinite(dist[0, 203])
    if len(lanes) > 1:
        assert n_vert[1] == n_slot[1] == 0  # the inactive lane reaches nothing


@pytest.mark.parametrize("case, finite, unreached", [
    ("delta", [205], []),
    ("tombstone", [2], [202, 203]),
    ("edge_mask", [4, 203], [3]),
    ("vertex_mask", [3], [2, 202, 203]),
    ("zero_weights", [203], []),
])
def test_sssp_rounds_with_delta_masks_and_zero_weights(case, finite, unreached):
    view, srcs, w, emask, vmask = _weighted_hub(case)
    dist, rounds, sparse, *_ = _check_sssp(view, w, srcs, emask, vmask, block=16)
    assert 0 < sparse < rounds  # the hub's round stays dense
    assert np.isfinite(dist[0, finite]).all() and np.isinf(dist[0, unreached]).all()


def _weighted_chain(n):
    view, et = make_view(n, np.arange(n - 1), np.arange(1, n),
                         {"w": np.full(n - 1, 0.5, np.float32)})
    return view, et.col("w")


def test_sssp_chain_past_64_rounds_converges():
    view, w = _weighted_chain(100)  # 99 rounds lower a distance, one more finds none
    srcs = jnp.asarray([0], jnp.int32)
    dist, rounds, sparse, *_ = _check_sssp(view, w, srcs, block=1024)
    assert dist[0, 99] == 49.5 and rounds == 100 == sparse
    d, _ = T.sssp(view, srcs, w)
    assert np.asarray(d).tobytes() == dist.tobytes()


# --------------------------------------------------------------------------
# the shared CSR chunk walk: BFS hops, sparse hops and distances as before
# --------------------------------------------------------------------------
@pytest.mark.parametrize("lanes, block, want", [
    # (hops, sparse hops, distance sums of the first and last lane), as the
    # sweep read before SSSP came to share its chunk walk
    ([5], 64, (7, 5, 8560, 8560)),
    ([5], 512, (7, 5, 8560, 8560)),
    ([5, -1, 77], 64, (7, 4, 8560, 10276)),
    ([5, -1, 77], 512, (7, 4, 8560, 10276)),
])
def test_bfs_hops_unchanged_by_the_shared_chunk_walk(lanes, block, want):
    rng = np.random.default_rng(2024)
    n, e = 3000, 9000
    p = 1.0 / np.arange(1, n + 1) ** 0.8
    p /= p.sum()
    src = rng.choice(n, e, p=p).astype(np.int32)
    dst = rng.integers(0, n, e).astype(np.int32)
    view, _ = make_view(n, src, dst, directed=False)
    d, hops, sparse = _bfs_hops(view, jnp.asarray(lanes, jnp.int32),
                                max_hops=24, block_size=block)
    d = np.asarray(d).astype(np.int64)
    assert (int(hops), int(sparse), int(d[0].sum()), int(d[-1].sum())) == want
