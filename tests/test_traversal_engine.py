"""TraversalEngine unit tests: backend policy, per-query knob, serving path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import GRFusion
from repro.core.graphview import build_graph_view
from repro.core.query import Query, P, col
from repro.core.table import Table
from repro.core.traversal_engine import TraversalEngine
from repro.serve.engine import QueryServer


def _chain_view(n=12):
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    et = Table.create("E", {
        "src": np.arange(n - 1, dtype=np.int32),
        "dst": np.arange(1, n, dtype=np.int32),
        "w": np.ones(n - 1, np.float32),
    })
    return build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")


def test_auto_policy_defaults_to_xla_on_cpu():
    view = _chain_view()
    te = TraversalEngine()
    assert te.resolve_backend(view, n_sources=64) == "xla_coo"


def test_auto_policy_is_device_count_aware():
    view = _chain_view()
    # multi-device mesh + stream past the threshold -> sharded
    te = TraversalEngine(n_devices=2, shard_min_slots=1)
    assert te.device_count() == 2
    assert te.resolve_backend(view) == "sharded"
    # same mesh, stream below the threshold -> single-device policy
    te = TraversalEngine(n_devices=2, shard_min_slots=1 << 30)
    assert te.resolve_backend(view) == "xla_coo"
    # single device never shards, no matter how large the stream
    te = TraversalEngine(n_devices=1, shard_min_slots=1)
    assert te.resolve_backend(view) == "xla_coo"
    # explicit request beats the size policy in both directions
    te = TraversalEngine(n_devices=2, shard_min_slots=1)
    assert te.resolve_backend(view, requested="reference") == "reference"


def test_env_override_reaches_sharded(monkeypatch):
    view = _chain_view()
    te = TraversalEngine()
    monkeypatch.setenv("REPRO_TRAVERSAL_BACKEND", "sharded")
    assert te.resolve_backend(view) == "sharded"


def test_shard_pack_cache_and_epoch_invalidation():
    view = _chain_view()
    te = TraversalEngine()
    p1 = te.get_shard_pack(view, n_shards=2)
    assert te.stats["shard_pack_builds"] == 1
    p2 = te.get_shard_pack(view, n_shards=2)
    assert p2 is p1
    assert te.stats["shard_pack_hits"] == 1
    # a different mesh width is a different pack
    te.get_shard_pack(view, n_shards=4)
    assert te.stats["shard_pack_builds"] == 2
    # epoch bump invalidates shard packs alongside dst-sort packs
    te.register_view("G")
    te.get_shard_pack(view, graph="G", n_shards=2)
    assert te.stats["shard_pack_builds"] == 3
    te.bump_epoch("G")
    te.get_shard_pack(view, graph="G", n_shards=2)
    assert te.stats["shard_pack_builds"] == 4


def test_shard_partition_covers_stream_exactly():
    from repro.kernels.frontier.shard import partition_edges_by_dst_block

    rng = np.random.default_rng(5)
    V, E, n = 300, 900, 4
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    eid = np.arange(E, dtype=np.int32)
    eid[::7] = -1  # tombstoned rows must be dropped
    ssrc, sdst, seid = partition_edges_by_dst_block(src, dst, eid, V, n)
    assert ssrc.shape == sdst.shape == seid.shape
    assert ssrc.shape[0] == n
    live = seid >= 0
    # every live edge appears exactly once, under its original endpoints
    got = sorted(zip(seid[live], ssrc[live], sdst[live]))
    want = sorted(zip(eid[eid >= 0], src[eid >= 0], dst[eid >= 0]))
    assert got == want
    # shard dst ranges are disjoint contiguous blocks, sorted within
    lo = -1
    for s in range(n):
        d = sdst[s][live[s]]
        assert np.all(np.diff(d) >= 0)
        if d.size:
            assert d.min() > lo or s == 0
            lo = d.max()
    # pad slots are inert: endpoints out of range, eid -1
    assert np.all(ssrc[~live] == V) and np.all(sdst[~live] == V)


def test_env_override_and_validation(monkeypatch):
    view = _chain_view()
    te = TraversalEngine()
    monkeypatch.setenv("REPRO_TRAVERSAL_BACKEND", "reference")
    assert te.resolve_backend(view) == "reference"
    # explicit request beats the env override
    assert te.resolve_backend(view, requested="xla_coo") == "xla_coo"
    monkeypatch.setenv("REPRO_TRAVERSAL_BACKEND", "nonsense")
    with pytest.raises(ValueError):
        te.resolve_backend(view)
    with pytest.raises(ValueError):
        TraversalEngine(default_backend="bogus")


@pytest.fixture
def social():
    eng = GRFusion()
    eng.create_table("Users", {
        "uId": np.array([1, 2, 3, 4, 5]),
        "fName": np.array(["Edy", "Jones", "Bill", "Ann", "Cara"]),
    }, capacity=8)
    eng.create_table("Relationships", {
        "uId1": np.array([1, 2, 3, 4]),
        "uId2": np.array([3, 3, 4, 5]),
        "w": np.array([1.0, 1.0, 2.0, 0.5], np.float32),
    }, capacity=16)
    eng.create_graph_view(
        "SocialNetwork", vertexes="Users", edges="Relationships",
        v_id="uId", e_src="uId1", e_dst="uId2", directed=False,
    )
    return eng


def _reach_query(backend=None):
    q = (Query().from_table("Users", "A").from_table("Users", "B")
         .from_paths("SocialNetwork", "PS")
         .where((col("A.fName") == "Edy") & (col("B.fName") == "Cara")
                & (P("PS").start.id == col("A.uId"))
                & (P("PS").end.id == col("B.uId")))
         .select(exists=col("PS.exists"), length=col("PS.length"))
         .limit(1))
    if backend:
        q = q.traversal_backend(backend)
    return q


@pytest.mark.parametrize(
    "backend", ["xla_coo", "pallas_frontier", "reference", "sharded"])
def test_engine_reachability_same_answer_on_every_backend(social, backend):
    base = social.run(_reach_query())
    r = social.run(_reach_query(backend))
    assert any(f"traversal backend: {backend}" in e for e in r.explain)
    assert bool(r.columns["exists"][0]) == bool(base.columns["exists"][0])
    assert int(r.columns["length"][0]) == int(base.columns["length"][0])
    assert social.traversal.stats[f"backend_{backend}"] >= 1


@pytest.mark.parametrize(
    "backend", ["xla_coo", "pallas_frontier", "reference", "sharded"])
def test_engine_sssp_same_answer_on_every_backend(social, backend):
    q = (Query().from_table("Users", "A").from_table("Users", "B")
         .from_paths("SocialNetwork", "PS")
         .where((col("A.fName") == "Edy") & (col("B.fName") == "Cara")
                & (P("PS").start.id == col("A.uId"))
                & (P("PS").end.id == col("B.uId")))
         .hint_shortest_path("w")
         .select(distance=col("PS.distance"))
         .traversal_backend(backend))
    r = social.run(q)
    assert r.count == 1
    assert float(r.columns["distance"][0]) == pytest.approx(3.5)


def test_query_server_batches_through_traversal_engine(social):
    srv = QueryServer(social, "SocialNetwork", lane_width=8, max_hops=8)
    srv.submit(1, 5)
    srv.submit(5, 1)
    srv.submit(1, 999)  # unknown id => unreachable, not an error
    out = srv.flush()
    assert [o["reachable"] for o in out] == [True, True, False]
    assert out[0]["hops"] == 3
    assert social.traversal.stats["batches_flushed"] == 1
    assert social.traversal.stats["queries_bfs"] == 1  # merged into one sweep


def test_two_query_servers_do_not_cross_flush(social):
    # each server flushes only its own handles; if srv1's flush drained
    # srv2's queue it would answer with srv1's hop budget (8) and the
    # second assertion would see reachable=True
    srv1 = QueryServer(social, "SocialNetwork", lane_width=8, max_hops=8)
    srv2 = QueryServer(social, "SocialNetwork", lane_width=8, max_hops=1)
    srv1.submit(1, 5)
    srv2.submit(1, 5)
    assert srv1.flush()[0]["reachable"]
    assert not srv2.flush()[0]["reachable"]  # 1 hop is not enough


def test_flush_chunks_wide_batches():
    view = _chain_view(16)
    te = TraversalEngine(lane_width=4, max_lanes=4)
    handles = [te.submit_reachability(view, 0, i % 16) for i in range(10)]
    te.flush(max_hops=20)
    before = te.stats["queries_bfs"]
    assert before == 3  # ceil(10 / max_lanes) sweeps, each at most 4 lanes
    for i, h in enumerate(handles):
        assert h.result["reachable"] and h.result["hops"] == i % 16


def test_submit_sssp_merges_shared_weight_array():
    view = _chain_view(10)
    w = jnp.full((9,), 1.0, jnp.float32)
    te = TraversalEngine(lane_width=4)
    hs = [te.submit_sssp(view, 0, t, w) for t in (3, 5, 7)]
    te.flush()
    assert te.stats["queries_sssp"] == 1  # same weights object => one sweep
    assert [h.result["distance"] for h in hs] == [3.0, 5.0, 7.0]


def test_submit_sssp_admission():
    view = _chain_view(10)
    w = jnp.full((9,), 2.0, jnp.float32)
    te = TraversalEngine(lane_width=4)
    h1 = te.submit_sssp(view, 0, 9, w)
    h2 = te.submit_sssp(view, 9, 0, w)
    te.flush()
    assert h1.result["reachable"] and h1.result["distance"] == pytest.approx(18.0)
    assert not h2.result["reachable"]


def test_sparse_hop_counter_sums_on_device_and_reads_with_stats():
    view = _chain_view()  # 11 edges: every hop fires at most one, so sparse
    te = TraversalEngine(default_backend="xla_coo")
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            te.bfs(view, jnp.asarray([0], jnp.int32), max_hops=2)
    assert isinstance(te._sparse_hops, jax.Array) and not te.events
    stats = te.stats
    assert stats["hops_xla_coo_sparse"] == stats["hops_xla_coo"] == 6


def test_sssp_counters_sum_on_device_and_read_with_stats():
    view = _chain_view()  # 12 vertices, 11 edges: every round sparse
    w = jnp.full((11,), 1.0, jnp.float32)
    te = TraversalEngine(default_backend="xla_coo")
    srcs = jnp.asarray([0, -1], jnp.int32)  # lane 1 inactive: reaches nothing
    with jax.transfer_guard_device_to_host("disallow"):
        for _ in range(3):
            te.sssp(view, srcs, w)
    for counter in (te._sssp_rounds, te._sssp_sparse_rounds, te._sssp_reached):
        assert isinstance(counter, jax.Array)
    assert not te.events
    stats = te.stats
    assert stats["sssp_rounds_xla_coo"] == stats["sssp_rounds_xla_coo_sparse"] == 3 * 12
    assert stats["sssp_reached_vertices"] == 3 * 12
    assert stats["sssp_reached_slots"] == 3 * 11  # the last vertex has no out-edge


def test_tally_sums_past_int32():
    te = TraversalEngine(default_backend="xla_coo")
    from repro.core.traversal_engine import _tally

    big = jnp.asarray([[2 ** 31 - 1, 5], [7, 2 ** 30]], jnp.int32)
    for _ in range(4):
        te._sssp_reached = _tally(te._sssp_reached, big)
    assert te.stats["sssp_reached_vertices"] == 4 * (2 ** 31 - 1 + 5)
    assert te.stats["sssp_reached_slots"] == 4 * (7 + 2 ** 30)


def _weighted_chain_view(n):
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    et = Table.create("E", {
        "src": np.arange(n - 1, dtype=np.int32),
        "dst": np.arange(1, n, dtype=np.int32),
        "w": np.full(n - 1, 0.25, np.float32),
    })
    return build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst"), et


@pytest.mark.parametrize("backend", ["xla_coo", "pallas_frontier", "reference", "sharded"])
def test_sssp_chain_past_64_rounds_converges_on_every_backend(backend):
    view, et = _weighted_chain_view(90)
    w = et.col("w")
    srcs = jnp.asarray([0, 30], jnp.int32)
    te = TraversalEngine()
    dist, _ = te.sssp(view, srcs, w, backend=backend)
    want = np.float32(0.25) * np.arange(90, dtype=np.float32)
    assert np.asarray(dist)[0].tobytes() == want.tobytes()
    assert te.stats[f"backend_{backend}"] == 1


def _sssp_engine(n, src, dst, w, capacity=None):
    eng = GRFusion()
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table("E", {"src": np.asarray(src, np.int32),
                           "dst": np.asarray(dst, np.int32),
                           "w": np.asarray(w, np.float32)}, capacity=capacity)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    PS = P("PS")
    q = (Query().from_paths("G", "PS")
         .where((PS.start.id == 0) & (PS.end.id == n - 1))
         .hint_shortest_path("w").hint_max_length(8)
         .select(distance=col("PS.distance")))
    return eng, q


def test_served_sssp_past_64_rounds_converges_with_one_host_sync():
    n = 80
    eng, q = _sssp_engine(n, np.arange(n - 1), np.arange(1, n), np.full(n - 1, 0.5))
    loop = eng.serving_loop()
    loop.submit(q)
    loop.drain()  # warm
    before = dict(eng.events)
    t = loop.submit(q)
    loop.drain()
    assert t.status == "done"
    assert float(t.result.columns["distance"][0]) == 39.5
    grew = {k: v - before.get(k, 0) for k, v in eng.events.items()
            if v != before.get(k, 0) and k.startswith("host_sync.")}
    assert grew == {"host_sync.finalize": 1}


@pytest.mark.parametrize("enters", ["create", "insert", "update"])
def test_shortest_path_refuses_negative_weights(enters):
    # a negative weight on an edge of a cycle (1 <-> 2) would lower
    # distances every round: refused where it enters the edge table
    w = [1.0, -1.0, -1.0, 1.0] if enters == "create" else [1.0, 1.0, 1.0, 1.0]
    eng, q = _sssp_engine(4, [0, 1, 2, 2], [1, 2, 1, 3], w, capacity=8)
    if enters != "create":
        assert float(eng.run(q).columns["distance"][0]) == 3.0
    if enters == "insert":
        eng.insert("E", {"src": np.asarray([3], np.int32), "dst": np.asarray([0], np.int32),
                         "w": np.asarray([-0.5], np.float32)})
    elif enters == "update":
        eng.update_where("E", col("src") == 1, "w", -1.0)
    with pytest.raises(ValueError, match="non-negative"):
        eng.run(q)
