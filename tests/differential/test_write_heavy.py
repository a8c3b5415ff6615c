"""Write-heavy differential harness: mutating interleavings, four backends.

The static differential suite (test_backends.py) freezes one topology and
sweeps backends over it. This file is its write-heavy sibling: seeded
interleavings of INSERT / TOMBSTONE / QUERY / COMPACT against a live
``GRFusion`` catalog, where every QUERY step asserts

  * BFS and SSSP distances bit-identical across ``xla_coo``,
    ``pallas_frontier``, ``sharded``, and ``reference`` — deltas,
    tombstones and all;
  * the view's live edge multiset equals an independent numpy oracle that
    replays the mutation log (so a lost / resurrected / duplicated edge is
    caught even if every backend shares the bug);
  * oracle BFS distances match (int hop counts are exact, so this is an
    equality, not a tolerance).

Warm-path acceptance rides along: between compactions the packing caches
must serve every query — total pack builds is bounded by compactions + 1,
i.e. delta-only inserts cause ZERO re-packs.

Runs in the differential marker set, so the sharded CI stage re-runs it at
forced host device counts 1, 2 and 4.
"""
import zlib

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import GRFusion
from repro.core.query import col
from repro.core.traversal_engine import BACKENDS

pytestmark = pytest.mark.differential

_MAX_HOPS = 40


# ------------------------------------------------------------------ oracle
class LogOracle:
    """Replays the mutation log into a plain python edge list."""

    def __init__(self, n, directed):
        self.n = n
        self.directed = directed
        self.edges = []  # dicts: src, dst, w, tag, alive

    def insert(self, src, dst, w, tag):
        for s, d, ww in zip(src, dst, w):
            self.edges.append(
                {"src": int(s), "dst": int(d), "w": float(ww),
                 "tag": int(tag), "alive": True}
            )

    def tombstone_tag(self, tag):
        for e in self.edges:
            if e["tag"] == int(tag):
                e["alive"] = False

    def live_triples(self):
        """Sorted (src, dst) pairs of live edges, mirrored if undirected."""
        out = []
        for e in self.edges:
            if not e["alive"]:
                continue
            out.append((e["src"], e["dst"]))
            if not self.directed:
                out.append((e["dst"], e["src"]))
        return sorted(out)

    def bfs(self, sources, max_hops):
        adj = [[] for _ in range(self.n)]
        for s, d in self.live_triples():
            adj[s].append(d)
        dists = np.full((len(sources), self.n), -1, np.int32)
        for i, s0 in enumerate(sources):
            dists[i, s0] = 0
            frontier = [int(s0)]
            hop = 0
            while frontier and hop < max_hops:
                nxt = []
                for u in frontier:
                    for v in adj[u]:
                        if dists[i, v] < 0:
                            dists[i, v] = hop + 1
                            nxt.append(v)
                frontier = nxt
                hop += 1
        return dists


# ---------------------------------------------------------------- scenario
def _run_scenario(seed, directed, steps=14):
    rng = np.random.default_rng((zlib.crc32(b"write_heavy"), seed,
                                 int(directed)))
    n = 20
    eng = GRFusion(compact_threshold=0.75)
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    # seed edges land in main via the initial build
    e0 = 24
    src0 = rng.integers(0, n, e0).astype(np.int32)
    dst0 = rng.integers(0, n, e0).astype(np.int32)
    w0 = rng.uniform(0.1, 5.0, e0).astype(np.float32)
    eng.create_table(
        "E", {"src": src0, "dst": dst0, "w": w0,
              "tag": np.zeros(e0, np.int32)},
        capacity=512,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        directed=directed, delta_capacity=32,
    )
    oracle = LogOracle(n, directed)
    oracle.insert(src0, dst0, w0, 0)

    te = eng.traversal
    next_tag = 1
    live_tags = [0]
    queries = 0
    for step in range(steps):
        op = rng.choice(["insert", "insert", "tombstone", "query", "query",
                         "compact"])
        if op == "insert":
            k = int(rng.integers(1, 8))
            s = rng.integers(0, n, k).astype(np.int32)
            d = rng.integers(0, n, k).astype(np.int32)
            w = rng.uniform(0.1, 5.0, k).astype(np.float32)
            eng.insert("E", {"src": s, "dst": d, "w": w,
                             "tag": np.full(k, next_tag, np.int32)})
            oracle.insert(s, d, w, next_tag)
            live_tags.append(next_tag)
            next_tag += 1
        elif op == "tombstone" and live_tags:
            tag = int(rng.choice(live_tags))
            live_tags.remove(tag)
            eng.delete_where("E", col("tag") == tag)
            oracle.tombstone_tag(tag)
        elif op == "compact":
            eng.compact("G", full=bool(rng.random() < 0.25))
        else:
            queries += _check_query(eng, te, oracle, rng, directed)
    # every scenario must actually have exercised the cross-backend check
    if queries == 0:
        queries += _check_query(eng, te, oracle, rng, directed)
    # warm-path acceptance: packs rebuild at most once per compaction —
    # delta-only inserts and tombstones between compactions re-pack NOTHING
    compactions = (
        eng.events["compactions_merge"] + eng.events["compactions_full"]
    )
    for key in ("pack_builds", "shard_pack_builds"):
        assert te.stats[key] <= compactions + 1, (
            key, te.stats[key], compactions,
        )
    assert queries >= 1


def _check_query(eng, te, oracle, rng, directed):
    view = eng.views["G"].view
    et = eng.tables["E"]
    valid = et.valid
    # 1) edge multiset vs the oracle's replay of the mutation log
    src, dst, eid = view.edge_stream(row_valid=valid)
    assert sorted(zip(src.tolist(), dst.tolist())) == oracle.live_triples()
    # 2) BFS bit-identical across all four backends AND equal to oracle
    srcs = rng.integers(0, view.n_vertices, 6).astype(np.int32)
    dists = {
        b: np.asarray(
            te.bfs(view, jnp.asarray(srcs), edge_mask_by_row=valid,
                   max_hops=_MAX_HOPS, backend=b, graph="G")
        )
        for b in BACKENDS
    }
    ref = dists["reference"]
    assert (ref == oracle.bfs(srcs, _MAX_HOPS)).all()
    for b in BACKENDS:
        assert (dists[b] == ref).all(), (b, np.argwhere(dists[b] != ref)[:5])
    # 3) SSSP distances + canonical parents bit-identical across backends
    w = et.col("w")
    out = {
        b: te.sssp(view, jnp.asarray(srcs[:3]), w, edge_mask_by_row=valid,
                   backend=b, graph="G")
        for b in BACKENDS
    }
    dref, pref = (np.asarray(x) for x in out["reference"])
    for b in BACKENDS:
        d, p = (np.asarray(x) for x in out[b])
        assert d.tobytes() == dref.tobytes(), b
        assert (p == pref).all(), b
    return 1


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("directed", [True, False])
def test_write_heavy_interleaving(seed, directed):
    _run_scenario(seed, directed)


def test_warm_queries_between_compactions_zero_repacks():
    """The sharpest form of the warm-path acceptance: a burst of queries
    with delta inserts in between builds each pack exactly once, and the
    next compaction bumps each exactly once."""
    n = 16
    eng = GRFusion(compact_threshold=1.1)
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {"src": np.arange(n - 1, dtype=np.int32),
         "dst": np.arange(1, n, dtype=np.int32),
         "w": np.ones(n - 1, np.float32)},
        capacity=64,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        delta_capacity=16,
    )
    te = eng.traversal
    srcs = jnp.zeros((4,), jnp.int32)

    def sweep():
        view = eng.views["G"].view
        valid = eng.tables["E"].valid
        for b in ("pallas_frontier", "sharded"):
            te.bfs(view, srcs, edge_mask_by_row=valid, max_hops=24,
                   backend=b, graph="G")

    sweep()
    assert te.stats["pack_builds"] == 1
    assert te.stats["shard_pack_builds"] == 1
    for i in range(3):  # sustained writes, all delta-path
        eng.insert("E", {"src": np.array([0], np.int32),
                         "dst": np.array([(i * 5 + 3) % n], np.int32),
                         "w": np.array([1.0], np.float32)})
        sweep()
    assert eng.events["delta_inserts"] == 3
    assert te.stats["pack_builds"] == 1  # ZERO re-packs under writes
    assert te.stats["shard_pack_builds"] == 1
    eng.compact("G")
    sweep()
    assert te.stats["pack_builds"] == 2  # exactly one re-pack per compaction
    assert te.stats["shard_pack_builds"] == 2
