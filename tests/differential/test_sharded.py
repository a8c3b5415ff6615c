"""Sharded-backend differential suite at scale (``-m sharded``).

The small-graph families in ``test_backends.py`` already include the
``sharded`` backend in their cross-backend bit-identity sweep (delta
buffers, tombstones, undirected streams, …) at whatever device count the
process started with. This module adds what they cannot afford: synthetic
**>=1M-vertex** Erdos-Renyi and power-law graphs, checked bit-for-bit
against an XLA-independent numpy oracle *and* against ``xla_coo``.

The oracle avoids ``np.logical_or.at`` / per-edge loops (hopeless at 4M
edges) by dst-sorting once and reducing per-destination segments with
``np.maximum.reduceat`` / ``np.minimum.reduceat``; min over float32 is
exact in any order, so the oracle's Jacobi rounds are bit-identical to
both the sharded ring combine and the single-device sweep by the same
argument the backends rely on.

``scripts/ci.sh sharded`` runs this module under
``XLA_FLAGS=--xla_force_host_platform_device_count={1,2,4}``; the tests
shard as wide as the visible device count allows, so a plain run still
covers the single-shard degenerate path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graphview import build_graph_view
from repro.core.table import Table
from repro.core.traversal_engine import TraversalEngine

pytestmark = pytest.mark.sharded

V_BIG = 1 << 20
E_BIG = 4 * V_BIG
S = 4  # query lanes; [S, V] f32 state stays ~16 MB at V=1M


def _n_shards():
    return min(jax.device_count(), 4)


def _er_edges(rng, v, e):
    return (rng.integers(0, v, e).astype(np.int32),
            rng.integers(0, v, e).astype(np.int32))


def _powerlaw_edges(rng, v, e):
    """Skewed dst degrees (hub-heavy): the worst case for edge-cut balance
    — hubs concentrate one shard's stream — exercising the padded-shard
    shapes and the ring combine under imbalance."""
    src = rng.integers(0, v, e).astype(np.int32)
    dst = np.minimum((v * rng.random(e) ** 4), v - 1).astype(np.int32)
    return src, dst


def _view(src, dst, v, w):
    vt = Table.create("V", {"vid": np.arange(v, dtype=np.int32)})
    et = Table.create("E", {"src": src, "dst": dst, "w": w})
    return build_graph_view("G", vt, et, v_id="vid", e_src="src", e_dst="dst")


# --------------------------------------------------------------- fast oracle
def _sorted_stream(src, dst, v):
    order = np.argsort(dst, kind="stable")
    sdst = dst[order]
    # segment starts per unique destination for reduceat
    starts = np.flatnonzero(np.r_[True, sdst[1:] != sdst[:-1]])
    return src[order], sdst, order, starts, sdst[starts]


def _oracle_bfs(src, dst, v, sources, max_hops):
    ssrc, sdst, _, starts, uniq = _sorted_stream(src, dst, v)
    s = sources.shape[0]
    frontier = np.zeros((s, v), bool)
    lanes = np.arange(s)
    frontier[lanes, sources] = True
    dist = np.where(frontier, 0, -1).astype(np.int32)
    visited = frontier.copy()
    hop = 0
    while hop < max_hops and frontier.any():
        msgs = frontier[:, ssrc].astype(np.uint8)  # [s, E] dst-sorted
        seg = np.maximum.reduceat(msgs, starts, axis=1)
        nxt = np.zeros((s, v), bool)
        nxt[:, uniq] = seg > 0
        nxt &= ~visited
        dist = np.where(nxt, hop + 1, dist).astype(np.int32)
        visited |= nxt
        frontier = nxt
        hop += 1
    return dist


def _oracle_sssp(src, dst, w, v, sources):
    ssrc, sdst, order, starts, uniq = _sorted_stream(src, dst, v)
    sw = w[order].astype(np.float32)
    s = sources.shape[0]
    dist = np.full((s, v), np.inf, np.float32)
    dist[np.arange(s), sources] = 0.0
    while True:  # Jacobi rounds until no distance falls
        cand = (dist[:, ssrc] + sw[None, :]).astype(np.float32)
        seg = np.minimum.reduceat(cand, starts, axis=1).astype(np.float32)
        new = dist.copy()
        new[:, uniq] = np.minimum(new[:, uniq], seg).astype(np.float32)
        if not (new < dist).any():
            break
        dist = new
    return dist


@pytest.fixture(scope="module")
def big_er():
    rng = np.random.default_rng(42)
    src, dst = _er_edges(rng, V_BIG, E_BIG)
    w = (rng.random(E_BIG).astype(np.float32) * 4 + 0.25)
    return src, dst, w, _view(src, dst, V_BIG, w)


@pytest.fixture(scope="module")
def big_powerlaw():
    rng = np.random.default_rng(43)
    src, dst = _powerlaw_edges(rng, V_BIG, E_BIG)
    w = (rng.random(E_BIG).astype(np.float32) * 4 + 0.25)
    return src, dst, w, _view(src, dst, V_BIG, w)


def _sources(seed):
    rng = np.random.default_rng(seed)
    return rng.integers(0, V_BIG, S).astype(np.int32)


@pytest.mark.parametrize("family", ["er", "powerlaw"])
def test_million_vertex_bfs_bit_identical(family, big_er, big_powerlaw):
    src, dst, _w, view = big_er if family == "er" else big_powerlaw
    te = TraversalEngine(n_devices=_n_shards())
    sp = _sources(7 if family == "er" else 8)
    max_hops = 12
    d_sh = np.asarray(
        te.bfs(view, jnp.asarray(sp), max_hops=max_hops, backend="sharded"))
    want = _oracle_bfs(src, dst, V_BIG, sp, max_hops)
    assert d_sh.tobytes() == want.tobytes()
    d_xla = np.asarray(
        te.bfs(view, jnp.asarray(sp), max_hops=max_hops, backend="xla_coo"))
    assert d_sh.tobytes() == d_xla.tobytes()


@pytest.mark.parametrize("family", ["er", "powerlaw"])
def test_million_vertex_sssp_bit_identical(family, big_er, big_powerlaw):
    src, dst, w, view = big_er if family == "er" else big_powerlaw
    te = TraversalEngine(n_devices=_n_shards())
    sp = _sources(9 if family == "er" else 10)
    d_sh, p_sh = te.sssp(
        view, jnp.asarray(sp), jnp.asarray(w), backend="sharded")
    want = _oracle_sssp(src, dst, w, V_BIG, sp)
    assert np.asarray(d_sh).tobytes() == want.tobytes()
    d_xla, p_xla = te.sssp(
        view, jnp.asarray(sp), jnp.asarray(w), backend="xla_coo")
    assert np.asarray(d_sh).tobytes() == np.asarray(d_xla).tobytes()
    # parents share the canonical pass; identical dists -> identical slots
    assert np.array_equal(np.asarray(p_sh), np.asarray(p_xla))


def test_warm_queries_zero_repacks(big_er):
    _src, _dst, _w, view = big_er
    te = TraversalEngine(n_devices=_n_shards())
    sp = jnp.asarray(_sources(11))
    te.bfs(view, sp, max_hops=4, backend="sharded")
    builds = te.stats["shard_pack_builds"]
    traces = te.stats["traces_bfs_sharded"]
    te.bfs(view, sp, max_hops=4, backend="sharded")
    assert te.stats["shard_pack_builds"] == builds  # zero re-packs
    assert te.stats["shard_pack_hits"] >= 1
    assert te.stats["traces_bfs_sharded"] == traces  # zero re-traces
