"""Property-style seeded tests for the Pallas frontier packer: every edge
round-trips through ``pack_edges_by_dst`` exactly once — no drops, no dupes —
including duplicate edges, empty graphs, and V % block_rows != 0."""
from collections import Counter

import numpy as np
from _prop import given, settings, st

from repro.kernels.frontier.ops import pack_edges_by_dst
from repro.kernels.segment.ops import pack_segments


def _roundtrip(src, dst, V, *, block_rows, block_edges):
    ps, pe, ldst = pack_edges_by_dst(
        src, dst, V, block_rows=block_rows, block_edges=block_edges
    )
    T, J, BE = ps.shape
    assert ps.shape == pe.shape == ldst.shape
    assert T == -(-V // block_rows) or (V == 0 and T == 0)
    live = pe >= 0
    # consistency: padding is -1 in every array at the same slots
    assert ((ps >= 0) == live).all()
    assert ((ldst >= 0) == live).all()
    seen = Counter(pe[live].tolist())
    # exactly-once: every in-range edge appears exactly once, never twice
    expect = Counter(i for i in range(len(src)) if 0 <= dst[i] < V)
    assert seen == expect, (seen - expect, expect - seen)
    # each packed slot reproduces its edge (src and tiled dst)
    tiles = np.arange(T)[:, None, None] * block_rows + ldst
    assert (ps[live] == src[pe[live]]).all()
    assert (tiles[live] == dst[pe[live]]).all()
    # local dsts stay inside the tile
    assert ldst[live].max(initial=0) < block_rows


@settings(max_examples=25, deadline=None)
@given(
    st.integers(1, 300),  # V
    st.integers(0, 800),  # E
    st.integers(0, 2**31 - 1),  # seed
)
def test_pack_roundtrips_every_edge_exactly_once(V, E, seed):
    rng = np.random.default_rng(seed)
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    _roundtrip(src, dst, V, block_rows=32, block_edges=16)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1))
def test_pack_with_duplicate_edges(seed):
    rng = np.random.default_rng(seed)
    V, E = 40, 60
    src = rng.integers(0, V, E).astype(np.int32)
    dst = rng.integers(0, V, E).astype(np.int32)
    dup = rng.integers(0, E, 30)
    src = np.concatenate([src, src[dup]])
    dst = np.concatenate([dst, dst[dup]])
    _roundtrip(src, dst, V, block_rows=16, block_edges=8)


def test_pack_empty_graph():
    src = np.zeros((0,), np.int32)
    dst = np.zeros((0,), np.int32)
    ps, pe, ldst = pack_edges_by_dst(src, dst, 17, block_rows=8, block_edges=4)
    assert (pe < 0).all() and (ps < 0).all() and (ldst < 0).all()


def test_pack_v_not_multiple_of_block_rows():
    # V=13 with block_rows=8 => 2 row tiles, last one ragged
    V = 13
    src = np.arange(V, dtype=np.int32)
    dst = np.roll(np.arange(V, dtype=np.int32), -1)
    _roundtrip(src, dst, V, block_rows=8, block_edges=4)


def test_pack_drops_out_of_range_dsts_only():
    V = 8
    src = np.array([0, 1, 2, 3], np.int32)
    dst = np.array([1, 8, 7, -1], np.int32)  # 8 and -1 out of range
    ps, pe, ldst = pack_edges_by_dst(src, dst, V, block_rows=4, block_edges=4)
    live = pe[pe >= 0]
    assert sorted(live.tolist()) == [0, 2]


def _pack_fill_loop(seg_ids, num_segments, block_rows, block_edges):
    """The per-edge sequential fill ``pack_segments`` replaced."""
    T = -(-num_segments // block_rows)
    keep = (seg_ids >= 0) & (seg_ids < num_segments)
    tile_of = np.where(keep, seg_ids // block_rows, -1)
    counts = np.bincount(tile_of[tile_of >= 0], minlength=T)
    J = max(1, int(-(-counts.max() // block_edges))) if counts.size else 1
    gather = np.full((T, J * block_edges), -1, np.int32)
    ldst = np.full((T, J * block_edges), -1, np.int32)
    fill = np.zeros(T, np.int64)
    for e in np.arange(seg_ids.shape[0])[keep]:
        t = tile_of[e]
        gather[t, fill[t]] = e
        ldst[t, fill[t]] = seg_ids[e] - t * block_rows
        fill[t] += 1
    return gather.reshape(T, J, block_edges), ldst.reshape(T, J, block_edges)


@settings(max_examples=40, deadline=None)
@given(
    st.integers(1, 200),  # num_segments
    st.integers(0, 600),  # edges
    st.integers(0, 2**31 - 1),  # seed
)
def test_pack_fill_matches_sequential_loop(V, E, seed):
    rng = np.random.default_rng(seed)
    # sorted ids with -1 drops in front and out-of-range ids at the back
    ids = np.sort(rng.integers(-1, V + 2, E)).astype(np.int32)
    br, be = int(rng.choice([4, 8, 16])), int(rng.choice([2, 4, 8]))
    gather, ldst, T, J = pack_segments(ids, V, block_rows=br, block_edges=be)
    g_ref, l_ref = _pack_fill_loop(ids, V, br, be)
    assert gather.dtype == g_ref.dtype and ldst.dtype == l_ref.dtype
    assert np.array_equal(gather, g_ref) and np.array_equal(ldst, l_ref)
    assert (T, J) == g_ref.shape[:2]
