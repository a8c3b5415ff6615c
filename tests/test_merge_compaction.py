"""Merge-based incremental compaction: property suite + overflow regressions.

The contract under test (core/graphview.py, merge_compact_view): folding the
delta buffer and tombstones into main by MERGING — sort only the delta, keep
main's order, drop dead slots in one pass — lands on exactly the arrays a
full ``build_graph_view`` rebuild would produce, field for field, bit for
bit. The scenarios are driven through ``GRFusion`` so the delta buffers fill
through the real insert path (id lookups, undirected mirrors, tombstones via
``delete_where``), then both compaction paths run on the same catalog state.

Also here: the delta-buffer overflow regressions. ``insert_delta`` must
REPORT how many valid entries it dropped (the silent-overflow bug), and the
engine path must never lose an edge — an oversized batch triggers a
compaction instead.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np

from _prop import given, settings, st
from repro.core.engine import GRFusion
from repro.core.graphview import build_graph_view, merge_compact_view
from repro.core.query import col
from repro.core.table import Table


# ---------------------------------------------------------------- scenario
def _build_engine(seed: int, directed: bool):
    """A live engine with tombstones + a part-filled delta buffer."""
    rng = np.random.default_rng((0x9E3779B9, seed, int(directed)))
    n = int(rng.integers(6, 28))
    e0 = int(rng.integers(0, 40))
    eng = GRFusion(compact_threshold=1.1)  # no auto-compaction: keep deltas
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {
            "src": rng.integers(0, n, e0).astype(np.int32),
            "dst": rng.integers(0, n, e0).astype(np.int32),
            "w": rng.uniform(0.1, 5.0, e0).astype(np.float32),
            "tag": np.zeros(e0, np.int32),
        },
        capacity=256,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        directed=directed, delta_capacity=64,
    )
    # interleave tombstones and delta-path inserts
    for step in range(int(rng.integers(1, 5))):
        if e0 and rng.random() < 0.6:
            thr = float(rng.uniform(0.1, 5.0))
            eng.delete_where("E", col("w") < thr)
        k = int(rng.integers(1, 7))
        eng.insert(
            "E",
            {
                "src": rng.integers(0, n, k).astype(np.int32),
                "dst": rng.integers(0, n, k).astype(np.int32),
                "w": rng.uniform(0.1, 5.0, k).astype(np.float32),
                "tag": np.full(k, step + 1, np.int32),
            },
        )
    return eng


def _assert_views_equal(a, b):
    """Every field of two GraphViews equal — arrays bit-for-bit."""
    for f in dataclasses.fields(a):
        va, vb = getattr(a, f.name), getattr(b, f.name)
        if f.name == "id_index":
            for sub in ("sorted_ids", "order"):
                xa = np.asarray(getattr(va, sub))
                xb = np.asarray(getattr(vb, sub))
                assert xa.dtype == xb.dtype and xa.shape == xb.shape, sub
                assert xa.tobytes() == xb.tobytes(), sub
            continue
        if isinstance(va, (jnp.ndarray, np.ndarray)):
            xa, xb = np.asarray(va), np.asarray(vb)
            assert xa.dtype == xb.dtype and xa.shape == xb.shape, f.name
            assert xa.tobytes() == xb.tobytes(), f.name
        else:
            assert va == vb, f.name


# -------------------------------------------------------------- properties
@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10_000), st.booleans())
def test_merge_equals_rebuild_bit_for_bit(seed, directed):
    eng = _build_engine(seed, directed)
    vb = eng.views["G"]
    vt, et = eng.tables["V"], eng.tables["E"]
    merged = merge_compact_view(
        vb.view, vt, et, v_id="vid", e_src="src", e_dst="dst",
        directed=directed,
    )
    rebuilt = build_graph_view(
        "G", vt, et, v_id="vid", e_src="src", e_dst="dst",
        directed=directed, delta_capacity=vb.delta_capacity,
    )
    _assert_views_equal(merged, rebuilt)


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.booleans())
def test_delta_empty_after_compact(seed, directed):
    eng = _build_engine(seed, directed)
    assert eng.events["compactions_merge"] == 0
    eng.compact("G")
    view = eng.views["G"].view
    assert not bool(jnp.any(view.delta_valid))
    assert int(np.asarray(view.delta_eid).max(initial=-1)) == -1
    assert eng.events["compactions_merge"] == 1


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10_000), st.booleans())
def test_edge_stream_invariant_across_compact(seed, directed):
    eng = _build_engine(seed, directed)
    valid = eng.tables["E"].valid
    before = eng.views["G"].view.edge_stream(row_valid=valid)
    eng.compact("G")
    after = eng.views["G"].view.edge_stream(row_valid=eng.tables["E"].valid)
    for xa, xb, name in zip(before, after, ("src", "dst", "eid")):
        assert xa.shape == xb.shape, name
        assert (xa == xb).all(), name


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_merge_then_full_rebuild_stable(seed):
    """Compacting an already-merged view is the identity (fixed point)."""
    eng = _build_engine(seed, True)
    eng.compact("G")
    v1 = eng.views["G"].view
    eng.compact("G", full=True)
    _assert_views_equal(v1, eng.views["G"].view)


# ------------------------------------------------- overflow regressions
def test_insert_delta_reports_dropped():
    """Regression: filling past delta capacity must REPORT the drop count,
    never silently discard edges (the standalone, engine-free path)."""
    n = 8
    vt = Table.create("V", {"vid": np.arange(n, dtype=np.int32)})
    et = Table.create(
        "E",
        {"src": np.zeros(1, np.int32), "dst": np.ones(1, np.int32)},
        capacity=32,
    )
    view = build_graph_view(
        "G", vt, et, v_id="vid", e_src="src", e_dst="dst", delta_capacity=4,
    )
    k = 7  # three more valid entries than the buffer holds
    sp = np.arange(k, dtype=np.int32) % n
    view2, dropped = view.insert_delta(
        jnp.asarray(sp), jnp.asarray((sp + 1) % n),
        jnp.arange(k, dtype=jnp.int32), jnp.ones(k, bool),
    )
    assert int(dropped) == 3
    assert bool(jnp.all(view2.delta_valid))
    # the invalid entries of a mixed batch consume placement slots too
    view3, dropped2 = view.insert_delta(
        jnp.asarray(sp), jnp.asarray((sp + 1) % n),
        jnp.arange(k, dtype=jnp.int32),
        jnp.asarray([True, False, False, False, True, True, True]),
    )
    assert int(dropped2) == 3  # entries 4..6 land past the 4 free slots
    assert int(jnp.sum(view3.delta_valid.astype(jnp.int32))) == 1


def test_engine_overflow_compacts_instead_of_dropping():
    """Engine path: a batch larger than the remaining delta capacity folds
    buffer + batch into main via one merge — no edge lost, counted."""
    n = 16
    eng = GRFusion(compact_threshold=1.1)
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {"src": np.zeros(1, np.int32), "dst": np.ones(1, np.int32),
         "w": np.ones(1, np.float32)},
        capacity=128,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        delta_capacity=8,
    )
    rng = np.random.default_rng(7)
    inserted = 1
    for k in (5, 6, 4):  # 5 fits; 6 overflows (3 free) -> compact; 4 fits
        eng.insert(
            "E",
            {"src": rng.integers(0, n, k).astype(np.int32),
             "dst": rng.integers(0, n, k).astype(np.int32),
             "w": np.ones(k, np.float32)},
        )
        inserted += k
    assert eng.events["delta_overflow_compactions"] == 1
    assert eng.events["compactions_merge"] == 1
    view = eng.views["G"].view
    src, dst, eid = view.edge_stream(row_valid=eng.tables["E"].valid)
    assert len(eid) == inserted  # nothing dropped anywhere
    assert len(set(eid.tolist())) == inserted


def test_threshold_schedules_compaction():
    """Fill past compact_threshold * capacity -> one scheduled merge."""
    n = 8
    eng = GRFusion(compact_threshold=0.5)
    eng.create_table("V", {"vid": np.arange(n, dtype=np.int32)})
    eng.create_table(
        "E",
        {"src": np.zeros(1, np.int32), "dst": np.ones(1, np.int32),
         "w": np.ones(1, np.float32)},
        capacity=64,
    )
    eng.create_graph_view(
        "G", vertexes="V", edges="E", v_id="vid", e_src="src", e_dst="dst",
        delta_capacity=8,
    )
    eng.insert("E", {"src": np.array([1, 2], np.int32),
                     "dst": np.array([2, 3], np.int32),
                     "w": np.ones(2, np.float32)})
    assert eng.events["threshold_compactions"] == 0  # 2 < 0.5 * 8
    eng.insert("E", {"src": np.array([3, 4], np.int32),
                     "dst": np.array([4, 5], np.int32),
                     "w": np.ones(2, np.float32)})
    assert eng.events["threshold_compactions"] == 1  # 4 >= 0.5 * 8
    assert not bool(jnp.any(eng.views["G"].view.delta_valid))
