"""Incremental HyperLogLog catalog stats under streaming inserts.

``Table.compute_stats(prev=, appended=)`` folds an insert batch into the
previous epoch's sketches instead of rescanning every live row. Because HLL
registers merge by elementwise max and the appended values are coerced to
the column dtypes exactly as ``insert`` stores them, the incremental
registers must land bit-identical to a full rebuild's — asserted here, plus
the coarser estimate-accuracy bound the ISSUE asks for (within 5x the
sketch's relative standard error of the true distinct count). The engine
wiring (``GRFusion._update_stats_incremental``) is covered too: a pure
insert between two ``table_stats`` calls takes the incremental path and
counts an ``events["stats_incremental"]``.
"""
import os

import numpy as np
import pytest

from _prop import given, settings, st
from repro.core.engine import GRFusion
from repro.core.sketch import DEFAULT_P, HyperLogLog
from repro.core.table import Table

_RSE = 1.04 / np.sqrt(1 << DEFAULT_P)


@pytest.fixture
def sketch_mode():
    """Force the sketch path regardless of table size."""
    old = os.environ.get("REPRO_STATS_EXACT_MAX")
    os.environ["REPRO_STATS_EXACT_MAX"] = "1"
    yield
    if old is None:
        del os.environ["REPRO_STATS_EXACT_MAX"]
    else:
        os.environ["REPRO_STATS_EXACT_MAX"] = old


def _with_sketch_mode(fn):
    old = os.environ.get("REPRO_STATS_EXACT_MAX")
    os.environ["REPRO_STATS_EXACT_MAX"] = "1"
    try:
        return fn()
    finally:
        if old is None:
            del os.environ["REPRO_STATS_EXACT_MAX"]
        else:
            os.environ["REPRO_STATS_EXACT_MAX"] = old


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 64))
def test_incremental_registers_bit_identical_to_rebuild(seed, k):
    def body():
        rng = np.random.default_rng((0xA5, seed))
        n0 = int(rng.integers(8, 200))
        base = {
            "a": rng.integers(0, 50, n0).astype(np.int32),
            "b": rng.uniform(0, 1, n0).astype(np.float32),
        }
        t = Table.create("T", base, capacity=n0 + k)
        s0 = t.compute_stats()
        assert s0.sketches is not None and set(s0.sketches) == {"a", "b"}
        batch = {
            # int64/float64 on purpose: the incremental path must coerce to
            # the column dtypes before hashing, like insert stores them
            "a": rng.integers(0, 50, k),
            "b": rng.uniform(0, 1, k),
        }
        t2, slots, overflow = t.insert(batch)
        assert not bool(overflow)
        inc = t2.compute_stats(prev=s0, appended=batch)
        full = t2.compute_stats()
        assert inc.row_count == full.row_count == n0 + k
        for c in ("a", "b"):
            assert (
                inc.sketches[c].registers.tobytes()
                == full.sketches[c].registers.tobytes()
            ), c
            assert inc.distinct[c] == full.distinct[c], c
        # prev's sketches must be untouched (copy-on-write, not in-place)
        assert s0.row_count == n0
        re0 = t.compute_stats()
        for c in ("a", "b"):
            assert (
                s0.sketches[c].registers.tobytes()
                == re0.sketches[c].registers.tobytes()
            ), c

    _with_sketch_mode(body)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 10_000))
def test_incremental_estimate_within_5x_rse(seed):
    def body():
        rng = np.random.default_rng((0xB7, seed))
        n0, k = 3000, 1500
        vals0 = rng.integers(0, 2000, n0).astype(np.int32)
        t = Table.create("T", {"a": vals0}, capacity=n0 + k)
        s0 = t.compute_stats()
        batch = {"a": rng.integers(0, 2000, k).astype(np.int32)}
        t2, _, _ = t.insert(batch)
        inc = t2.compute_stats(prev=s0, appended=batch)
        truth = int(np.unique(np.concatenate([vals0, batch["a"]])).size)
        err = abs(inc.distinct["a"] - truth) / truth
        assert err <= 5 * _RSE, (inc.distinct["a"], truth, err)

    _with_sketch_mode(body)


def test_engine_pure_insert_takes_incremental_path(sketch_mode):
    eng = GRFusion()
    rng = np.random.default_rng(3)
    n0 = 64
    eng.create_table(
        "E",
        {"src": rng.integers(0, 32, n0).astype(np.int32),
         "dst": rng.integers(0, 32, n0).astype(np.int32)},
        capacity=256,
    )
    s0 = eng.table_stats("E")  # populates the per-epoch cache
    assert s0.sketches is not None
    assert eng.events["stats_incremental"] == 0
    eng.insert("E", {"src": rng.integers(0, 32, 16).astype(np.int32),
                     "dst": rng.integers(0, 32, 16).astype(np.int32)})
    assert eng.events["stats_incremental"] == 1
    s1 = eng.table_stats("E")  # cache refreshed in place: same object
    assert s1.row_count == n0 + 16
    full = eng.tables["E"].compute_stats()
    for c in ("src", "dst"):
        assert (
            s1.sketches[c].registers.tobytes()
            == full.sketches[c].registers.tobytes()
        ), c
    # a delete breaks the pure-insert precondition: next insert rescans
    from repro.core.query import col

    eng.delete_where("E", col("src") == 0)
    eng.insert("E", {"src": np.array([1], np.int32),
                     "dst": np.array([2], np.int32)})
    assert eng.events["stats_incremental"] == 1  # did NOT fire again


def test_sketch_copy_isolates_registers():
    a = HyperLogLog().add(np.arange(100, dtype=np.int64))
    b = a.copy().add(np.arange(100, 200, dtype=np.int64))
    assert a.registers.tobytes() != b.registers.tobytes()
    c = HyperLogLog().add(np.arange(200, dtype=np.int64))
    assert b.registers.tobytes() == c.registers.tobytes()
