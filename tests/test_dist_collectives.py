"""Traversal-side collective tests (``repro.dist.compression``).

The sharded traversal backend's correctness rests on two properties the
tests here pin down:

* :func:`ring_allreduce_exact` is **bitwise** identical to reducing the
  unsharded stream — for ``min`` over float32 (including inf lanes, the
  unreached-vertex encoding) and ``or``/``max`` over integer frontier
  lanes — at whatever device counts the process was started with. The
  ``sharded`` CI stage re-runs this module under
  ``XLA_FLAGS=--xla_force_host_platform_device_count={2,4}``; a plain
  tier-1 run covers the single-participant degenerate path.
* the int8 error-feedback ring is **never** routed to dist/parent/
  frontier lanes: those carry integer or min-fixpoint semantics where
  "converges in sum over steps" is meaningless (regression test for the
  ``traversal_allreduce`` lane guard).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.compression import (
    EXACT_LANES,
    ring_allreduce_exact,
    ring_allreduce_int8,
    traversal_allreduce,
)

AXIS = "shards"


def _mesh_sizes():
    n = jax.device_count()
    return [s for s in (1, 2, 4) if s <= n]


def _run_ring(n, per_shard, op, dtype):
    """All-reduce ``per_shard`` ([n, ...] stacked shard contributions)
    over an n-device mesh; returns the replicated result from shard 0."""
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))

    def body(x):
        return ring_allreduce_exact(x[0], axis_name=AXIS, op=op)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS, None),), out_specs=P(),
        check_vma=False,
    )
    return np.asarray(fn(jnp.asarray(per_shard, dtype)))


@pytest.mark.parametrize("n", _mesh_sizes())
@pytest.mark.parametrize("shape", [(7,), (3, 65)])
def test_ring_min_float32_bitwise_exact(n, shape):
    rng = np.random.default_rng(n * 100 + shape[0])
    per_shard = rng.random((n,) + shape).astype(np.float32) * 10
    # inf lanes model unreached vertices; some lanes inf on every shard
    inf_mask = rng.random((n,) + shape) < 0.25
    per_shard[inf_mask] = np.inf
    per_shard[:, ..., :1] = np.inf
    got = _run_ring(n, per_shard, "min", jnp.float32)
    want = per_shard.min(axis=0)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("n", _mesh_sizes())
def test_ring_or_uint8_frontier_exact(n):
    rng = np.random.default_rng(n)
    per_shard = (rng.random((n, 5, 33)) < 0.3).astype(np.uint8)
    got = _run_ring(n, per_shard, "or", jnp.uint8)
    want = per_shard.max(axis=0)
    assert got.tobytes() == want.tobytes()


@pytest.mark.parametrize("n", _mesh_sizes())
def test_ring_max_int32_exact(n):
    rng = np.random.default_rng(n + 7)
    per_shard = rng.integers(-(2**30), 2**30, (n, 41)).astype(np.int32)
    got = _run_ring(n, per_shard, "max", jnp.int32)
    assert got.tobytes() == per_shard.max(axis=0).astype(np.int32).tobytes()


@pytest.mark.parametrize("n", _mesh_sizes())
def test_ring_sum_int32_exact(n):
    # integer sums reassociate exactly (unlike float sums)
    rng = np.random.default_rng(n + 13)
    per_shard = rng.integers(0, 1000, (n, 29)).astype(np.int32)
    got = _run_ring(n, per_shard, "sum", jnp.int32)
    assert got.tobytes() == per_shard.sum(axis=0).astype(np.int32).tobytes()


def test_unknown_op_rejected():
    from repro.dist.compression import _combine

    # the op dispatch sits in the chunk-combine step (reached only on
    # multi-participant axes — n==1 short-circuits to the identity)
    with pytest.raises(ValueError, match="unknown exact all-reduce op"):
        _combine(jnp.zeros((2, 3)), 0, jnp.zeros((3,)), "xor")
    if jax.device_count() >= 2:
        with pytest.raises(ValueError, match="unknown exact all-reduce op"):
            _run_ring(2, np.zeros((2, 4), np.float32), "xor", jnp.float32)


# ----------------------------------------------------------- lane routing
@pytest.mark.parametrize("lane", sorted(EXACT_LANES))
def test_int8_error_feedback_never_touches_exact_lanes(lane):
    """Regression: dist/parent/frontier lanes must reject the quantized
    ring at call time, *before* any collective is traced."""
    with pytest.raises(ValueError, match="exact lane"):
        traversal_allreduce(
            jnp.zeros((4,), jnp.float32), axis_name=AXIS,
            lane=lane, mode="int8_ef",
        )


def test_traversal_allreduce_routes_modes():
    mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))

    def body(x):
        exact = traversal_allreduce(
            x[0], axis_name=AXIS, lane="dist", mode="exact", op="min")
        agg = traversal_allreduce(
            x[0], axis_name=AXIS, lane="agg", mode="int8_ef")
        return exact, agg

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS, None),), out_specs=P(),
        check_vma=False,
    )
    x = jnp.asarray([[1.0, np.inf, 3.0]], jnp.float32)
    exact, agg = fn(x)
    # single-participant axis: both paths are the identity
    assert np.asarray(exact).tobytes() == np.asarray(x[0]).tobytes()
    assert np.asarray(agg).tobytes() == np.asarray(x[0]).tobytes()
    with pytest.raises(ValueError, match="unknown all-reduce mode"):
        traversal_allreduce(x[0], axis_name=AXIS, lane="agg", mode="fp8")


@pytest.mark.parametrize("n", _mesh_sizes())
def test_int8_ring_still_serves_approximate_lanes(n):
    """The quantized ring stays available for approximate-tolerant
    aggregates — per-tensor scale keeps error small for same-magnitude
    contributions."""
    mesh = Mesh(np.array(jax.devices()[:n]), (AXIS,))

    def body(x):
        return ring_allreduce_int8(x[0], axis_name=AXIS)

    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(P(AXIS, None),), out_specs=P(),
        check_vma=False,
    )
    rng = np.random.default_rng(n)
    per_shard = rng.random((n, 64)).astype(np.float32)
    got = np.asarray(fn(jnp.asarray(per_shard)))
    want = per_shard.sum(axis=0)
    assert np.max(np.abs(got - want)) <= 0.05 * max(1.0, np.abs(want).max())
