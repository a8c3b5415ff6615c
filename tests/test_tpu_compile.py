"""Compiles of the engine's device programs for a described TPU v5e.

Nothing runs: each test lowers a program from abstract shapes and
compiles it for a ``v5e:2x2`` topology that is described, not attached,
so the TPU compiler refuses here what it would refuse on the chip (block
shapes off the tiling, programs over the 16 GiB of HBM). The topology is
described inside a fixture, never at import, and every test skips where
it cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import grfusion as CELL
from repro.core import traversal as T
from repro.dist.sharding import TRAVERSAL_AXIS, edge_stream_specs
from repro.kernels.frontier import shard as FS
from repro.kernels.frontier.kernel import MSG_DTYPE, frontier_hop
from repro.kernels.segment.kernel import tiled_segment_sum

HBM_BYTES = 16 * 2**30  # one v5e chip
BT, BE = 128, 256  # the engine's kernel tile: block_rows, block_edges
S = 16  # lanes of the multi-source sweep at the Twitter cell


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _total_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes)


@pytest.mark.parametrize("lanes,tiles", [(S, CELL.V // BT), (128, 256)])
def test_frontier_hop_compiles(one_chip, lanes, tiles):
    J = 6
    n = tiles * J * BE
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda *a: frontier_hop(*a, block_rows=BT, block_edges=BE,
                                interpret=False)
    ).lower(
        sds((lanes, n), MSG_DTYPE), sds((1, n), jnp.int32),
        sds((lanes, tiles * BT), jnp.float32),
        sds((lanes, tiles * BT), jnp.int32), sds((1, 1), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _total_bytes(compiled) < HBM_BYTES


def test_segment_sum_compiles(one_chip):
    tiles, J, D = 64, 4, 128
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    compiled = jax.jit(
        lambda v, l: tiled_segment_sum(v, l, block_rows=BT, interpret=False)
    ).lower(
        sds((tiles, J, BE, D), jnp.float32), sds((tiles, J, BE), jnp.int32)
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _placed(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree,
    )


def test_xla_bfs_compiles_at_twitter_scale(one_chip):
    view = _placed(CELL._abstract_view(), one_chip)
    sources = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(
        lambda v, s: T.bfs(v, s, target_pos=s, max_hops=8,
                           block_size=1 << 16)
    ).lower(view, sources).compile()
    assert view.n_vertices == CELL.V and view.n_slots == CELL.E
    assert _total_bytes(compiled) < HBM_BYTES


def test_sharded_bfs_compiles_on_four_devices(topo, monkeypatch):
    n = 4
    mesh = Mesh(np.array(topo.devices[:n]), (TRAVERSAL_AXIS,))
    monkeypatch.setattr(FS, "traversal_mesh", lambda k: mesh)
    FS._sharded_bfs_fn.cache_clear()
    try:
        fn = FS._sharded_bfs_fn(n)
        specs = edge_stream_specs()
        epad = CELL.E // n + (1 << 16)
        at = lambda shape, dt, name: jax.ShapeDtypeStruct(
            shape, dt, sharding=NamedSharding(mesh, specs[name]))
        args = (
            at((n, epad), jnp.int32, "shard_src"),
            at((n, epad), jnp.int32, "shard_dst"),
            at((n, epad), jnp.int32, "shard_eid"),
            at((1024,), jnp.int32, "delta_src"),
            at((1024,), jnp.int32, "delta_dst"),
            at((1024,), jnp.int32, "delta_eid"),
            at((S,), jnp.int32, "source_pos"),
            at((CELL.E,), jnp.bool_, "edge_mask_by_row"),
            at((CELL.V,), jnp.bool_, "vertex_mask"),
            at((S,), jnp.int32, "target_pos"),
        )
        compiled = fn.lower(*args, max_hops=8, has_targets=True).compile()
    finally:
        FS._sharded_bfs_fn.cache_clear()
    text = compiled.as_text()
    assert "all-reduce" in text  # the per-hop combine crosses chips
    assert _total_bytes(compiled) < HBM_BYTES  # bytes on each device
