"""jit'd wrapper + packing utilities for the tiled segment-sum kernel.

`pack_segments` turns a dst-sorted edge stream into the row-tile-bucketed
layout the kernel consumes (host-side numpy: graph preprocessing, done once
per topology — the same amortization as the paper's one-pass graph-view
construction). `segment_sum` is the end-to-end convenience entry.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.kernels.segment.kernel import tiled_segment_sum
from repro.kernels.segment.ref import segment_sum_ref  # noqa: F401 (re-export)


def pack_segments(
    seg_ids: np.ndarray,  # int32 [E] sorted non-decreasing, -1 = dropped
    num_segments: int,
    *,
    block_rows: int = 128,
    block_edges: int = 256,
):
    """Returns (gather_idx [T, J, BE], ldst [T, J, BE], T, J).

    ``gather_idx`` indexes the original edge stream (-1 = padding); callers
    gather their per-edge values with it so one packing serves any number of
    value arrays (weights, messages, masks).
    """
    seg_ids = np.asarray(seg_ids)
    T = -(-num_segments // block_rows)
    keep = (seg_ids >= 0) & (seg_ids < num_segments)
    tile_of = np.where(keep, seg_ids // block_rows, -1)
    counts = np.bincount(tile_of[tile_of >= 0], minlength=T)
    J = max(1, int(-(-counts.max() // block_edges))) if counts.size else 1
    gather = np.full((T, J * block_edges), -1, np.int32)
    ldst = np.full((T, J * block_edges), -1, np.int32)
    # seg_ids sorted => each tile's kept edges are contiguous in stream
    # order; an edge's slot is its rank among the kept edges minus the
    # number of kept edges in earlier tiles
    e = np.flatnonzero(keep)
    t = tile_of[e]
    first = np.concatenate([[0], np.cumsum(counts)[:-1]])
    k = np.arange(e.shape[0]) - first[t]
    gather[t, k] = e
    ldst[t, k] = seg_ids[e] - t * block_rows
    return (
        gather.reshape(T, J, block_edges),
        ldst.reshape(T, J, block_edges),
        T,
        J,
    )


def segment_sum(
    vals,  # [E, D]
    seg_ids,  # int32 [E] sorted
    num_segments: int,
    *,
    block_rows: int = 128,
    block_edges: int = 256,
    interpret: bool | None = None,
):
    vals = jnp.asarray(vals)
    gather, ldst, T, J = pack_segments(
        np.asarray(seg_ids), num_segments,
        block_rows=block_rows, block_edges=block_edges,
    )
    g = jnp.asarray(gather)
    safe = jnp.clip(g, 0, vals.shape[0] - 1)
    vt = jnp.where(
        (g >= 0)[..., None], jnp.take(vals, safe.reshape(-1), axis=0).reshape(
            T, J, block_edges, vals.shape[-1]
        ), 0.0
    ).astype(jnp.float32)
    out = tiled_segment_sum(
        vt, jnp.asarray(ldst), block_rows=block_rows, interpret=interpret
    )
    return out[:num_segments]
