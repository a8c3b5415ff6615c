"""Fused BFS frontier-hop Pallas TPU kernel (the paper's BFScan, §5.1.2).

One traversal hop for a batch of S concurrent queries, lane-major layout
(state ``[S, VP]``, vertices on the 128-wide minor axis):

    acc[:, dst_tile]   = sum_j msgs_j @ onehot(local_dst_j)^T   (MXU scatter)
    next[:, dst_tile]  = (acc > 0) & ~visited                   (frontier OR)
    dist[:, dst_tile]  = hop  where newly reached
    visited           |= next

The expansion (scatter-by-matmul) and the entire BFS epilogue (dedup against
the visited set, distance stamping) are fused into one pass over the
destination-vertex tiles — the VMEM-resident equivalent of the paper's
"explore a traversed vertex only once" bookkeeping. msgs are the pushed-down
predicate-masked frontier values gathered by edge source (ops.py), so
filtering happens during the traversal exactly as §6.2 prescribes.

Layout: the packed edge stream is flat, ``T * J`` edge blocks of ``BE``
slots each (tile ``t`` owns blocks ``t*J .. t*J+J-1``). ``msgs`` is
``[S, T*J*BE]`` and ``ldst`` is ``[1, T*J*BE]``, so every block's last two
dims are either whole array dims or multiples of the TPU's (8, 128) tiling,
and no HBM array carries a narrow minor dim that the tiling would pad.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret

# msgs dtype on the TPU: 0/1 values are exact in bf16, which halves the
# largest per-hop buffer ([S, T*J*BE]); the MXU accumulates in f32. The
# interpreter stays in f32 (the CPU dot has no bf16 x bf16 -> f32 form).
MSG_DTYPE = jnp.bfloat16


def _hop_kernel(msgs_ref, ldst_ref, vis_ref, dist_ref, hop_ref,
                next_ref, ndist_ref, nvis_ref, *, block_rows: int, n_eblocks: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        next_ref[...] = jnp.zeros_like(next_ref)

    msgs = msgs_ref[...]  # [S, BE] 0/1 (already predicate-masked)
    ldst = ldst_ref[...]  # [1, BE] row within the tile, -1 = padding
    be = ldst.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, be), 0)
    onehot = (ldst == rows).astype(msgs.dtype)  # [BT, BE]
    next_ref[...] += jax.lax.dot_general(
        msgs, onehot, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j == n_eblocks - 1)
    def _finalize():
        acc = next_ref[...]
        vis = vis_ref[...]
        dist = dist_ref[...]
        hop = hop_ref[...]  # [1, 1], broadcasts
        newly = (acc > 0.0) & (vis == 0.0)
        next_ref[...] = newly.astype(jnp.float32)
        ndist_ref[...] = jnp.where(newly & (dist < 0), hop, dist)
        nvis_ref[...] = jnp.maximum(vis, newly.astype(jnp.float32))


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_edges", "interpret")
)
def frontier_hop(
    msgs: jnp.ndarray,  # [S, T*J*BE] masked frontier values by packed slot
    ldst: jnp.ndarray,  # int32 [1, T*J*BE] row within the dst tile (-1 pad)
    visited: jnp.ndarray,  # f32 [S, T*BT]
    dist: jnp.ndarray,  # int32 [S, T*BT]
    hop: jnp.ndarray,  # int32 [1, 1] current hop index
    *,
    block_rows: int,
    block_edges: int,
    interpret: bool | None = None,
):
    interpret = resolve_interpret(interpret)
    S, VP = visited.shape
    T = VP // block_rows
    J = msgs.shape[1] // (T * block_edges)
    out_shapes = (
        jax.ShapeDtypeStruct((S, VP), jnp.float32),  # next frontier
        jax.ShapeDtypeStruct((S, VP), jnp.int32),  # dist
        jax.ShapeDtypeStruct((S, VP), jnp.float32),  # visited
    )
    tile = lambda i, j: (0, i)
    eblock = lambda i, j: (0, i * J + j)
    nxt, ndist, nvis = pl.pallas_call(
        functools.partial(_hop_kernel, block_rows=block_rows, n_eblocks=J),
        grid=(T, J),
        in_specs=[
            pl.BlockSpec((S, block_edges), eblock),
            pl.BlockSpec((1, block_edges), eblock),
            pl.BlockSpec((S, block_rows), tile),
            pl.BlockSpec((S, block_rows), tile),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((S, block_rows), tile),
            pl.BlockSpec((S, block_rows), tile),
            pl.BlockSpec((S, block_rows), tile),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(msgs.astype(jnp.float32 if interpret else MSG_DTYPE), ldst, visited,
      dist, hop)
    return nxt, ndist, nvis
