"""Tiled segment-sum Pallas TPU kernel ("scatter-by-matmul").

The hot loop of both the paper's traversal hop (frontier expansion is a
segment-OR of 0/1 messages by destination vertex) and of every GNN /
embedding-bag in the framework is a segment reduction over a dst-sorted edge
stream. TPUs have no scatter unit; the MXU-native formulation is:

    out[rows of tile t]  +=  onehot(local_dst)  @  vals_block
                              [BT, BE]             [BE, D]

i.e. the scatter becomes a sequence of small matmuls on the systolic array —
the hardware adaptation of the paper's per-edge pointer chase (DESIGN.md §2).

Layout: edges are pre-packed per output row-tile (degree-bucketed ELL-ish
packing, `ops.pack_segments`): every row tile owns `J` edge blocks of size
`BE`; `local_dst` is the row index within the tile (-1 = padding), read by
the kernel from its flat ``[1, T*J*BE]`` form. Grid is
(T, J); grid iteration on TPU is sequential, so the output tile accumulates
across its J edge blocks in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import resolve_interpret


def _seg_kernel(vals_ref, ldst_ref, out_ref, *, block_rows: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    vals = vals_ref[0, 0]  # [BE, D]
    ldst = ldst_ref[...]  # [1, BE]
    be = ldst.shape[1]
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, be), 0)
    onehot = (ldst == rows).astype(vals.dtype)  # [BT, BE]
    out_ref[...] += jnp.dot(onehot, vals, preferred_element_type=out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_rows", "interpret")
)
def tiled_segment_sum(
    vals_t: jnp.ndarray,  # [T, J, BE, D]
    ldst_t: jnp.ndarray,  # int32 [T, J, BE], row-in-tile or -1 padding
    *,
    block_rows: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns out [T * block_rows, D]."""
    T, J, BE, D = vals_t.shape
    # ldst goes in flat ([1, T*J*BE]): a (1, 1, BE) block of [T, J, BE]
    # has a second-minor dim of 1 over J, which TPU tiling rejects
    out = pl.pallas_call(
        functools.partial(_seg_kernel, block_rows=block_rows),
        grid=(T, J),
        in_specs=[
            pl.BlockSpec((1, 1, BE, D), lambda i, j: (i, j, 0, 0)),
            pl.BlockSpec((1, BE), lambda i, j: (0, i * J + j)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((T * block_rows, D), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(vals_t, ldst_t.reshape(1, T * J * BE))
    return out
