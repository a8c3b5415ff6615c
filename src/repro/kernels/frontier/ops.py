"""jit'd wrapper: multi-source BFS driven by the fused Pallas frontier hop.

Packs the dst-sorted edge stream once per (topology, tile shape) using the
segment-kernel packer, then iterates `frontier_hop` — gather(frontier by
src) and predicate masking happen in XLA (where they fuse into the gather),
the scatter/dedup/distance epilogue in the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_interpret
from repro.kernels.frontier.kernel import frontier_hop
from repro.kernels.frontier.ref import bfs_ref, frontier_hop_ref  # noqa: F401
from repro.kernels.segment.ops import pack_segments


def pack_edges_by_dst(src, dst, n_vertices, *, block_rows=128, block_edges=256):
    """Sort edges by destination and pack for the kernel. Host-side, once per
    topology (amortized like the paper's one-pass view construction).

    Returns (packed_src, packed_eid, ldst) each int32 [T, J, BE]; -1 = pad.
    """
    src = np.asarray(src)
    dst = np.asarray(dst)
    order = np.argsort(dst, kind="stable")
    gather, ldst, T, J = pack_segments(
        dst[order], n_vertices, block_rows=block_rows, block_edges=block_edges
    )
    if len(src) == 0:  # empty stream: all-padding tiles
        pad = np.full_like(gather, -1, dtype=np.int32)
        return pad, pad.copy(), ldst
    src_sorted = src[order]
    safe = np.clip(gather, 0, len(src) - 1)
    packed_src = np.where(gather >= 0, src_sorted[safe], -1)
    packed_eid = np.where(gather >= 0, order[safe], -1)
    return packed_src.astype(np.int32), packed_eid.astype(np.int32), ldst


@functools.partial(
    jax.jit, static_argnames=("block_rows", "block_edges", "interpret")
)
def _gather_hop(frontier, src_flat, ldst_k, visited, dist, hop, *,
                block_rows, block_edges, interpret):
    """One hop as one XLA program: the gather of frontier lanes by packed
    edge source fuses with the kernel's input cast, so the f32 ``[S,
    T*J*BE]`` message array is never materialized."""
    msgs = jnp.take(frontier, src_flat, axis=1)
    return frontier_hop(
        msgs, ldst_k, visited, dist, hop,
        block_rows=block_rows, block_edges=block_edges, interpret=interpret,
    )


def bfs_pallas(
    sources,  # int32 [S] vertex positions (-1 = inactive lane)
    packed_src: jnp.ndarray,  # [T, J, BE]
    packed_eid: jnp.ndarray,  # [T, J, BE]
    ldst: jnp.ndarray,  # [T, J, BE]
    n_vertices: int,
    edge_mask_by_row: jnp.ndarray | None = None,
    vertex_mask: jnp.ndarray | None = None,  # bool [V]
    target_pos: jnp.ndarray | None = None,  # int32 [S] early-exit targets
    *,
    block_rows: int = 128,
    max_hops: int = 8,
    interpret: bool | None = None,
    delta_src=None,  # int32 [D] delta COO buffer (uncompacted inserts)
    delta_dst=None,
    delta_eid=None,
    delta_valid=None,  # bool [D]
):
    """Returns dist int32 [S, V] (-1 unreachable).

    Vertex masks are folded into the packed edge validity (an edge from or
    into a masked vertex never fires), matching the blocked-COO sweep's
    semantics exactly. With ``target_pos`` the host hop loop stops once
    every lane has reached its target (or its lane is inactive), mirroring
    the XLA sweep's while-loop condition.

    The optional ``delta_*`` arrays carry a view's uncompacted insert
    buffer. Each hop unions their contribution into the kernel's frontier
    (same prev-frontier, same not-yet-visited gate), so the packed layout
    — built from the MAIN stream only — stays warm across delta inserts
    while results match the all-edges sweep exactly: a hop's reachable set
    is a union over edges, and union is order-independent.
    """
    packed_src = jnp.asarray(packed_src)
    packed_eid = jnp.asarray(packed_eid)
    ldst = jnp.asarray(ldst)
    T, J, BE = packed_src.shape
    VP = T * block_rows
    sources = jnp.asarray(sources, jnp.int32)
    S = sources.shape[0]

    if edge_mask_by_row is not None:
        eok = (packed_eid >= 0) & jnp.take(
            edge_mask_by_row, jnp.clip(packed_eid, 0, edge_mask_by_row.shape[0] - 1)
        )
    else:
        eok = packed_eid >= 0
    src_ok = (packed_src >= 0) & eok
    src_safe = jnp.clip(packed_src, 0, VP - 1)
    if vertex_mask is not None:
        vmask_p = jnp.pad(
            jnp.asarray(vertex_mask, jnp.bool_), (0, VP - n_vertices),
            constant_values=False,
        )
        gdst = (
            jnp.arange(T, dtype=jnp.int32)[:, None, None] * block_rows + ldst
        )
        src_ok = (
            src_ok
            & jnp.take(vmask_p, src_safe)
            & jnp.take(vmask_p, jnp.clip(gdst, 0, VP - 1))
        )
    ldst_m = jnp.where(src_ok, ldst, -1)

    # delta-edge lanes: validity folds in the row mask and both vertex
    # masks, exactly as packed-edge validity does above
    d_s = d_ok = d_dst_idx = None
    if delta_src is not None:
        delta_src = jnp.asarray(delta_src, jnp.int32)
        delta_dst = jnp.asarray(delta_dst, jnp.int32)
        delta_eid = jnp.asarray(delta_eid, jnp.int32)
        d_ok = jnp.asarray(delta_valid, jnp.bool_) & (delta_eid >= 0)
        if edge_mask_by_row is not None:
            d_ok = d_ok & jnp.take(
                edge_mask_by_row,
                jnp.clip(delta_eid, 0, edge_mask_by_row.shape[0] - 1),
            )
        d_ok = d_ok & (delta_src >= 0) & (delta_src < n_vertices)
        d_ok = d_ok & (delta_dst >= 0) & (delta_dst < n_vertices)
        d_s = jnp.clip(delta_src, 0, VP - 1)
        if vertex_mask is not None:
            d_ok = (
                d_ok
                & jnp.take(vmask_p, d_s)
                & jnp.take(vmask_p, jnp.clip(delta_dst, 0, VP - 1))
            )
        d_dst_idx = jnp.where(d_ok, delta_dst, VP)  # VP -> dropped

    # lane-major state [S, VP]; the kernel layout is the flat packed stream
    # (see kernel.py). A slot masked off above carries ldst -1, so its
    # one-hot row is zero and the message it gathers never lands.
    src_flat = src_safe.reshape(-1)
    ldst_k = ldst_m.reshape(1, -1)
    frontier = (
        jnp.zeros((S, VP), jnp.float32)
        .at[jnp.arange(S), sources]
        .set(1.0, mode="drop")
    )
    if vertex_mask is not None:
        frontier = frontier * vmask_p.astype(jnp.float32)[None, :]
    visited = frontier
    dist = jnp.where(frontier > 0, 0, -1).astype(jnp.int32)

    tgt_c = None
    if target_pos is not None:
        tgt_c = jnp.clip(jnp.asarray(target_pos, jnp.int32), 0, VP - 1)

    for h in range(1, max_hops + 1):
        # same stop conditions as the XLA sweep's while-loop, checked
        # before each hop: frontier drained, or every lane found its target
        if not bool(jnp.any(frontier > 0)):
            break
        if tgt_c is not None:
            found = dist[jnp.arange(S), tgt_c] >= 0
            found = found | (target_pos < 0) | (sources < 0)
            if bool(jnp.all(found)):
                break
        prev = frontier
        frontier, dist, visited = _gather_hop(
            frontier, src_flat, ldst_k, visited, dist,
            jnp.full((1, 1), h, jnp.int32),
            block_rows=block_rows, block_edges=BE,
            interpret=resolve_interpret(interpret),
        )
        if d_s is not None:
            # union in the delta edges' contribution to this hop: messages
            # read the SAME pre-hop frontier the kernel consumed, and the
            # not-yet-visited gate uses the kernel-updated visited set, so
            # a vertex reached by both main and delta gets hop h exactly
            # once — identical to one sweep over the concatenated stream
            dmsg = jnp.take(prev, d_s, axis=1) * d_ok.astype(jnp.float32)[None, :]
            dscat = (
                jnp.zeros((S, VP), jnp.float32)
                .at[:, d_dst_idx]
                .max(dmsg, mode="drop")
            )
            add = (dscat > 0) & (visited == 0)
            addf = add.astype(jnp.float32)
            frontier = jnp.maximum(frontier, addf)
            visited = jnp.maximum(visited, addf)
            dist = jnp.where(add, h, dist)
    return dist[:, :n_vertices]
