"""Vectorized graph-traversal physical operators (paper §5.1.2, §6.3).

The paper's PathScan has three physical operators: DFScan, BFScan, SPScan.
On TPU the traversal state must be data-parallel, so:

  * ``bfs``   — BFScan: multi-source frontier BFS. The frontier is a
    ``[n_sources, V]`` mask; one hop is a blocked *boolean SpMV over the
    masked adjacency*: gather frontier lanes by edge source, AND with the
    pushed-down edge predicate mask, scatter-OR (max) by edge destination.
    Thousands of queries share one sweep over the edge stream.
  * ``sssp``  — SPScan: Dijkstra's priority queue does not vectorize; the
    accelerator-native equivalent with identical results (non-negative
    weights) is frontier Bellman-Ford relaxation with ``scatter-min``, run to
    fixpoint, followed by one parent-extraction pass for path reconstruction.
  * ``enumerate_paths`` — DFScan/BFScan path enumeration: bounded-length
    simple-path expansion in expand→mask→compact rounds over a *fixed
    capacity* path buffer. The paper's DFS-vs-BFS memory model (F·L vs F^L,
    §6.3) survives as the planner's choice of this buffer capacity; overflow
    is reported, matching the paper's concern for discrete memory use.

All predicate masks are **by edge-table row / vertex position** (pushed-down
filters, §6.2) and are gathered through tuple-pointer arrays inside the hop,
so pruning happens during traversal, never after. Path aggregates
(``Sum(PS.Edges.Cost) < bound``) ride along in the path buffer and prune
in-flight, exactly as §6.2 prescribes.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.graphview import GraphView
from repro.core.struct import pytree, field, static_field

INT_MAX = jnp.iinfo(jnp.int32).max


# --------------------------------------------------------------------------
# shared vector primitives
# --------------------------------------------------------------------------
def expand_by_counts(counts: jnp.ndarray, capacity: int):
    """Flatten variable-fanout expansion into a fixed-capacity slot array.

    Returns (parent, within, valid, total): slot i belongs to ``parent[i]``
    and is its ``within[i]``-th child; slots past ``total`` are invalid.
    """
    counts = counts.astype(jnp.int32)
    offs = jnp.cumsum(counts) - counts
    total = jnp.sum(counts)
    idx = jnp.arange(capacity, dtype=jnp.int32)
    parent = jnp.searchsorted(offs, idx, side="right").astype(jnp.int32) - 1
    parent = jnp.clip(parent, 0, counts.shape[0] - 1)
    within = idx - jnp.take(offs, parent)
    valid = idx < total
    return parent, within, valid, total


def compact_targets(mask: jnp.ndarray, capacity: int, base=0):
    """Scatter targets that pack ``mask`` entries to the front (+``base``).

    Entries that don't fit in ``capacity`` get an out-of-bounds target and are
    dropped by ``mode='drop'`` scatters. Returns (targets, n_kept, overflow).
    """
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1 + base
    tgt = jnp.where(mask & (pos < capacity), pos, capacity)
    total = jnp.sum(mask.astype(jnp.int32))
    overflow = (total + base) > capacity
    kept = jnp.minimum(total + base, capacity) - base
    return tgt.astype(jnp.int32), kept, overflow


def _blocked_coo(view: GraphView, block_size: int):
    """Padded [n_blocks, block] COO streams (main + delta)."""
    src, dst, eid = view.all_coo()
    e = src.shape[0]
    nb = -(-e // block_size)
    pad = nb * block_size - e
    V = view.n_vertices
    src = jnp.pad(src, (0, pad), constant_values=V).reshape(nb, block_size)
    dst = jnp.pad(dst, (0, pad), constant_values=V).reshape(nb, block_size)
    eid = jnp.pad(eid, (0, pad), constant_values=-1).reshape(nb, block_size)
    return src, dst, eid, nb


def _full_edge_mask(view: GraphView, edge_mask_by_row, edge_table_cap: int):
    if edge_mask_by_row is None:
        return jnp.ones((edge_table_cap,), jnp.bool_)
    return edge_mask_by_row


def _walk_rows(view: GraphView, fired, m, emask_rows, relax, acc, *,
               chunk: int, vertex: bool, scope: str):
    """Fold the CSR rows of the vertices with ``fired`` (their fan-out, or
    0) > 0, ``m`` edges in all, into ``acc`` in ``chunk``-slot steps: the
    cost is in chunks of ``m``, not of the stream. ``relax(acc, vert, eid,
    dst, ok)`` folds one chunk: per slot its source vertex (None unless
    ``vertex``), edge row, destination, and whether it is a live slot (in
    the walk, ``dst < V``, passing ``emask_rows``).

    The vertices with edges are listed in order; edge ``g`` of the walk
    (0 <= g < m) is the ``g - first[i]``-th out-edge of list entry ``i``. A
    chunk finds each slot's entry by marking where entries start and taking
    the running max, with no search per slot.
    """
    V = view.n_vertices
    k = jnp.arange(chunk, dtype=jnp.int32)
    has = fired > 0
    at = jnp.where(has, jnp.cumsum(has.astype(jnp.int32)) - 1, V + chunk)
    first = jnp.cumsum(fired) - fired
    # padded by a chunk of INT_MAX so a chunk's slice of starts stays whole
    l_first = jnp.full((V + chunk,), INT_MAX, jnp.int32).at[at].set(first, mode="drop")
    l_base = jnp.zeros((V,), jnp.int32).at[at].set(
        view.out_offsets[:-1] - first, mode="drop")
    if vertex:
        l_vert = jnp.zeros((V,), jnp.int32).at[at].set(
            jnp.arange(V, dtype=jnp.int32), mode="drop")

    def body(c, carry):
        with jax.named_scope(scope):
            acc, i0 = carry  # i0: the entry holding the chunk's first edge
            g0 = c * chunk
            starts = jax.lax.dynamic_slice(l_first, (i0 + 1,), (chunk,)) - g0
            mark = jnp.full((chunk,), -1, jnp.int32).at[starts].set(
                i0 + 1 + k, mode="drop")
            entry = jax.lax.cummax(jnp.maximum(mark, i0))
            ok = g0 + k < m
            slot = jnp.where(ok, jnp.take(l_base, entry) + g0 + k, 0)
            dst = jnp.take(view.out_dst, slot)
            eid = jnp.take(view.out_eid, slot)
            ok = ok & (dst < V) & view.gather_edge_mask(emask_rows, eid)
            vert = jnp.take(l_vert, entry) if vertex else None
            return relax(acc, vert, eid, dst, ok), entry[-1]

    n_chunks = (m + chunk - 1) // chunk
    acc, _ = jax.lax.fori_loop(0, n_chunks, body, (acc, jnp.int32(0)))
    return acc


# --------------------------------------------------------------------------
# BFScan — multi-source frontier BFS
# --------------------------------------------------------------------------
BFS_STATIC_ARGNAMES = (
    "max_hops", "block_size", "unroll_hops", "state_spec", "dist_dtype"
)

# A hop whose frontier fires ``m_f`` CSR edges runs frontier-sparse when
# ``m_f * SPARSE_EDGE_COST`` fits in the padded stream, else dense: the
# chip's cost of one sparse frontier edge over one dense slot. Measured on
# one TPU v5e at Graph500 SCALE 21 (2^26 slots): 85 ns an edge against
# 23 ns a slot, break-even at 3.8; for an SSSP round at SCALE 18 (2^23
# slots): 62 ns an edge against 14 ns a slot, break-even at 4.6 (PERF.md).
SPARSE_EDGE_COST = 4


@functools.partial(jax.jit, static_argnames=BFS_STATIC_ARGNAMES)
def bfs(
    view: GraphView,
    source_pos: jnp.ndarray,  # int32 [S]; -1 = inactive query lane
    edge_mask_by_row: jnp.ndarray | None = None,  # bool [edge_cap]
    vertex_mask: jnp.ndarray | None = None,  # bool [V]
    target_pos: jnp.ndarray | None = None,  # int32 [S] early-exit targets
    *,
    max_hops: int = 32,
    block_size: int = 1 << 16,
    unroll_hops: bool = False,
    state_spec=None,  # PartitionSpec for the [S, V] state (Appendix-B layout)
    dist_dtype: str = "int32",
) -> jnp.ndarray:
    """Hop distances ``dist[S, V]`` (-1 unreachable, 0 at the source).

    With ``target_pos`` the sweep stops as soon as every query lane has
    reached its target (the paper's reachability + LIMIT 1 pattern).
    ``unroll_hops`` replaces the early-exit while loop with a fixed
    unrolled sweep of dense hops (dry-run cost accounting; XLA counts loop
    bodies once).
    """
    return bfs_hops(
        view, source_pos, edge_mask_by_row, vertex_mask, target_pos,
        max_hops=max_hops, block_size=block_size, unroll_hops=unroll_hops,
        state_spec=state_spec, dist_dtype=dist_dtype,
    )[0]


def bfs_hops(
    view: GraphView,
    source_pos: jnp.ndarray,
    edge_mask_by_row: jnp.ndarray | None = None,
    vertex_mask: jnp.ndarray | None = None,
    target_pos: jnp.ndarray | None = None,
    *,
    max_hops: int = 32,
    block_size: int = 1 << 16,
    unroll_hops: bool = False,
    state_spec=None,
    dist_dtype: str = "int32",
):
    """``bfs`` (unjitted) that also counts its hops: (dist [S, V], hops,
    sparse hops), both int32 scalars. ``TraversalEngine`` jits this one and
    counts the hops without a host sync per query.

    Each hop picks its form on the device. Dense sweeps every slot of the
    blocked main + delta stream. Frontier-sparse (top-down, Beamer et al.,
    SC'12) walks only the frontier's CSR rows in ``block_size`` chunks,
    then the delta buffer; it runs while the frontier's out-edges times
    ``SPARSE_EDGE_COST`` fit in the padded stream. Both give the same
    next frontier, so ``dist`` and the hop count do not depend on the form.
    """
    V = view.n_vertices
    S = source_pos.shape[0]
    vmask = view.v_valid if vertex_mask is None else (view.v_valid & vertex_mask)
    vmask_u8 = vmask.astype(jnp.uint8)[None, :]

    src_b, dst_b, eid_b, nb = _blocked_coo(view, block_size)
    ecap = 1 if edge_mask_by_row is None else edge_mask_by_row.shape[0]
    emask_rows = _full_edge_mask(view, edge_mask_by_row, ecap)
    emask_b = (eid_b >= 0) & jnp.take(
        emask_rows, jnp.clip(eid_b, 0, emask_rows.shape[0] - 1)
    )
    sparse_limit = int(nb * block_size / SPARSE_EDGE_COST)

    ddt = jnp.dtype(dist_dtype)

    def constrain(x):
        if state_spec is None:
            return x
        return jax.lax.with_sharding_constraint(x, state_spec)

    frontier0 = (
        jnp.zeros((S, V), jnp.uint8)
        .at[jnp.arange(S), source_pos]
        .set(1, mode="drop")
    )
    frontier0 = constrain(frontier0 * vmask_u8)
    dist0 = constrain(jnp.where(frontier0 > 0, 0, -1).astype(ddt))

    src_c = jnp.clip(src_b, 0, V - 1)

    def expand(frontier):
        def body(i, nxt):
            with jax.named_scope("grf.bfs.block"):
                msgs = jnp.take(frontier, src_c[i], axis=1) * emask_b[i].astype(jnp.uint8)
                return nxt.at[:, dst_b[i]].max(msgs, mode="drop")

        if unroll_hops:  # fixed-shape accounting: unroll the block loop too
            nxt = jnp.zeros_like(frontier)
            for i in range(nb):
                nxt = body(i, nxt)
            return nxt
        return jax.lax.fori_loop(0, nb, body, jnp.zeros_like(frontier))

    # the delta buffer, swept by the sparse form after the CSR (main only)
    d_ok = view.delta_valid & view.gather_edge_mask(emask_rows, view.delta_eid)
    d_src = jnp.clip(view.delta_src, 0, V - 1)
    d_dst = jnp.where(d_ok, view.delta_dst, V)

    def expand_sparse(frontier, fired, m_f):
        """Top-down over the CSR rows of the frontier vertices, whose
        ``fired`` (fan-out) edges sum to ``m_f``, then the delta buffer."""
        def relax(nxt, vert, eid, dst, ok):
            if S > 1:
                msgs = jnp.take(frontier, vert, axis=1) * ok.astype(jnp.uint8)
            else:  # one lane: every listed vertex is that lane's frontier
                msgs = ok.astype(jnp.uint8)[None, :]
            return nxt.at[:, jnp.where(ok, dst, V)].max(msgs, mode="drop")

        nxt = _walk_rows(view, fired, m_f, emask_rows, relax,
                         jnp.zeros_like(frontier), chunk=block_size,
                         vertex=S > 1, scope="grf.bfs.chunk")
        msgs = jnp.take(frontier, d_src, axis=1) * d_ok.astype(jnp.uint8)
        return nxt.at[:, d_dst].max(msgs, mode="drop")

    def targets_done(dist):
        if target_pos is None:
            return jnp.asarray(False)
        tp = jnp.clip(target_pos, 0, V - 1)
        found = jnp.take_along_axis(dist, tp[:, None], axis=1)[:, 0] >= 0
        found = found | (target_pos < 0) | (source_pos < 0)
        return jnp.all(found)

    def cond(state):
        frontier, _, dist, hop, _ = state
        return (hop < max_hops) & jnp.any(frontier > 0) & ~targets_done(dist)

    def step(state):
        with jax.named_scope("grf.bfs.hop"):
            frontier, visited, dist, hop, n_sparse = state
            if unroll_hops:
                nxt, sparse = expand(frontier), jnp.asarray(False)
            else:
                fired = jnp.where(jnp.any(frontier > 0, axis=0), view.fan_out, 0)
                m_f = jnp.sum(fired)
                sparse = m_f <= sparse_limit
                nxt = jax.lax.cond(
                    sparse, lambda f: expand_sparse(f, fired, m_f), expand, frontier
                )
            nxt = constrain(nxt * (1 - visited) * vmask_u8)
            dist = constrain(jnp.where(nxt > 0, (hop + 1).astype(ddt), dist))
            return (nxt, constrain(visited | nxt), dist, hop + 1,
                    n_sparse + sparse.astype(jnp.int32))

    state = (frontier0, frontier0, dist0, jnp.int32(0), jnp.int32(0))
    if unroll_hops:
        for _ in range(max_hops):
            state = step(state)
    else:
        state = jax.lax.while_loop(cond, step, state)
    return state[2], state[3], state[4]


# --------------------------------------------------------------------------
# SPScan — frontier Bellman-Ford with parent extraction
# --------------------------------------------------------------------------
SSSP_STATIC_ARGNAMES = ("block_size",)


@functools.partial(jax.jit, static_argnames=SSSP_STATIC_ARGNAMES)
def sssp(
    view: GraphView,
    source_pos: jnp.ndarray,  # int32 [S]; -1 = inactive query lane
    weight_by_row: jnp.ndarray,  # f32 [edge_cap] (non-negative)
    edge_mask_by_row: jnp.ndarray | None = None,
    vertex_mask: jnp.ndarray | None = None,
    *,
    block_size: int = 1 << 16,
):
    """Shortest-path distances + parent edge slots.

    Returns (dist f32 [S, V], parent_slot int32 [S, V]) where parent_slot
    indexes the padded COO stream (-1 = none / source). Equivalent to the
    paper's Dijkstra SPScan for non-negative weights.
    """
    return sssp_rounds(
        view, source_pos, weight_by_row, edge_mask_by_row, vertex_mask,
        block_size=block_size,
    )[:2]


def round_bound(n_vertices: int) -> int:
    """Rounds an SSSP loop may run: ``V``. With non-negative weights at
    most ``V - 1`` rounds lower a distance, and one more finds that none
    falls, so the bound never stops a loop early."""
    return max(n_vertices, 1)


def sssp_rounds(
    view: GraphView,
    source_pos: jnp.ndarray,
    weight_by_row: jnp.ndarray,
    edge_mask_by_row: jnp.ndarray | None = None,
    vertex_mask: jnp.ndarray | None = None,
    *,
    block_size: int = 1 << 16,
):
    """``sssp`` (unjitted) with its counts: (dist, parent, rounds, sparse
    rounds, reached vertices [S], reached CSR slots [S]).
    ``TraversalEngine`` jits this one and sums the counts on the device.

    Round 1 relaxes out of the sources, each later round out of the
    vertices whose distance fell in the round before, until none falls
    (``round_bound``).
    Each round picks its form on the device, as ``bfs_hops`` does: while
    the changed vertices' out-edges times ``SPARSE_EDGE_COST`` fit in the
    padded stream it walks only their CSR rows, then the delta buffer;
    else it sweeps every slot. Both read the round's starting distances
    (Jacobi), and a vertex that did not change has already relaxed its
    edges, so every round ends on the same distances in either form: those
    of the dense loop, and the least fixpoint of ``min(dist[u] + w)``.

    A lane's reached vertices are its finite distances; their CSR slots
    are the sum of their fan-out (main arrays); inactive lanes count 0.
    """
    V = view.n_vertices
    S = source_pos.shape[0]
    vmask = view.v_valid if vertex_mask is None else (view.v_valid & vertex_mask)
    INF = jnp.float32(jnp.inf)

    src_b, dst_b, eid_b, nb = _blocked_coo(view, block_size)
    ecap = weight_by_row.shape[0]
    emask_rows = _full_edge_mask(view, edge_mask_by_row, ecap)
    w_rows = weight_by_row.astype(jnp.float32)
    eid_c = jnp.clip(eid_b, 0, ecap - 1)
    ok_b = (eid_b >= 0) & jnp.take(emask_rows, eid_c)
    w_b = jnp.where(ok_b, jnp.take(w_rows, eid_c), INF)
    src_c = jnp.clip(src_b, 0, V - 1)
    sparse_limit = int(nb * block_size / SPARSE_EDGE_COST)

    active = source_pos >= 0
    dist0 = jnp.full((S, V), INF)
    dist0 = dist0.at[jnp.arange(S), jnp.where(active, source_pos, V)].set(
        0.0, mode="drop")
    dist0 = jnp.where(vmask[None, :], dist0, INF)

    def relax_dense(dist):
        def body(i, d):
            cand = jnp.take(dist, src_c[i], axis=1) + w_b[i][None, :]
            return d.at[:, dst_b[i]].min(cand, mode="drop")

        return jax.lax.fori_loop(0, nb, body, dist)

    # the delta buffer, relaxed by the sparse form after the CSR (main only)
    d_ok = view.delta_valid & view.gather_edge_mask(emask_rows, view.delta_eid)
    d_src = jnp.clip(view.delta_src, 0, V - 1)
    d_dst = jnp.where(d_ok, view.delta_dst, V)
    d_w = jnp.take(w_rows, jnp.clip(view.delta_eid, 0, ecap - 1))

    def relax_sparse(dist, changed, fired, m_c):
        """Out of the changed vertices only: their CSR rows, whose
        ``fired`` (fan-out) edges sum to ``m_c``, then the delta buffer."""
        def relax(new, vert, eid, dst, ok):
            cand = jnp.take(dist, vert, axis=1) + jnp.take(
                w_rows, jnp.clip(eid, 0, ecap - 1))[None, :]
            live = ok[None, :]
            if S > 1:  # one lane: every listed vertex changed in it
                live = live & jnp.take(changed, vert, axis=1)
            cand = jnp.where(live, cand, INF)
            return new.at[:, jnp.where(ok, dst, V)].min(cand, mode="drop")

        new = _walk_rows(view, fired, m_c, emask_rows, relax, dist,
                         chunk=block_size, vertex=True, scope="grf.sssp.chunk")
        live = jnp.take(changed, d_src, axis=1) & d_ok[None, :]
        cand = jnp.where(live, jnp.take(dist, d_src, axis=1) + d_w[None, :], INF)
        return new.at[:, d_dst].min(cand, mode="drop")

    def cond(state):
        _, changed, it, _ = state
        return jnp.any(changed) & (it < round_bound(V))

    def step(state):
        with jax.named_scope("grf.sssp.round"):
            dist, changed, it, n_sparse = state
            fired = jnp.where(jnp.any(changed, axis=0), view.fan_out, 0)
            m_c = jnp.sum(fired)
            sparse = m_c <= sparse_limit
            new = jax.lax.cond(
                sparse, lambda d: relax_sparse(d, changed, fired, m_c),
                relax_dense, dist,
            )
            new = jnp.where(vmask[None, :], new, INF)
            return new, new < dist, it + 1, n_sparse + sparse.astype(jnp.int32)

    dist, _, rounds, n_sparse = jax.lax.while_loop(
        cond, step, (dist0, jnp.isfinite(dist0), jnp.int32(0), jnp.int32(0))
    )
    parent = _parent_pass(
        view, dist, source_pos, weight_by_row,
        edge_mask_by_row=edge_mask_by_row, block_size=block_size,
    )
    reached = jnp.isfinite(dist) & active[:, None]
    return (
        dist, parent, rounds, n_sparse,
        jnp.sum(reached, axis=1, dtype=jnp.int32),
        jnp.sum(jnp.where(reached, view.fan_out[None, :], 0), axis=1, dtype=jnp.int32),
    )


def _parent_pass(
    view: GraphView,
    dist: jnp.ndarray,  # f32 [S, V] converged SSSP distances
    source_pos: jnp.ndarray,  # int32 [S]
    weight_by_row: jnp.ndarray,
    edge_mask_by_row: jnp.ndarray | None = None,
    *,
    block_size: int = 1 << 16,
) -> jnp.ndarray:
    """Canonical parent extraction: one pass over the blocked COO stream;
    among edges achieving dist[dst] pick the lowest slot index (deterministic
    tie-break). Because slots index the padded ``all_coo`` stream, any
    backend that produces the same ``dist`` gets bit-identical parents from
    this pass — the seam the differential harness relies on.
    """
    V = view.n_vertices
    S = dist.shape[0]
    INF = jnp.float32(jnp.inf)
    src_b, dst_b, eid_b, nb = _blocked_coo(view, block_size)
    ecap = weight_by_row.shape[0]
    emask_rows = _full_edge_mask(view, edge_mask_by_row, ecap)
    eid_c = jnp.clip(eid_b, 0, ecap - 1)
    ok_b = (eid_b >= 0) & jnp.take(emask_rows, eid_c)
    w_b = jnp.where(ok_b, jnp.take(weight_by_row.astype(jnp.float32), eid_c), INF)
    src_c = jnp.clip(src_b, 0, V - 1)

    def parent_body(i, par):
        cand = jnp.take(dist, src_c[i], axis=1) + w_b[i][None, :]
        reach = jnp.take_along_axis(
            dist, jnp.clip(dst_b[i], 0, V - 1)[None, :].repeat(S, 0), axis=1
        )
        hit = jnp.isclose(cand, reach, rtol=1e-6, atol=1e-6) & (cand < INF)
        slot = i * src_b.shape[1] + jnp.arange(src_b.shape[1], dtype=jnp.int32)
        val = jnp.where(hit, slot[None, :], INT_MAX)
        return par.at[:, dst_b[i]].min(val, mode="drop")

    parent = jax.lax.fori_loop(
        0, nb, parent_body, jnp.full((S, V), INT_MAX, jnp.int32)
    )
    at_source = (
        jnp.zeros((S, V), jnp.bool_).at[jnp.arange(S), source_pos].set(True, mode="drop")
    )
    return jnp.where((parent == INT_MAX) | at_source | ~jnp.isfinite(dist), -1, parent)


sssp_parents = jax.jit(_parent_pass, static_argnames=("block_size",))


@functools.partial(jax.jit, static_argnames=("max_len", "block_size"))
def reconstruct_paths(
    view: GraphView,
    parent_slot: jnp.ndarray,  # int32 [S, V]
    target_pos: jnp.ndarray,  # int32 [S]
    *,
    max_len: int = 32,
    block_size: int = 1 << 16,
):
    """Backtrack parent slots into edge-row / vertex-position sequences.

    Returns (edges int32 [S, max_len] edge rows reversed-order -1 padded,
    verts int32 [S, max_len+1], length int32 [S]).
    """
    src_b, _, eid_b, _ = _blocked_coo(view, block_size)
    flat_src = src_b.reshape(-1)
    flat_eid = eid_b.reshape(-1)
    V = view.n_vertices
    S = target_pos.shape[0]

    def one(parent_row, tgt):
        def body(j, state):
            cur, edges, verts, length = state
            slot = jnp.where(cur >= 0, parent_row[jnp.clip(cur, 0, V - 1)], -1)
            has = slot >= 0
            e = jnp.where(has, flat_eid[jnp.clip(slot, 0, flat_eid.shape[0] - 1)], -1)
            nxt = jnp.where(has, flat_src[jnp.clip(slot, 0, flat_src.shape[0] - 1)], -1)
            edges = edges.at[j].set(jnp.where(has, e, -1))
            verts = verts.at[j + 1].set(jnp.where(has, nxt, -1))
            length = length + has.astype(jnp.int32)
            return nxt, edges, verts, length

        edges0 = jnp.full((max_len,), -1, jnp.int32)
        verts0 = jnp.full((max_len + 1,), -1, jnp.int32).at[0].set(tgt)
        _, edges, verts, length = jax.lax.fori_loop(
            0, max_len, body, (tgt, edges0, verts0, jnp.int32(0))
        )
        return edges, verts, length

    return jax.vmap(one)(parent_slot, target_pos)


# --------------------------------------------------------------------------
# PathScan — bounded simple-path enumeration (expand / mask / compact)
# --------------------------------------------------------------------------
@pytree
class PathSet:
    """Fixed-capacity set of materialized paths (the Path extended-tuple type,
    paper §5.2: Length, StartVertex, EndVertex, Vertexes, Edges + aggregates)."""

    edges: jnp.ndarray = field()  # int32 [R, Lmax] edge-table rows, -1 pad
    verts: jnp.ndarray = field()  # int32 [R, Lmax+1] vertex positions, -1 pad
    length: jnp.ndarray = field()  # int32 [R]
    agg: jnp.ndarray = field()  # f32 [R, n_agg] running aggregates
    anyf: jnp.ndarray = field()  # bool [R, n_any] ANY-predicate flags
    origin: jnp.ndarray = field()  # int32 [R] probe lane the path grew from
    count: jnp.ndarray = field()  # int32 scalar
    overflow: jnp.ndarray = field()  # bool scalar (result or work buffer)

    @property
    def capacity(self):
        return int(self.edges.shape[0])

    @property
    def max_len(self):
        return int(self.edges.shape[1])

    def start_vertex(self):
        return self.verts[:, 0]

    def end_vertex(self):
        idx = jnp.clip(self.length, 0, self.max_len)
        return jnp.take_along_axis(self.verts, idx[:, None], axis=1)[:, 0]

    def valid(self):
        return jnp.arange(self.capacity) < self.count


def enumerate_paths(
    view: GraphView,
    start_pos: jnp.ndarray,  # int32 [S] (-1 inactive)
    *,
    min_len: int,
    max_len: int,
    hop_edge_masks: Sequence[jnp.ndarray] | None = None,  # per hop, by edge row
    vertex_mask: jnp.ndarray | None = None,  # by position (interior+end)
    start_vertex_mask: jnp.ndarray | None = None,
    end_anchor: jnp.ndarray | None = None,  # bool [V] end-vertex requirement
    close_loop: bool = False,  # require end == start at max_len (triangles)
    agg_weights: jnp.ndarray | None = None,  # f32 [n_agg, edge_cap]
    agg_upper_bounds: jnp.ndarray | None = None,  # f32 [n_agg] prune if sum >
    any_masks: jnp.ndarray | None = None,  # bool [n_any, edge_cap]
    work_capacity: int = 1 << 14,
    result_capacity: int = 1 << 12,
    count_only: bool = False,
):
    """Enumerate simple paths of length in [min_len, max_len] from start_pos.

    Per-hop predicate masks are applied *during* expansion (pushdown, §6.2);
    running aggregates prune in-flight against upper bounds. ``close_loop``
    restricts the final hop to return to the start vertex (sub-graph pattern
    queries, Listing 4). Requires a compacted view (the engine compacts the
    delta buffer before enumeration).

    Returns a PathSet (or (count, overflow) when count_only).
    """
    V = view.n_vertices
    W = work_capacity
    R = result_capacity
    Lmax = max_len
    n_agg = 0 if agg_weights is None else agg_weights.shape[0]
    n_any = 0 if any_masks is None else any_masks.shape[0]

    vmask = view.v_valid if vertex_mask is None else (view.v_valid & vertex_mask)
    smask = vmask if start_vertex_mask is None else (vmask & start_vertex_mask)

    S = start_pos.shape[0]
    sp = jnp.clip(start_pos, 0, V - 1)
    alive0 = (start_pos >= 0) & jnp.take(smask, sp)

    # layer state, capacity W
    def place(x, fill):
        pad = jnp.full((W - S,) + x.shape[1:], fill, x.dtype) if x.ndim > 1 else jnp.full((W - S,), fill, x.dtype)
        return jnp.concatenate([x, pad], axis=0)

    if S > W:
        raise ValueError("work_capacity smaller than the start set")
    end = place(jnp.where(alive0, sp, 0), 0)
    verts = jnp.full((W, Lmax + 1), -1, jnp.int32).at[: S, 0].set(jnp.where(alive0, sp, -1))
    edges = jnp.full((W, Lmax), -1, jnp.int32)
    agg = jnp.zeros((W, max(n_agg, 1)), jnp.float32)
    anyf = jnp.zeros((W, max(n_any, 1)), jnp.bool_)
    origin = place(jnp.arange(S, dtype=jnp.int32), -1)
    alive = place(alive0, False)

    # results
    r_edges = jnp.full((R, Lmax), -1, jnp.int32)
    r_verts = jnp.full((R, Lmax + 1), -1, jnp.int32)
    r_len = jnp.zeros((R,), jnp.int32)
    r_agg = jnp.zeros((R, max(n_agg, 1)), jnp.float32)
    r_any = jnp.zeros((R, max(n_any, 1)), jnp.bool_)
    r_origin = jnp.full((R,), -1, jnp.int32)
    r_count = jnp.int32(0)
    overflow = jnp.asarray(False)
    count_total = jnp.int32(0)

    ones_rows = jnp.ones((view.out_eid.shape[0],), jnp.bool_)
    ecap = (
        hop_edge_masks[0].shape[0]
        if hop_edge_masks
        else (agg_weights.shape[1] if agg_weights is not None else 1)
    )

    def emit(h_len, end_v, verts_l, edges_l, agg_l, any_l, origin_l, alive_l, r):
        (r_edges, r_verts, r_len, r_agg, r_any, r_origin, r_count, overflow, count_total) = r
        ok = alive_l
        if end_anchor is not None:
            ok = ok & jnp.take(end_anchor, jnp.clip(end_v, 0, V - 1))
        if close_loop:
            ok = ok & (end_v == verts_l[:, 0])
        count_total = count_total + jnp.sum(ok.astype(jnp.int32))
        tgt, _, ovf = compact_targets(ok, R, base=r_count)
        if count_only:
            ovf = jnp.asarray(False)  # result buffer unused when counting
        r_edges = r_edges.at[tgt].set(edges_l, mode="drop")
        r_verts = r_verts.at[tgt].set(verts_l, mode="drop")
        r_len = r_len.at[tgt].set(h_len, mode="drop")
        r_agg = r_agg.at[tgt].set(agg_l, mode="drop")
        r_any = r_any.at[tgt].set(any_l, mode="drop")
        r_origin = r_origin.at[tgt].set(origin_l, mode="drop")
        r_count = jnp.minimum(r_count + jnp.sum(ok.astype(jnp.int32)), R)
        return (r_edges, r_verts, r_len, r_agg, r_any, r_origin, r_count, overflow | ovf, count_total)

    res = (r_edges, r_verts, r_len, r_agg, r_any, r_origin, r_count, overflow, count_total)
    if min_len == 0:
        res = emit(jnp.int32(0), end, verts, edges, agg, anyf, origin, alive, res)

    for h in range(max_len):
        with jax.named_scope("grf.enum.hop"):
            counts = jnp.where(alive, jnp.take(view.fan_out, end), 0)
            parent, within, vslot, total = expand_by_counts(counts, W)
            work_ovf = total > W
            eslot = jnp.take(view.out_offsets, jnp.take(end, parent)) + within
            eslot = jnp.clip(eslot, 0, view.out_eid.shape[0] - 1)
            erow = jnp.take(view.out_eid, eslot)
            ndst = jnp.take(view.out_dst, eslot)

            ok = vslot & (erow >= 0) & (ndst < V)
            erc = jnp.clip(erow, 0, max(ecap - 1, 0))
            if hop_edge_masks is not None:
                ok = ok & jnp.take(hop_edge_masks[h], erc)
            ndc = jnp.clip(ndst, 0, V - 1)
            ok = ok & jnp.take(vmask, ndc)

            pv = jnp.take(verts, parent, axis=0)  # [W, Lmax+1]
            # simple-path: never revisit interior vertices; the start vertex may
            # only be revisited on the closing hop of a loop query.
            revisit_interior = jnp.any(pv[:, 1 : h + 1] == ndst[:, None], axis=1) if h >= 1 else jnp.zeros((W,), jnp.bool_)
            ok = ok & ~revisit_interior
            at_start = pv[:, 0] == ndst
            if close_loop and h == max_len - 1:
                ok = ok & at_start
            else:
                ok = ok & ~at_start

            nagg = jnp.take(agg, parent, axis=0)
            if n_agg:
                wrow = agg_weights[:, erc].T  # [W, n_agg]
                nagg = nagg + wrow
                if agg_upper_bounds is not None:
                    ok = ok & jnp.all(nagg <= agg_upper_bounds[None, :], axis=1)
            nany = jnp.take(anyf, parent, axis=0)
            if n_any:
                nany = nany | any_masks[:, erc].T

            nedges = jnp.take(edges, parent, axis=0).at[:, h].set(jnp.where(ok, erow, -1))
            nverts = pv.at[:, h + 1].set(jnp.where(ok, ndst, -1))

            norigin = jnp.take(origin, parent)

            tgt, kept, ovf = compact_targets(ok, W)
            end = jnp.zeros((W,), jnp.int32).at[tgt].set(ndc, mode="drop")
            verts = jnp.full((W, Lmax + 1), -1, jnp.int32).at[tgt].set(nverts, mode="drop")
            edges = jnp.full((W, Lmax), -1, jnp.int32).at[tgt].set(nedges, mode="drop")
            agg = jnp.zeros_like(agg).at[tgt].set(nagg, mode="drop")
            anyf = jnp.zeros_like(anyf).at[tgt].set(nany, mode="drop")
            origin = jnp.full((W,), -1, jnp.int32).at[tgt].set(norigin, mode="drop")
            alive = jnp.zeros((W,), jnp.bool_).at[tgt].set(ok, mode="drop")
            res = res[:7] + (res[7] | ovf | work_ovf, res[8])

            if (h + 1) >= min_len and (not close_loop or (h + 1) == max_len):
                res = emit(jnp.int32(h + 1), end, verts, edges, agg, anyf, origin, alive, res)

    (r_edges, r_verts, r_len, r_agg, r_any, r_origin, r_count, overflow, count_total) = res
    if count_only:
        return count_total, overflow
    return PathSet(
        edges=r_edges,
        verts=r_verts,
        length=r_len,
        agg=r_agg,
        anyf=r_any,
        origin=r_origin,
        count=r_count,
        overflow=overflow,
    )


enumerate_paths_jit = jax.jit(
    enumerate_paths,
    static_argnames=(
        "min_len", "max_len", "close_loop",
        "work_capacity", "result_capacity", "count_only",
    ),
)


def count_closed_triangles(
    view: GraphView,
    label_masks: Sequence[jnp.ndarray],
    *,
    start_vertex_mask: jnp.ndarray | None = None,
    work_capacity: int = 1 << 16,
):
    """Listing-4 pattern: ordered 3-edge loops with per-position edge masks.

    Start-set pruning: only vertices with at least one out-edge passing the
    first position's mask can begin a match (planner-style pushdown)."""
    assert len(label_masks) == 3
    m0 = label_masks[0]
    src, _, eid = view.all_coo()
    has0 = view.gather_edge_mask(m0, eid)
    seed_mask = (
        jnp.zeros((view.n_vertices,), jnp.bool_)
        .at[src]
        .max(has0, mode="drop")
    )
    if start_vertex_mask is not None:
        seed_mask = seed_mask & start_vertex_mask
    starts = jnp.arange(view.n_vertices, dtype=jnp.int32)
    starts = jnp.where(seed_mask, starts, -1)
    count, overflow = enumerate_paths_jit(
        view,
        starts,
        min_len=3,
        max_len=3,
        hop_edge_masks=list(label_masks),
        close_loop=True,
        work_capacity=work_capacity,
        result_capacity=1,
        count_only=True,
    )
    return count, overflow
