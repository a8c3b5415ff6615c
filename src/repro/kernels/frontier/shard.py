"""Shard-local packed frontier sweeps for the ``sharded`` traversal backend.

The single-device sweeps hold the whole edge stream in one device's memory;
graphs bigger than one HBM need the stream *partitioned*. This module is the
kernel-layer half of that story:

* :func:`partition_edges_by_dst_block` — host-side **edge-cut by dst
  block**: shard ``s`` owns every edge whose destination falls in its
  contiguous block of vertices (block boundaries aligned to the packed
  frontier kernel's ``block_rows`` tiling, stream padding aligned to the
  engine's adaptive blocked-COO granularity so shapes — and therefore XLA
  traces — are shared across topologies of similar size). Paid once per
  topology epoch and cached by the engine, exactly like the dst-sort pack.
* :func:`sharded_bfs` / :func:`sharded_sssp_dist` — ``shard_map`` drivers
  over a 1-D ``"shards"`` mesh. Each device runs the scatter relaxation
  over *its* edge slice only; per-hop partial frontiers / distance arrays
  are combined with XLA's all-reduce under an exact op (``pmax`` for the
  frontier OR, ``pmin`` for distances), never the int8 error-feedback
  ring — frontier membership and min-fixpoint distances are
  correctness-critical (see ``traversal_allreduce``'s lane guard). The
  hand-rolled ring (:func:`repro.dist.compression.ring_allreduce_exact`)
  gives the same bits, but the TPU compiler spends minutes on it at
  Twitter-cell widths (about 140 s for [16, 2^22] uint8 over a v5e 2x2),
  against seconds for the native collective.

Bit-identity argument (the differential suite asserts it at host-platform
device counts 1/2/4): BFS combines per-shard boolean scatter-ORs — set
union is partition-independent — and mirrors the single-device while-loop's
stop conditions exactly, so even target-early-exit partial sweeps match.
SSSP runs Jacobi rounds where each shard computes
``min(dist, shard-local candidates)`` from the *same* replicated ``dist``;
the elementwise float32 min across shards equals the unsharded round's
result bit-for-bit (min never rounds), so every iterate — and the
``changed`` stopping sequence — is identical to ``xla_coo``'s.

The hop loops live *inside* one jitted ``shard_map`` call: state stays on
device across hops, and the per-hop combine is device-to-device collective
traffic. Host transfers of shard_map outputs inside a hop loop are exactly
what the ``cross-shard-host-transfer`` lint rule rejects.
"""
from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.dist.sharding import TRAVERSAL_AXIS, edge_stream_specs

_INF = jnp.float32(jnp.inf)

# Trace counters, module-level like the engine's: one XLA trace cache per
# process, so tests can assert warm sharded queries re-trace nothing.
TRACE_COUNTS: collections.Counter = collections.Counter()


# --------------------------------------------------------------------------
# host-side edge-cut partitioner (once per topology epoch, engine-cached)
# --------------------------------------------------------------------------
def partition_edges_by_dst_block(
    src, dst, eid, n_vertices: int, n_shards: int,
    *, block_rows: int = 128, pad_block: int = 1024,
):
    """Edge-cut the COO stream by destination block.

    Shard ``s`` owns dst positions ``[s*vb, (s+1)*vb)`` where ``vb`` is
    ``ceil(V / n_shards)`` rounded up to a multiple of ``block_rows`` (the
    packed kernel's dst tiling, so a future per-shard Pallas sweep tiles
    cleanly). Edges are dst-sorted within each shard (scatter locality) and
    every shard is padded to the same length — a multiple of ``pad_block``,
    which the engine sets from its adaptive ``_block_for`` machinery so
    similarly-sized topologies share shapes and XLA traces.

    Returns ``(shard_src, shard_dst, shard_eid)`` int32 ``[n_shards, Epad]``
    with pad slots ``src = dst = n_vertices`` and ``eid = -1`` (inert under
    the drop-mode scatters, same convention as ``_blocked_coo``).
    """
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    eid = np.asarray(eid, np.int32)
    V = n_vertices
    live = (eid >= 0) & (src < V) & (dst < V)

    vb = -(-V // max(n_shards, 1))
    vb = -(-vb // block_rows) * block_rows  # align block boundaries
    shard_of = np.minimum(dst // max(vb, 1), n_shards - 1)

    counts = np.bincount(shard_of[live], minlength=n_shards)
    epad = int(counts.max()) if counts.size and counts.max() else 0
    epad = max(-(-max(epad, 1) // pad_block) * pad_block, pad_block)

    ssrc = np.full((n_shards, epad), V, np.int32)
    sdst = np.full((n_shards, epad), V, np.int32)
    seid = np.full((n_shards, epad), -1, np.int32)
    for s in range(n_shards):
        sel = np.flatnonzero(live & (shard_of == s))
        sel = sel[np.argsort(dst[sel], kind="stable")]
        k = sel.shape[0]
        ssrc[s, :k] = src[sel]
        sdst[s, :k] = dst[sel]
        seid[s, :k] = eid[sel]
    return ssrc, sdst, seid


# --------------------------------------------------------------------------
# mesh plumbing
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def traversal_mesh(n_shards: int) -> Mesh:
    """1-D device mesh over the first ``n_shards`` local devices."""
    devs = jax.devices()
    if n_shards > len(devs):
        raise ValueError(
            f"sharded traversal wants {n_shards} devices but only "
            f"{len(devs)} are visible (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_shards} on CPU)"
        )
    return Mesh(np.array(devs[:n_shards]), (TRAVERSAL_AXIS,))


def _specs(*names):
    table = edge_stream_specs()
    return tuple(table[n] for n in names)


# --------------------------------------------------------------------------
# BFS — per-shard scatter-OR, all-reduce OR-combine each hop
# --------------------------------------------------------------------------
def _bfs_body(
    src_l, dst_l, eid_l,  # [1, Epad] local edge slice (leading shard dim)
    d_src, d_dst, d_eid,  # [D] replicated delta COO (invalid: V, V, -1)
    source_pos,  # int32 [S] replicated
    emask_rows,  # bool [ecap] replicated (ones((1,)) = no mask)
    vmask,  # bool [V] replicated
    target_pos,  # int32 [S] replicated (ignored unless has_targets)
    *, max_hops: int, has_targets: bool,
):
    # every shard sweeps its slice plus the whole (tiny) delta buffer; the
    # OR combine is idempotent, so the duplicated delta work is exact
    src_l = jnp.concatenate([src_l[0], d_src])
    dst_l = jnp.concatenate([dst_l[0], d_dst])
    eid_l = jnp.concatenate([eid_l[0], d_eid])
    V = vmask.shape[0]
    S = source_pos.shape[0]
    ecap = emask_rows.shape[0]
    eok = (eid_l >= 0) & jnp.take(emask_rows, jnp.clip(eid_l, 0, ecap - 1))
    src_c = jnp.clip(src_l, 0, V - 1)

    frontier0 = (
        jnp.zeros((S, V), jnp.uint8)
        .at[jnp.arange(S), source_pos]
        .set(1, mode="drop")
    )
    frontier0 = frontier0 * vmask.astype(jnp.uint8)[None, :]
    dist0 = jnp.where(frontier0 > 0, 0, -1).astype(jnp.int32)

    def expand(frontier):
        msgs = jnp.take(frontier, src_c, axis=1) * eok.astype(jnp.uint8)
        local = jnp.zeros_like(frontier).at[:, dst_l].max(msgs, mode="drop")
        return jax.lax.pmax(local, TRAVERSAL_AXIS)  # exact OR over shards

    def targets_done(dist):
        if not has_targets:
            return jnp.asarray(False)
        tp = jnp.clip(target_pos, 0, V - 1)
        found = jnp.take_along_axis(dist, tp[:, None], axis=1)[:, 0] >= 0
        found = found | (target_pos < 0) | (source_pos < 0)
        return jnp.all(found)

    def cond(state):
        frontier, _, dist, hop = state
        return (hop < max_hops) & jnp.any(frontier > 0) & ~targets_done(dist)

    def step(state):
        frontier, visited, dist, hop = state
        nxt = expand(frontier)
        nxt = nxt * (1 - visited) * vmask.astype(jnp.uint8)[None, :]
        dist = jnp.where(nxt > 0, (hop + 1).astype(jnp.int32), dist)
        return nxt, visited | nxt, dist, hop + 1

    _, _, dist, _ = jax.lax.while_loop(
        cond, step, (frontier0, frontier0, dist0, jnp.int32(0))
    )
    return dist


@functools.lru_cache(maxsize=None)
def _sharded_bfs_fn(n_shards: int):
    mesh = traversal_mesh(n_shards)
    in_specs = _specs(
        "shard_src", "shard_dst", "shard_eid",
        "delta_src", "delta_dst", "delta_eid",
        "source_pos", "edge_mask_by_row", "vertex_mask", "target_pos",
    )

    def call(ssrc, sdst, seid, dsrc, ddst, deid, source_pos, emask_rows,
             vmask, target_pos, *, max_hops, has_targets):
        TRACE_COUNTS["traces_bfs_sharded"] += 1  # runs at trace time only
        body = functools.partial(
            _bfs_body, max_hops=max_hops, has_targets=has_targets
        )
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,  # replication of the combined state is by construction
        )(ssrc, sdst, seid, dsrc, ddst, deid, source_pos, emask_rows,
          vmask, target_pos)

    return jax.jit(call, static_argnames=("max_hops", "has_targets"))


def sharded_bfs(
    shard_src, shard_dst, shard_eid,  # int32 [n_shards, Epad]
    source_pos,  # int32 [S]
    n_vertices: int,
    edge_mask_by_row=None,
    vertex_mask=None,  # bool [V]; REQUIRED live-vertex mask from the view
    target_pos=None,  # int32 [S] early-exit targets
    *,
    max_hops: int = 32,
    delta_src=None,  # int32 [D] replicated delta COO (invalid: V, V, -1)
    delta_dst=None,
    delta_eid=None,
):
    """Multi-device BFS over an edge-cut stream. Returns dist int32 [S, V].

    Semantics (loop conditions, masks, early exit) mirror ``traversal.bfs``
    exactly; the only difference is *where* each scatter runs. The optional
    delta arrays carry the view's uncompacted insert buffer, replicated to
    every shard — delta-only inserts stay visible without re-partitioning.
    """
    n_shards = int(shard_src.shape[0])
    source_pos = jnp.asarray(source_pos, jnp.int32)
    if edge_mask_by_row is None:
        edge_mask_by_row = jnp.ones((1,), jnp.bool_)
    has_targets = target_pos is not None
    if target_pos is None:
        target_pos = jnp.full(source_pos.shape, -1, jnp.int32)
    if delta_src is None:
        delta_src = delta_dst = jnp.zeros((0,), jnp.int32)
        delta_eid = jnp.full((0,), -1, jnp.int32)
    return _sharded_bfs_fn(n_shards)(
        jnp.asarray(shard_src), jnp.asarray(shard_dst), jnp.asarray(shard_eid),
        jnp.asarray(delta_src, jnp.int32), jnp.asarray(delta_dst, jnp.int32),
        jnp.asarray(delta_eid, jnp.int32),
        source_pos, jnp.asarray(edge_mask_by_row, jnp.bool_),
        jnp.asarray(vertex_mask, jnp.bool_),
        jnp.asarray(target_pos, jnp.int32),
        max_hops=max_hops, has_targets=has_targets,
    )


# --------------------------------------------------------------------------
# SSSP — per-shard scatter-min Jacobi rounds, all-reduce MIN-combine each round
# --------------------------------------------------------------------------
def _sssp_body(
    src_l, dst_l, eid_l,  # [1, Epad] local edge slice
    d_src, d_dst, d_eid,  # [D] replicated delta COO (invalid: V, V, -1)
    source_pos,  # int32 [S]
    weight_by_row,  # f32 [ecap]
    emask_rows,  # bool [ecap]
    vmask,  # bool [V]
    *, max_iters: int,
):
    # replicated delta edges relax on every shard; the MIN combine is
    # idempotent, so the duplicate candidates are exact
    src_l = jnp.concatenate([src_l[0], d_src])
    dst_l = jnp.concatenate([dst_l[0], d_dst])
    eid_l = jnp.concatenate([eid_l[0], d_eid])
    V = vmask.shape[0]
    S = source_pos.shape[0]
    ecap = weight_by_row.shape[0]
    eid_c = jnp.clip(eid_l, 0, ecap - 1)
    eok = (eid_l >= 0) & jnp.take(emask_rows, jnp.clip(eid_l, 0, emask_rows.shape[0] - 1))
    w_l = jnp.where(eok, jnp.take(weight_by_row, eid_c), _INF)
    src_c = jnp.clip(src_l, 0, V - 1)

    dist0 = jnp.full((S, V), _INF)
    dist0 = dist0.at[jnp.arange(S), jnp.where(source_pos >= 0, source_pos, V)].set(
        0.0, mode="drop")
    dist0 = jnp.where(vmask[None, :], dist0, _INF)

    def relax(dist):
        cand = jnp.take(dist, src_c, axis=1) + w_l[None, :]
        local = dist.at[:, dst_l].min(cand, mode="drop")
        new = jax.lax.pmin(local, TRAVERSAL_AXIS)  # float min never rounds
        return jnp.where(vmask[None, :], new, _INF)

    def cond(state):
        dist, changed, it = state
        return changed & (it < max_iters)

    def step(state):
        dist, _, it = state
        new = relax(dist)
        return new, jnp.any(new < dist), it + 1

    dist, _, _ = jax.lax.while_loop(
        cond, step, (dist0, jnp.asarray(True), jnp.int32(0))
    )
    return dist


@functools.lru_cache(maxsize=None)
def _sharded_sssp_fn(n_shards: int):
    mesh = traversal_mesh(n_shards)
    in_specs = _specs(
        "shard_src", "shard_dst", "shard_eid",
        "delta_src", "delta_dst", "delta_eid",
        "source_pos", "weight_by_row", "edge_mask_by_row", "vertex_mask",
    )

    def call(ssrc, sdst, seid, dsrc, ddst, deid, source_pos, weight_by_row,
             emask_rows, vmask, *, max_iters):
        TRACE_COUNTS["traces_sssp_sharded"] += 1  # runs at trace time only
        body = functools.partial(_sssp_body, max_iters=max_iters)
        return jax.shard_map(
            body, mesh=mesh, in_specs=in_specs, out_specs=P(),
            check_vma=False,
        )(ssrc, sdst, seid, dsrc, ddst, deid, source_pos, weight_by_row,
          emask_rows, vmask)

    return jax.jit(call, static_argnames=("max_iters",))


def sharded_sssp_dist(
    shard_src, shard_dst, shard_eid,  # int32 [n_shards, Epad]
    source_pos,  # int32 [S]
    weight_by_row,  # f32 [edge_cap]
    n_vertices: int,
    edge_mask_by_row=None,
    vertex_mask=None,  # bool [V]; REQUIRED live-vertex mask from the view
    *,
    delta_src=None,  # int32 [D] replicated delta COO (invalid: V, V, -1)
    delta_dst=None,
    delta_eid=None,
):
    """Multi-device Bellman-Ford distances over an edge-cut stream, in
    rounds until no distance falls (at most ``n_vertices``, as
    ``traversal.round_bound`` bounds every SSSP loop).

    Returns dist f32 [S, V]; parents come from the engine's canonical
    single-pass parent extraction, shared with every other backend. The
    optional delta arrays carry the view's uncompacted insert buffer,
    replicated to every shard.
    """
    n_shards = int(shard_src.shape[0])
    source_pos = jnp.asarray(source_pos, jnp.int32)
    weight_by_row = jnp.asarray(weight_by_row, jnp.float32)
    if edge_mask_by_row is None:
        edge_mask_by_row = jnp.ones((1,), jnp.bool_)
    if delta_src is None:
        delta_src = delta_dst = jnp.zeros((0,), jnp.int32)
        delta_eid = jnp.full((0,), -1, jnp.int32)
    return _sharded_sssp_fn(n_shards)(
        jnp.asarray(shard_src), jnp.asarray(shard_dst), jnp.asarray(shard_eid),
        jnp.asarray(delta_src, jnp.int32), jnp.asarray(delta_dst, jnp.int32),
        jnp.asarray(delta_eid, jnp.int32),
        source_pos, weight_by_row,
        jnp.asarray(edge_mask_by_row, jnp.bool_),
        jnp.asarray(vertex_mask, jnp.bool_),
        max_iters=max(n_vertices, 1),
    )
