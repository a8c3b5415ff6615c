"""TraversalEngine: unified dispatch for all BFS/SSSP/path traversal.

GRAPHITE (arXiv:1412.6477) argues traversal backends should be
interchangeable *physical operators* behind one logical interface; GRFusion
(arXiv:1709.06715) needs that seam so the planner can trade the blocked-COO
XLA sweep against the fused Pallas frontier kernel per query. This module is
that seam. Everything in the engine that walks a graph goes through here.

Backend registry
----------------
  * ``xla_coo``          — the blocked-COO frontier sweep / Bellman-Ford in
                           ``core/traversal.py``. Works everywhere, shapes
                           are static per (S, V), jit-cached.
  * ``pallas_frontier``  — the packed dst-sorted frontier path from
                           ``kernels/frontier/ops.py``: one host-side edge
                           sort per topology, then fused scatter/dedup/
                           distance hops on the MXU (interpret mode off-TPU).
                           SSSP runs dst-sorted packed Jacobi relaxation on
                           the same packing.
  * ``reference``        — pure-numpy oracle (independent of XLA *and*
                           Pallas); the ground truth the differential suite
                           compares everything against.
  * ``sharded``          — multi-device edge-cut sweep: the COO stream is
                           partitioned by dst block across a 1-D device
                           mesh (``kernels/frontier/shard.py``), per-shard
                           frontier relaxations run under ``shard_map`` and
                           per-hop partial frontiers / distances combine
                           with the exact ring all-reduce
                           (``repro.dist.compression``). Graphs bigger than
                           one device's HBM; CI exercises it with
                           ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
  * ``auto``             — device-count-aware density policy: streams past
                           the per-device threshold on a multi-device mesh
                           take ``sharded``; dense multi-source sweeps on
                           TPU take the fused kernel (avg fan-out and batch
                           width above thresholds); everything else takes
                           ``xla_coo``.

All backends return bit-identical results by construction: BFS distances
are integral hop counts; SSSP distances are the unique least fixpoint of
float32 ``min(dist[src] + w)`` relaxation (order-independent for
non-negative weights); SSSP parents always come from the *canonical*
parent pass (``traversal.sssp_parents``) over the blocked COO stream, so
identical distances imply identical parent slots.

Caches
------
  * **Shard-pack cache** — key ``(packing_key, n_shards, pad_block)``,
    value the per-shard edge-cut ``(shard_src, shard_dst, shard_eid)``
    arrays. Same epoch lifecycle as the packing cache below: the edge-cut
    partition is paid once per (packing epoch, mesh width), warm queries
    hit it with zero re-packs (the BENCH_sharded gate asserts this), and
    ``bump_epoch`` invalidates it alongside the dst-sort packs.
  * **Packing cache** — key ``(packing_key, block_rows, block_edges)``,
    value the packed ``(packed_src, packed_eid, ldst)`` arrays built from
    the MAIN coo stream only. The packing key is ``(graph_name,
    pack-epoch)`` when the owning engine registers the view — the
    ``pack:<name>`` epoch bumps ONLY on compaction / rebuild
    (``bump_epoch``); delta-only inserts take ``bump_delta_epoch``, which
    bumps just the plain topology epoch (query/value caches) and leaves
    every pack warm, since all backends consult the delta buffer at query
    time. Standalone views key on a content fingerprint of the main COO
    arrays. Edge sorting is therefore paid once per compaction, not per
    query or per insert. Attribute updates (weights, tombstones,
    predicate masks) never touch the key — the paper's §3.2 decoupling.
  * **Plan (trace) cache** — module-level jitted entry points shared by
    every engine instance; XLA traces are keyed on array shapes only, so
    recompaction with unchanged capacities (and sibling engines with the
    same shapes) reuses traces. ``stats`` counts traces and pack
    builds/hits so tests can assert the second query is cache-hot.

Batched admission
-----------------
``submit_reachability`` / ``submit_sssp`` enqueue point queries;
``flush`` merges each queue into one ``[S, V]`` multi-source sweep (lanes
padded to a power-of-two bucket to bound retracing). This is the paper's
"thousands of queries share one sweep over the edge stream" serving shape.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import os
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import traversal as T
from repro.core.compiled import EpochRegistry, pack_key
from repro.core.graphview import GraphView
from repro.kernels import resolve_interpret
from repro.kernels.frontier import shard as FS
from repro.kernels.frontier.ops import bfs_pallas, pack_edges_by_dst
from repro.robust import faults
from repro.tracing import to_host

BACKENDS = ("xla_coo", "pallas_frontier", "reference", "sharded")
_INF = jnp.float32(jnp.inf)

# Graceful degradation (GRAPHITE's strategy-failover contract): when a
# backend attempt raises — an injected fault, a device error, a kernel
# bug — the query falls over along this chain instead of failing. Every
# backend is bit-identical by construction, so a degraded query returns
# the same answer, just slower; the ``degraded_backend`` flag on
# QueryResult and the failover event counters make the degradation
# visible instead of silent. ``reference`` is the floor: pure numpy,
# no XLA, no Pallas — if it fails too, the error propagates.
FAILOVER_CHAIN = {
    "sharded": ("xla_coo", "reference"),
    "pallas_frontier": ("xla_coo", "reference"),
    "xla_coo": ("reference",),
    "reference": (),
}

# fault-injection seams (repro.robust.faults; compiled to a no-op global
# read when no plan is active)
SITE_DISPATCH = {
    b: faults.register_site(f"traversal.dispatch.{b}") for b in BACKENDS
}
SITE_PACK_BUILD = faults.register_site("traversal.pack_build")
SITE_SHARD_PACK_BUILD = faults.register_site("traversal.shard_pack_build")

# Default auto-policy threshold: edge-stream slots above which a
# multi-device mesh shards the sweep instead of running single-device.
# Sized so every benchmark/test graph below ~4M edge slots keeps its
# existing backend; overridable per engine (tests set it to 1).
SHARD_MIN_SLOTS = 1 << 22

# Trace counters live at module level because the jitted entry points do
# too: one XLA trace cache is shared by every TraversalEngine instance
# (identical shapes never recompile per engine). The counters increment at
# trace time only, so tests can assert "the second query re-traced
# nothing". Per-engine event counts live on the instance; the ``stats``
# property merges both views.
_TRACE_COUNTS: collections.Counter = collections.Counter()


def _trace_counted(fn, key, static_argnames=(), name=None):
    def inner(*a, **k):
        _TRACE_COUNTS[key] += 1  # runs at trace time only
        return fn(*a, **k)

    functools.update_wrapper(inner, fn)
    if name is not None:  # the XLA module is named jit_<name>
        inner.__name__ = inner.__qualname__ = name
    return jax.jit(inner, static_argnames=static_argnames)


# T.bfs with its hop count; compiled as ``jit_bfs``, the module name the
# benchmark's trace reduction reads
_bfs_xla = _trace_counted(
    T.bfs_hops, "traces_bfs_xla", T.BFS_STATIC_ARGNAMES, name="bfs"
)
# T.sssp with its round and reach counts, compiled as ``jit_sssp``
_sssp_xla = _trace_counted(
    T.sssp_rounds, "traces_sssp_xla", T.SSSP_STATIC_ARGNAMES, name="sssp"
)
_enum_xla = _trace_counted(
    T.enumerate_paths, "traces_enum",
    (
        "min_len", "max_len", "close_loop",
        "work_capacity", "result_capacity", "count_only",
    ),
)


def _reference_edges(view: GraphView, edge_mask_by_row=None):
    """Live numpy (src, dst, eid) streams for the oracles: tombstoned /
    masked rows dropped, endpoints in range. The single definition all
    reference implementations share — semantic tweaks happen here once."""
    V = view.n_vertices
    src, dst, eid = (np.asarray(a) for a in view.all_coo())
    ok = eid >= 0
    if edge_mask_by_row is not None:
        em = np.asarray(edge_mask_by_row)
        ok = ok & em[np.clip(eid, 0, em.shape[0] - 1)]
    ok = ok & (src < V) & (dst < V)
    return src[ok], dst[ok], eid[ok]


def _reference_vmask(view: GraphView, vertex_mask=None) -> np.ndarray:
    vmask = np.asarray(view.v_valid)
    if vertex_mask is not None:
        vmask = vmask & np.asarray(vertex_mask)
    return vmask


@dataclasses.dataclass
class PendingQuery:
    """A point query admitted to the batcher; filled in by ``flush``."""

    kind: str  # 'reach' | 'sssp'
    source: int  # vertex position (-1 = unresolvable, answered unreachable)
    target: int
    result: Optional[dict] = None


@jax.jit
def _tally(words, counts):
    """Running sums that pass 2^31: ``words`` int32 [k, 2] (the sum's bits
    above and below bit 16) plus the lane sums of ``counts`` int32 [k, S]
    (each lane's count non-negative and int32)."""
    lo = words[:, 1] + jnp.sum(counts & 0xFFFF, axis=1)
    hi = words[:, 0] + jnp.sum(counts >> 16, axis=1) + (lo >> 16)
    return jnp.stack([hi, lo & 0xFFFF], axis=1)


@jax.jit
def _packed_sssp_dist(
    dist0,  # f32 [S, VP] (INF init, 0 at sources, INF at masked)
    src_safe,  # int32 [F] flat packed sources (clipped)
    gdst,  # int32 [F] flat global dsts (VP = dropped)
    w,  # f32 [F] per-slot weights (INF = inactive slot)
    vmask_p,  # bool [VP]
    max_iters,  # int32: ``T.round_bound``
):
    """Jacobi scatter-min relaxation over the dst-sorted packed stream,
    until no distance falls.

    Ends each round on the same float32 distances as the blocked-COO
    rounds (min over identical candidate sets; float min is exact), which
    is what makes cross-backend distances bit-identical.
    """

    def relax(dist):
        cand = jnp.take(dist, src_safe, axis=1) + w[None, :]
        new = dist.at[:, gdst].min(cand, mode="drop")
        return jnp.where(vmask_p[None, :], new, _INF)

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


class TraversalEngine:
    """Front door for all traversal dispatch (see module docstring)."""

    def __init__(
        self,
        *,
        default_backend: str = "auto",
        block_rows: int = 128,
        block_edges: int = 256,
        block_size: int = 1 << 16,
        interpret: Optional[bool] = None,
        pack_cache_capacity: int = 16,
        lane_width: int = 32,
        max_lanes: int = 1024,
        epochs: Optional[EpochRegistry] = None,
        n_devices: Optional[int] = None,
        shard_min_slots: int = SHARD_MIN_SLOTS,
        backend_retries: int = 1,
        events: Optional[collections.Counter] = None,
    ):
        if default_backend != "auto" and default_backend not in BACKENDS:
            raise ValueError(f"unknown backend {default_backend!r}")
        self.default_backend = default_backend
        # failover policy: each backend in the chain gets 1 + this many
        # attempts before the query falls over to the next backend
        self.backend_retries = max(int(backend_retries), 0)
        # engine-wide event counter (shared with the owning GRFusion so
        # degraded queries are visible in `engine.events`); standalone
        # engines get their own
        self.events = events if events is not None else collections.Counter()
        # per-call degraded flag: set by _dispatch when a fallback backend
        # answered, read (and cleared) by the executor via consume_degraded
        self._last_degraded: Optional[str] = None
        # the most recent exception a backend attempt raised (failover
        # keeps the query alive; this keeps the cause inspectable)
        self.last_error: Optional[BaseException] = None
        # sharded-backend knobs: mesh width (None = every visible device,
        # read per query so forced host-platform device counts apply) and
        # the auto policy's stream-size threshold for picking `sharded`
        self.n_devices = n_devices
        self.shard_min_slots = shard_min_slots
        self.block_rows = block_rows
        self.block_edges = block_edges
        self.block_size = block_size
        # Pallas interpret mode: required off-TPU; overridable for tests
        self.interpret = resolve_interpret(interpret)
        self.lane_width = lane_width
        self.max_lanes = max_lanes  # widest single [S, V] sweep flush builds
        self._stats = collections.Counter()
        # xla_coo hops (all, and the frontier-sparse ones), summed on the
        # device until ``stats`` reads them
        self._hops = jnp.zeros((), jnp.int32)
        self._sparse_hops = jnp.zeros((), jnp.int32)
        # xla_coo SSSP rounds (all, and the frontier-sparse ones), and the
        # vertices and CSR slots its answers reached (``_tally`` words),
        # likewise
        self._sssp_rounds = jnp.zeros((), jnp.int32)
        self._sssp_sparse_rounds = jnp.zeros((), jnp.int32)
        self._sssp_reached = jnp.zeros((2, 2), jnp.int32)
        # graph -> (topology key, avg_fan_out as a host float)
        self._fan_out: Dict[str, Tuple[Tuple, float]] = {}
        self._packs: "collections.OrderedDict" = collections.OrderedDict()
        self._shard_packs: "collections.OrderedDict" = collections.OrderedDict()
        self._pack_cap = pack_cache_capacity
        # shared with the owning GRFusion: one registry answers both "did
        # the topology change?" (packing cache) and "did a table change?"
        # (compiled predicate-mask cache in core/compiled.py)
        self.epochs = epochs if epochs is not None else EpochRegistry()
        self._fp_cache: "collections.OrderedDict" = collections.OrderedDict()
        self._pending: List[Tuple[GraphView, Optional[str], PendingQuery]] = []
        self._pending_w: List[
            Tuple[GraphView, Optional[str], object, PendingQuery]
        ] = []

    @property
    def stats(self) -> collections.Counter:
        """Per-engine event counts merged with the shared trace counters.
        ``hops_xla_coo`` sums the hops every ``xla_coo`` BFS sweep ran,
        ``hops_xla_coo_sparse`` those of them that ran frontier-sparse;
        ``sssp_rounds_xla_coo`` and ``sssp_rounds_xla_coo_sparse`` the same
        for SSSP rounds, ``sssp_reached_vertices`` and
        ``sssp_reached_slots`` the vertices those SSSPs reached (active
        lanes) and their CSR slots."""
        hops, sparse, rounds, sparse_rounds, reached = jax.device_get((
            self._hops, self._sparse_hops, self._sssp_rounds,
            self._sssp_sparse_rounds, self._sssp_reached,
        ))
        reached = [(int(hi) << 16) + int(lo) for hi, lo in reached]
        counted = collections.Counter(
            hops_xla_coo=int(hops), hops_xla_coo_sparse=int(sparse),
            sssp_rounds_xla_coo=int(rounds),
            sssp_rounds_xla_coo_sparse=int(sparse_rounds),
            sssp_reached_vertices=reached[0], sssp_reached_slots=reached[1],
        )
        return self._stats + counted + _TRACE_COUNTS + FS.TRACE_COUNTS

    def fan_out(self, view: GraphView, graph: Optional[str] = None) -> float:
        """``view.avg_fan_out`` as a host float, read once per topology
        epoch of a registered graph (every read for a standalone view)."""
        if graph is None or not self.epochs.known(graph):
            return float(to_host(view.avg_fan_out, "fan_out", self.events))
        key = self.topology_key(view, graph)
        ent = self._fan_out.get(graph)
        if ent is None or ent[0] != key:
            ent = (key, float(to_host(view.avg_fan_out, "fan_out", self.events)))
            self._fan_out[graph] = ent
        return ent[1]

    # ------------------------------------------------------- topology epochs
    def register_view(self, name: str):
        """Start epoch tracking for a named graph (owning-engine path)."""
        self.epochs.ensure(name)
        self.epochs.ensure(pack_key(name))

    def bump_epoch(self, name: str):
        """MAIN arrays changed (compaction / rebuild): invalidate packs
        and every downstream cache keyed on the plain topology epoch."""
        self.epochs.bump(name)
        self.epochs.bump(pack_key(name))
        for packs in (self._packs, self._shard_packs):
            stale = [k for k in packs if k[0][0] == name]
            for k in stale:
                del packs[k]

    def bump_delta_epoch(self, name: str):
        """Delta-only insert: topology changed (query/value caches must
        see the new edges) but MAIN is untouched — packs and shard packs
        stay warm, because every backend consults the delta stream at
        query time. Only ``bump_epoch`` (compaction) drops packs."""
        self.epochs.bump(name)

    def topology_key(self, view: GraphView, graph: Optional[str] = None):
        if graph is not None and self.epochs.known(graph):
            return (graph, self.epochs.get(graph))
        return self._fingerprint(view)

    def packing_key(self, view: GraphView, graph: Optional[str] = None):
        """Cache key for packs/shard packs: the ``pack:<name>`` epoch for
        registered views (bumped on compaction only), or a MAIN-arrays-only
        fingerprint for standalone views — either way, delta inserts leave
        the key (and the cache entry) untouched."""
        if graph is not None and self.epochs.known(graph):
            return (graph, self.epochs.get(pack_key(graph)))
        return self._fingerprint(view, main_only=True)

    def _fingerprint(self, view: GraphView, main_only: bool = False):
        """Content key for standalone views (identity-memoized per object)."""
        ck = (id(view), main_only)
        ent = self._fp_cache.get(ck)
        if ent is not None and ent[0] is view:
            self._fp_cache.move_to_end(ck)
            return ent[1]
        arrays = (view.coo_src, view.coo_dst, view.coo_eid)
        if not main_only:
            arrays = arrays + (
                view.delta_src, view.delta_dst, view.delta_eid,
                view.delta_valid,
            )
        h = hashlib.blake2b(digest_size=16)
        for a in arrays:
            h.update(np.asarray(a).tobytes())
        key = ("#fp", h.hexdigest())
        self._fp_cache[ck] = (view, key)
        while len(self._fp_cache) > 64:
            self._fp_cache.popitem(last=False)
        return key

    # --------------------------------------------------------- packing cache
    def get_pack(self, view: GraphView, graph: Optional[str] = None):
        """Packed dst-sorted streams for the frontier kernel, cached per
        (packing epoch, block shape). Packs cover the MAIN arrays only —
        the delta buffer is consulted at query time — so delta-only
        inserts hit the cached pack unchanged."""
        key = (self.packing_key(view, graph), self.block_rows, self.block_edges)
        hit = self._packs.get(key)
        if hit is not None:
            self._stats["pack_hits"] += 1
            self._packs.move_to_end(key)
            return hit
        faults.check(SITE_PACK_BUILD)
        src, dst, eid = view.coo_src, view.coo_dst, view.coo_eid
        ps, pstream, ldst = pack_edges_by_dst(
            np.asarray(src), np.asarray(dst), view.n_vertices,
            block_rows=self.block_rows, block_edges=self.block_edges,
        )
        # the packer indexes the raw stream; translate to edge-TABLE rows so
        # masks/weights gather correctly for delta and undirected streams
        # (stream position != row there)
        eid_np = np.asarray(eid)
        safe = np.clip(pstream, 0, max(eid_np.shape[0] - 1, 0))
        pe = np.where(pstream >= 0, eid_np[safe], -1).astype(np.int32)
        pack = (jnp.asarray(ps), jnp.asarray(pe), jnp.asarray(ldst))
        self._packs[key] = pack
        while len(self._packs) > self._pack_cap:
            self._packs.popitem(last=False)
        self._stats["pack_builds"] += 1
        return pack

    # ----------------------------------------------------- sharded edge-cut
    def device_count(self) -> int:
        """Mesh width for the sharded backend (constructor override or
        every visible device — read lazily so forced host-platform device
        counts picked up at process start apply)."""
        return self.n_devices if self.n_devices is not None else jax.device_count()

    def get_shard_pack(
        self, view: GraphView, graph: Optional[str] = None,
        n_shards: Optional[int] = None,
    ):
        """Per-shard edge-cut streams for the sharded backend, cached per
        (packing epoch, mesh width), MAIN arrays only (the delta buffer
        rides along replicated at query time, so delta inserts never
        re-partition). The pad granularity reuses the adaptive
        ``_block_for`` machinery so similarly-sized topologies share
        shapes (and therefore XLA traces) across epochs."""
        n = n_shards if n_shards is not None else self.device_count()
        pad_block = self._block_for(view)
        key = (self.packing_key(view, graph), n, pad_block)
        hit = self._shard_packs.get(key)
        if hit is not None:
            self._stats["shard_pack_hits"] += 1
            self._shard_packs.move_to_end(key)
            return hit
        faults.check(SITE_SHARD_PACK_BUILD)
        src, dst, eid = view.coo_src, view.coo_dst, view.coo_eid
        ssrc, sdst, seid = FS.partition_edges_by_dst_block(
            np.asarray(src), np.asarray(dst), np.asarray(eid),
            view.n_vertices, n,
            block_rows=self.block_rows, pad_block=pad_block,
        )
        pack = (jnp.asarray(ssrc), jnp.asarray(sdst), jnp.asarray(seid))
        self._shard_packs[key] = pack
        while len(self._shard_packs) > self._pack_cap:
            self._shard_packs.popitem(last=False)
        self._stats["shard_pack_builds"] += 1
        return pack

    @staticmethod
    def _delta_stream(view: GraphView):
        """The delta buffer in stream convention (invalid: V, V, -1), the
        shape the sharded bodies and packed relaxation concatenate onto
        their main slices. Fixed [delta_capacity] shape, so passing it on
        every call keeps one XLA trace across empty/non-empty deltas."""
        V = view.n_vertices
        return (
            jnp.where(view.delta_valid, view.delta_src, V),
            jnp.where(view.delta_valid, view.delta_dst, V),
            jnp.where(view.delta_valid, view.delta_eid, -1),
        )

    def _block_for(self, view: GraphView) -> int:
        """Effective COO block size for one view: the configured block,
        shrunk to the next power of two covering the actual edge stream.
        ``_blocked_coo`` pads the stream to a whole number of blocks, so a
        small graph under a large block sweeps mostly padding — at the
        benchmark quick sizes that alone was ~2x per-query overhead on the
        planned path versus a raw engine sized to the graph. Blocking does
        not affect results, only shapes (each (nb, block) pair jit-caches
        its own trace)."""
        n = view.n_slots + view.delta_capacity
        b = 1 << 10
        while b < n and b < self.block_size:
            b <<= 1
        return b

    # ------------------------------------------------------- backend policy
    def resolve_backend(
        self,
        view: GraphView,
        *,
        requested: Optional[str] = None,
        n_sources: int = 1,
        graph: Optional[str] = None,
    ) -> str:
        """Auto policy: device-count-aware frontier-density heuristic.

        Streams past the per-device slot threshold on a multi-device mesh
        take ``sharded`` (the whole point of partitioning is graphs that
        exceed one device); the fused MXU kernel amortizes its packed
        layout when the [S, V] sweep is dense — wide query batches over
        high-fan-out graphs — and only runs compiled on TPU (interpret
        mode elsewhere is a correctness tool, not a fast path).
        ``REPRO_TRAVERSAL_BACKEND`` overrides the auto choice.
        """
        b = requested or self.default_backend
        env = os.environ.get("REPRO_TRAVERSAL_BACKEND")
        if b == "auto" and env:
            b = env
        if b != "auto":
            if b not in BACKENDS:
                raise ValueError(f"unknown traversal backend {b!r}")
            return b
        if self.device_count() > 1:
            n_slots = view.n_slots + view.delta_capacity
            if n_slots >= self.shard_min_slots:
                return "sharded"
        if jax.default_backend() == "tpu":
            dense = self.fan_out(view, graph) >= 4.0 and n_sources >= 8
            if dense:
                return "pallas_frontier"
        return "xla_coo"

    # --------------------------------------------------------- failover
    def consume_degraded(self) -> Optional[str]:
        """The backend a fallback answered the LAST bfs/sssp call with
        (None when the resolved backend answered itself). Reading clears
        the flag — the executor threads it onto ``QueryResult`` per query."""
        d, self._last_degraded = self._last_degraded, None
        return d

    def _dispatch(self, resolved: str, run_one):
        """Run one traversal with bounded retry + backend failover.

        ``run_one(backend)`` executes the traversal on one specific
        backend. Each backend in ``(resolved,) + FAILOVER_CHAIN[resolved]``
        gets ``1 + backend_retries`` attempts; any exception (injected
        fault, device error, kernel bug) counts as a failed attempt and is
        recorded, never swallowed silently. Results are bit-identical
        across backends by construction, so a degraded query returns the
        same answer — ``_last_degraded`` and the event counters make the
        degradation observable. Only a failure of the whole chain
        (reference included) propagates.
        """
        self._last_degraded = None
        chain = (resolved,) + FAILOVER_CHAIN.get(resolved, ())
        last_err: Optional[BaseException] = None
        for i, b in enumerate(chain):
            for attempt in range(1 + self.backend_retries):
                try:
                    out = run_one(b)
                except Exception as e:  # noqa: BLE001 - degrade, don't die
                    last_err = self.last_error = e
                    self._stats["backend_faults"] += 1
                    self._stats[f"backend_fault_{b}"] += 1
                    self.events["traversal_faults"] += 1
                    if attempt < self.backend_retries:
                        self._stats["backend_retries"] += 1
                        self.events["traversal_retries"] += 1
                    continue
                self._stats[f"backend_{b}"] += 1
                if i > 0:
                    self._last_degraded = b
                    self._stats["backend_failovers"] += 1
                    self._stats[f"failover_{resolved}_to_{b}"] += 1
                    self.events["traversal_failovers"] += 1
                return out
            self.events["traversal_backend_exhausted"] += 1
        assert last_err is not None
        raise last_err

    # ------------------------------------------------------------------ BFS
    def bfs(
        self,
        view: GraphView,
        source_pos,
        edge_mask_by_row=None,
        vertex_mask=None,
        target_pos=None,
        *,
        max_hops: int = 32,
        backend: Optional[str] = None,
        graph: Optional[str] = None,
    ) -> jnp.ndarray:
        """Hop distances int32 [S, V]; -1 unreachable. Bit-identical across
        backends (targets only bound the sweep, identically everywhere);
        a failing backend degrades along ``FAILOVER_CHAIN`` rather than
        failing the query (see ``_dispatch``)."""
        source_pos = jnp.asarray(source_pos, jnp.int32)
        b = self.resolve_backend(
            view, requested=backend, n_sources=int(source_pos.shape[0]),
            graph=graph,
        )
        self._stats["queries_bfs"] += 1
        return self._dispatch(
            b,
            lambda bk: self._bfs_backend(
                bk, view, source_pos, edge_mask_by_row, vertex_mask,
                target_pos, max_hops=max_hops, graph=graph,
            ),
        )

    def _bfs_backend(
        self, b, view, source_pos, edge_mask_by_row, vertex_mask,
        target_pos, *, max_hops, graph,
    ) -> jnp.ndarray:
        """One BFS on one specific backend (the failover unit)."""
        faults.check(SITE_DISPATCH[b])
        if b == "xla_coo":
            dist, hops, sparse = _bfs_xla(
                view, source_pos, edge_mask_by_row, vertex_mask,
                target_pos, max_hops=max_hops, block_size=self._block_for(view),
            )
            self._hops = self._hops + hops
            self._sparse_hops = self._sparse_hops + sparse
            return dist
        if b == "pallas_frontier":
            ps, pe, ldst = self.get_pack(view, graph)
            vmask = view.v_valid if vertex_mask is None else (
                view.v_valid & vertex_mask
            )
            has_delta = bool(
                to_host(jnp.any(view.delta_valid), "delta_check", self.events)
            )
            return bfs_pallas(
                source_pos, ps, pe, ldst, view.n_vertices,
                edge_mask_by_row=edge_mask_by_row,
                vertex_mask=vmask, target_pos=target_pos,
                block_rows=self.block_rows, max_hops=max_hops,
                interpret=self.interpret,
                delta_src=view.delta_src if has_delta else None,
                delta_dst=view.delta_dst if has_delta else None,
                delta_eid=view.delta_eid if has_delta else None,
                delta_valid=view.delta_valid if has_delta else None,
            )
        if b == "sharded":
            ssrc, sdst, seid = self.get_shard_pack(view, graph)
            vmask = view.v_valid if vertex_mask is None else (
                view.v_valid & vertex_mask
            )
            dsrc, ddst, deid = self._delta_stream(view)
            return FS.sharded_bfs(
                ssrc, sdst, seid, source_pos, view.n_vertices,
                edge_mask_by_row=edge_mask_by_row,
                vertex_mask=vmask, target_pos=target_pos,
                max_hops=max_hops,
                delta_src=dsrc, delta_dst=ddst, delta_eid=deid,
            )
        return jnp.asarray(
            self._bfs_reference(
                view, source_pos, edge_mask_by_row, vertex_mask,
                target_pos, max_hops=max_hops,
            )
        )

    @staticmethod
    def _bfs_reference(
        view, source_pos, edge_mask_by_row, vertex_mask, target_pos,
        *, max_hops,
    ) -> np.ndarray:
        """Numpy oracle mirroring the XLA sweep's loop conditions exactly."""
        V = view.n_vertices
        src, dst, _ = _reference_edges(view, edge_mask_by_row)
        vmask = _reference_vmask(view, vertex_mask)
        sp = np.asarray(source_pos)
        S = sp.shape[0]
        frontier = np.zeros((S, V), bool)
        lanes = (sp >= 0) & (sp < V)
        frontier[np.arange(S)[lanes], sp[lanes]] = True
        frontier &= vmask[None, :]
        dist = np.where(frontier, 0, -1).astype(np.int32)
        visited = frontier.copy()
        tp = None if target_pos is None else np.asarray(target_pos)

        def targets_done(d):
            if tp is None:
                return False
            tc = np.clip(tp, 0, V - 1)
            found = d[np.arange(S), tc] >= 0
            found = found | (tp < 0) | (sp < 0)
            return bool(found.all())

        hop = 0
        while hop < max_hops and frontier.any() and not targets_done(dist):
            # one lane at a time: an [S, E] message array does not fit
            # host memory at Twitter scale, and 1-D fancy assignment is
            # far faster than a 2-D ufunc.at
            nxt = np.zeros((S, V), bool)
            for s in np.flatnonzero(frontier.any(axis=1)):
                nxt[s, dst[frontier[s, src]]] = True
            nxt &= ~visited & vmask[None, :]
            dist = np.where(nxt, hop + 1, dist).astype(np.int32)
            visited |= nxt
            frontier = nxt
            hop += 1
        return dist

    # ----------------------------------------------------------------- SSSP
    def sssp(
        self,
        view: GraphView,
        source_pos,
        weight_by_row,
        edge_mask_by_row=None,
        vertex_mask=None,
        *,
        backend: Optional[str] = None,
        graph: Optional[str] = None,
    ):
        """(dist f32 [S, V], parent_slot int32 [S, V]). Parents always come
        from the canonical blocked-COO parent pass, so equal distances give
        equal parents regardless of backend; a failing backend degrades
        along ``FAILOVER_CHAIN`` rather than failing the query.

        Every backend runs rounds until no distance falls (``T.round_bound``
        bounds them; the weights must be non-negative, which the engine
        checks where they enter its tables)."""
        source_pos = jnp.asarray(source_pos, jnp.int32)
        weight_by_row = jnp.asarray(weight_by_row, jnp.float32)
        b = self.resolve_backend(
            view, requested=backend, n_sources=int(source_pos.shape[0]),
            graph=graph,
        )
        self._stats["queries_sssp"] += 1
        return self._dispatch(
            b,
            lambda bk: self._sssp_backend(
                bk, view, source_pos, weight_by_row, edge_mask_by_row,
                vertex_mask, graph=graph,
            ),
        )

    def _sssp_backend(
        self, b, view, source_pos, weight_by_row, edge_mask_by_row,
        vertex_mask, *, graph,
    ):
        """One SSSP on one specific backend (the failover unit)."""
        faults.check(SITE_DISPATCH[b])
        if b == "xla_coo":
            dist, parent, rounds, sparse, n_vert, n_slot = _sssp_xla(
                view, source_pos, weight_by_row, edge_mask_by_row,
                vertex_mask, block_size=self._block_for(view),
            )
            self._sssp_rounds = self._sssp_rounds + rounds
            self._sssp_sparse_rounds = self._sssp_sparse_rounds + sparse
            self._sssp_reached = _tally(self._sssp_reached, jnp.stack([n_vert, n_slot]))
            return dist, parent
        if b == "pallas_frontier":
            dist = self._sssp_packed_dist(
                view, source_pos, weight_by_row, edge_mask_by_row,
                vertex_mask, graph=graph,
            )
        elif b == "sharded":
            ssrc, sdst, seid = self.get_shard_pack(view, graph)
            vmask = view.v_valid if vertex_mask is None else (
                view.v_valid & vertex_mask
            )
            dsrc, ddst, deid = self._delta_stream(view)
            dist = FS.sharded_sssp_dist(
                ssrc, sdst, seid, source_pos, weight_by_row,
                view.n_vertices, edge_mask_by_row=edge_mask_by_row,
                vertex_mask=vmask,
                delta_src=dsrc, delta_dst=ddst, delta_eid=deid,
            )
        else:
            dist = jnp.asarray(
                self._sssp_reference_dist(
                    view, source_pos, weight_by_row, edge_mask_by_row,
                    vertex_mask,
                )
            )
        parent = T.sssp_parents(
            view, dist, source_pos, weight_by_row,
            edge_mask_by_row, block_size=self._block_for(view),
        )
        return dist, parent

    def _sssp_packed_dist(
        self, view, source_pos, weight_by_row, edge_mask_by_row,
        vertex_mask, *, graph,
    ):
        ps, pe, ldst = self.get_pack(view, graph)
        Tt, J, BE = ps.shape
        VP = Tt * self.block_rows
        V = view.n_vertices
        ecap = weight_by_row.shape[0]
        ok = pe >= 0
        if edge_mask_by_row is not None:
            ok = ok & jnp.take(
                edge_mask_by_row, jnp.clip(pe, 0, ecap - 1)
            )
        w = jnp.where(ok, jnp.take(weight_by_row, jnp.clip(pe, 0, ecap - 1)), _INF)
        gdst = (
            jnp.arange(Tt, dtype=jnp.int32)[:, None, None] * self.block_rows + ldst
        )
        gdst = jnp.where(ldst >= 0, gdst, VP).reshape(-1)
        src_safe = jnp.clip(ps, 0, VP - 1).reshape(-1)
        w = w.reshape(-1)
        # delta candidates ride along flat (pack covers MAIN only); the
        # fixpoint min runs over the same edge multiset as all_coo, so
        # distances stay bit-identical to the blocked-COO sweep
        dsrc, ddst, deid = self._delta_stream(view)
        d_ok = deid >= 0
        if edge_mask_by_row is not None:
            d_ok = d_ok & jnp.take(
                edge_mask_by_row, jnp.clip(deid, 0, ecap - 1)
            )
        d_w = jnp.where(
            d_ok, jnp.take(weight_by_row, jnp.clip(deid, 0, ecap - 1)), _INF
        )
        src_safe = jnp.concatenate([src_safe, jnp.clip(dsrc, 0, VP - 1)])
        gdst = jnp.concatenate([gdst, jnp.where(d_ok, ddst, VP)])
        w = jnp.concatenate([w, d_w])
        vmask = view.v_valid if vertex_mask is None else (
            view.v_valid & vertex_mask
        )
        vmask_p = jnp.pad(vmask, (0, VP - V), constant_values=False)
        S = source_pos.shape[0]
        dist0 = jnp.full((S, VP), _INF)
        dist0 = dist0.at[
            jnp.arange(S), jnp.where(source_pos >= 0, source_pos, VP)
        ].set(0.0, mode="drop")
        dist0 = jnp.where(vmask_p[None, :], dist0, _INF)
        dist = _packed_sssp_dist(
            dist0, src_safe, gdst, w, vmask_p,
            jnp.int32(T.round_bound(V)),
        )
        return dist[:, :V]

    @staticmethod
    def _sssp_reference_dist(
        view, source_pos, weight_by_row, edge_mask_by_row, vertex_mask,
    ) -> np.ndarray:
        """Numpy float32 Bellman-Ford to fixpoint (Jacobi sweeps, at most
        ``T.round_bound``)."""
        V = view.n_vertices
        src, dst, eid = _reference_edges(view, edge_mask_by_row)
        w_rows = np.asarray(weight_by_row, np.float32)
        w = w_rows[np.clip(eid, 0, w_rows.shape[0] - 1)].astype(np.float32)
        vmask = _reference_vmask(view, vertex_mask)
        sp = np.asarray(source_pos)
        S = sp.shape[0]
        dist = np.full((S, V), np.inf, np.float32)
        lanes = (sp >= 0) & (sp < V)
        dist[np.arange(S)[lanes], sp[lanes]] = 0.0
        dist = np.where(vmask[None, :], dist, np.inf).astype(np.float32)
        for _ in range(T.round_bound(V)):
            new = dist.copy()
            for s in np.flatnonzero(np.isfinite(dist).any(axis=1)):
                # 1-D ufunc.at per lane (numpy's fast path)
                np.minimum.at(new[s], dst, (dist[s, src] + w).astype(np.float32))
            new = np.where(vmask[None, :], new, np.inf).astype(np.float32)
            if not (new < dist).any():
                break
            dist = new
        return dist

    # ------------------------------------------------------------- paths
    def reconstruct_paths(self, view, parent_slot, target_pos, *, max_len=32):
        return T.reconstruct_paths(
            view, parent_slot, target_pos,
            max_len=max_len, block_size=self._block_for(view),
        )

    def enumerate_paths(self, view, start_pos, **kwargs):
        """Bounded simple-path enumeration (single XLA implementation; the
        differential suite checks its counts against a numpy brute force)."""
        self._stats["queries_enum"] += 1
        return _enum_xla(view, start_pos, **kwargs)

    # -------------------------------------------------- batched admission
    def submit_reachability(
        self, view: GraphView, src_pos: int, dst_pos: int,
        *, graph: Optional[str] = None,
    ) -> PendingQuery:
        q = PendingQuery("reach", int(src_pos), int(dst_pos))
        self._pending.append((view, graph, q))
        return q

    def submit_sssp(
        self, view: GraphView, src_pos: int, dst_pos: int, weight_by_row,
        *, graph: Optional[str] = None,
    ) -> PendingQuery:
        """Weighted queries merge into one sweep only when they share the
        same ``weight_by_row`` array object — pass the table column itself,
        not a fresh copy per call."""
        q = PendingQuery("sssp", int(src_pos), int(dst_pos))
        self._pending_w.append((view, graph, weight_by_row, q))
        return q

    def _lanes(self, n: int, lane_width: Optional[int] = None) -> int:
        lanes = max(lane_width or self.lane_width, 1)
        while lanes < n:
            lanes <<= 1
        return lanes

    def _chunks(self, qs: list) -> list:
        return [qs[i : i + self.max_lanes] for i in range(0, len(qs), self.max_lanes)]

    def flush(
        self,
        *,
        max_hops: int = 16,
        edge_mask_by_row=None,
        backend: Optional[str] = None,
        lane_width: Optional[int] = None,
        handles: Optional[List[PendingQuery]] = None,
    ) -> List[PendingQuery]:
        """Merge admitted point queries into [S, V] sweeps (per view for
        reachability; per (view, weights) for weighted queries), each sweep
        at most ``max_lanes`` wide, and resolve their PendingQueries.

        ``handles`` restricts the flush to those specific queries — callers
        that share one TraversalEngine (e.g. several QueryServers) must pass
        their own handles so another caller's queries are never resolved
        with this caller's edge mask / hop budget / backend.
        """
        only = None if handles is None else {id(h) for h in handles}

        def _take(pending):
            if only is None:
                mine, rest = list(pending), []
            else:
                mine = [e for e in pending if id(e[-1]) in only]
                rest = [e for e in pending if id(e[-1]) not in only]
            pending.clear()
            pending.extend(rest)
            return mine

        done: List[PendingQuery] = []
        by_view: Dict[int, Tuple[GraphView, Optional[str], List[PendingQuery]]] = {}
        for view, graph, q in _take(self._pending):
            by_view.setdefault(id(view), (view, graph, []))[2].append(q)
        for view, graph, all_qs in by_view.values():
            for qs in self._chunks(all_qs):
                lanes = self._lanes(len(qs), lane_width)
                src = np.full(lanes, -1, np.int32)
                tgt = np.full(lanes, -1, np.int32)
                for i, q in enumerate(qs):
                    src[i], tgt[i] = q.source, q.target
                dist = self.bfs(
                    view, jnp.asarray(src), edge_mask_by_row=edge_mask_by_row,
                    target_pos=jnp.asarray(tgt), max_hops=max_hops,
                    backend=backend, graph=graph,
                )
                d = np.asarray(
                    jnp.take_along_axis(
                        dist,
                        jnp.clip(jnp.asarray(tgt), 0, view.n_vertices - 1)[:, None],
                        axis=1,
                    )[:, 0]
                )
                for i, q in enumerate(qs):
                    hops = int(d[i]) if q.source >= 0 and q.target >= 0 else -1
                    q.result = {"reachable": hops >= 0, "hops": hops}
                    done.append(q)
                self._stats["batches_flushed"] += 1

        by_view_w: Dict[tuple, tuple] = {}
        for view, graph, w, q in _take(self._pending_w):
            by_view_w.setdefault((id(view), id(w)), (view, graph, w, []))[3].append(q)
        for view, graph, w, all_qs in by_view_w.values():
            for qs in self._chunks(all_qs):
                lanes = self._lanes(len(qs), lane_width)
                src = np.full(lanes, -1, np.int32)
                tgt = np.full(lanes, -1, np.int32)
                for i, q in enumerate(qs):
                    src[i], tgt[i] = q.source, q.target
                dist, _ = self.sssp(
                    view, jnp.asarray(src), w,
                    edge_mask_by_row=edge_mask_by_row,
                    backend=backend, graph=graph,
                )
                d = np.asarray(
                    jnp.take_along_axis(
                        dist,
                        jnp.clip(jnp.asarray(tgt), 0, view.n_vertices - 1)[:, None],
                        axis=1,
                    )[:, 0]
                )
                for i, q in enumerate(qs):
                    ok = q.source >= 0 and q.target >= 0 and np.isfinite(d[i])
                    q.result = {
                        "reachable": bool(ok),
                        "distance": float(d[i]) if ok else float("inf"),
                    }
                    done.append(q)
                self._stats["batches_flushed"] += 1
        return done


# ---------------------------------------------------------------- reference
def count_paths_reference(
    view: GraphView,
    start_pos,
    *,
    min_len: int,
    max_len: int,
    close_loop: bool = False,
    edge_mask_by_row=None,
    vertex_mask=None,
) -> int:
    """Brute-force simple-path count with ``enumerate_paths`` semantics
    (interior vertices never revisited; the start vertex only on the
    closing hop of a loop query). Small graphs only — oracle use."""
    V = view.n_vertices
    src, dst, _ = _reference_edges(view, edge_mask_by_row)
    vmask = _reference_vmask(view, vertex_mask)
    adj: Dict[int, list] = {}
    for s, d in zip(src, dst):
        adj.setdefault(int(s), []).append(int(d))
    count = 0

    def rec(path):
        nonlocal count
        L = len(path) - 1
        if min_len <= L <= max_len:
            if not close_loop or (L == max_len and path[-1] == path[0]):
                count += 1
        if L == max_len:
            return
        for nb in adj.get(path[-1], ()):
            closing = close_loop and L == max_len - 1 and nb == path[0]
            if not vmask[nb]:
                continue
            if nb in path and not closing:
                continue
            if close_loop and not closing and L == max_len - 1:
                continue
            rec(path + [nb])

    for s in np.asarray(start_pos):
        s = int(s)
        if s >= 0 and s < V and vmask[s]:
            rec([s])
    return count
