"""Tree-walking physical executor for the cross-model plan IR (paper §5).

Each physical node produces/consumes ``RelBatch`` (the fixed-capacity
columnar batch both data models share), so relational operators and graph
operators compose in one tree:

  TableScanExec / VertexScanExec / EdgeScanExec   leaf scans + pushed filters
  HashJoinExec / CrossJoinExec                    relational combination
  PathScanExec                                    traversal; consumes anchor
                                                  lanes from its child and
                                                  dispatches bfs / bfs_path /
                                                  sssp / enum through the
                                                  TraversalEngine (§6.3)
  PathJoinExec                                    hash join of two PATHS
                                                  sources on endpoint vertex
                                                  ids (end-only / const-start
                                                  composition)
  PathDisjointExec                                cross-path vertex
                                                  disjointness (globally
                                                  simple paths)
  ResidualFilterExec / SortExec / LimitExec       post-combination shaping
  ProjectExec / AggregateExec                     root finalizers -> QueryResult

PATHS sources compose two ways: a scan start-anchored on a column of the
plan below *stacks* above it, its output rows gathering the lower plan's
columns through the origin lane (§5.3); anything else joins like a
relation through PathJoinExec — there is no structural asymmetry left
between graph and relational sources in the plan IR.
"""
from __future__ import annotations

from dataclasses import dataclass, field as dfield
from typing import Any, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import expr as X
from repro.core import operators as O
from repro.core import query as Q
from repro.core.logical import PathSpec, format_pathspec
from repro.core.logical import pretty as _tree_pretty
from repro.tracing import span, to_host


@dataclass
class QueryResult:
    columns: Dict[str, np.ndarray]
    count: int
    explain: List[str]
    overflow: bool = False
    # set when a traversal in this query was answered by a failover
    # backend (the name of the backend that answered) rather than the
    # one the planner resolved — results are still bit-identical, the
    # flag makes the degradation visible per query
    degraded_backend: Optional[str] = None

    def rows(self) -> List[dict]:
        return [
            {k: v[i] for k, v in self.columns.items()} for i in range(self.count)
        ]

    def scalar(self, name=None):
        name = name or next(iter(self.columns))
        v = self.columns[name]
        if np.ndim(v) == 0:
            return v
        if np.shape(v)[0] == 0 or self.count == 0:
            return None
        return v[0]


@dataclass
class ExecContext:
    engine: Any  # GRFusion
    plan: Any  # optimizer.PhysicalPlan
    runtime: Any = None  # compiled.PlanRuntime (epoch-keyed mask cache)
    params: Dict[str, Any] = dfield(default_factory=dict)  # bound Param values
    explain: List[str] = dfield(default_factory=list)
    overflow: bool = False
    degraded_backend: Optional[str] = None  # failover backend, if any

    def note_degraded(self, backend: Optional[str]) -> None:
        """Record a traversal failover (first one wins per execution)."""
        if backend is not None and self.degraded_backend is None:
            self.degraded_backend = backend

    def param(self, name):
        if name not in self.params:
            raise KeyError(
                f"unbound parameter {name!r}; call PreparedPlan.bind"
                f"({name}=...) before executing"
            )
        return self.params[name]


# --------------------------------------------------------------------------
# node base + tree printing
# --------------------------------------------------------------------------
class ExecNode:
    def children(self) -> list:
        return []

    def label(self) -> str:
        return type(self).__name__

    def run(self, ctx: ExecContext) -> O.RelBatch:
        raise NotImplementedError


def pretty(node: ExecNode, indent: int = 0) -> str:
    # same duck-typed children()/label() walk as the logical printer
    return _tree_pretty(node, indent)


# --------------------------------------------------------------------------
# scans
# --------------------------------------------------------------------------
def _apply_scan_filters(ctx, batch, source_table, alias, filters, *, epoch):
    """Pushed-down filters against one scan through the plan's compiled
    mask cache: the predicate conjunction compiles once into a fused
    column program, and its mask is reused until ``epoch`` (or a bound
    parameter feeding it) changes."""
    if not filters:
        return batch
    mask = ctx.runtime.mask(
        ("scan", alias), filters,
        table=source_table, epoch=epoch,
        resolve=lambda c: batch.col(f"{alias}.{c}"),
        base=batch.valid, params=ctx.params,
    )
    return batch.replace(valid=mask)


@dataclass
class _ScanExec(ExecNode):
    alias: str
    source: str  # table name (TableScan) or graph-view name (Vertex/Edge)
    filters: List[X.Expr]

    def label(self):
        f = f" [{len(self.filters)} pushed filter(s)]" if self.filters else ""
        return f"{type(self).__name__}({self.source} AS {self.alias}){f}"

    def cache_site_keys(self):
        """Call-site keys this node caches under on ``PlanRuntime`` (the
        plan verifier checks they are stable and plan-unique)."""
        return (("scan", self.alias),) if self.filters else ()


class TableScanExec(_ScanExec):
    def run(self, ctx):
        b = O.table_scan(ctx.engine.tables[self.source], prefix=self.alias + ".")
        return _apply_scan_filters(
            ctx, b, self.source, self.alias, self.filters,
            epoch=ctx.engine.table_epoch(self.source),
        )


class VertexScanExec(_ScanExec):
    def run(self, ctx):
        vb = ctx.engine.views[self.source]
        b = O.vertex_scan(
            vb.view, ctx.engine.tables[vb.vertex_table], prefix=self.alias + "."
        )
        # fanin/fanout/_pos columns come from the view, so the mask depends
        # on the topology epoch as well as the table epoch
        return _apply_scan_filters(
            ctx, b, vb.vertex_table, self.alias, self.filters,
            epoch=(
                ctx.engine.table_epoch(vb.vertex_table),
                ctx.engine.graph_epoch(self.source),
            ),
        )


class EdgeScanExec(_ScanExec):
    def run(self, ctx):
        vb = ctx.engine.views[self.source]
        b = O.edge_scan(
            vb.view, ctx.engine.tables[vb.edge_table], prefix=self.alias + "."
        )
        return _apply_scan_filters(
            ctx, b, vb.edge_table, self.alias, self.filters,
            epoch=ctx.engine.table_epoch(vb.edge_table),
        )


# --------------------------------------------------------------------------
# joins
# --------------------------------------------------------------------------
@dataclass
class HashJoinExec(ExecNode):
    left: ExecNode
    right: ExecNode
    left_key: str
    right_key: str
    # output capacity from the cost-based join-ordering rule; None keeps
    # the operator default (left batch capacity)
    capacity: Optional[int] = None

    def children(self):
        return [self.left, self.right]

    def label(self):
        cap = f", cap={self.capacity}" if self.capacity else ""
        return f"HashJoinExec({self.left_key} == {self.right_key}{cap})"

    def run(self, ctx):
        lb = self.left.run(ctx)
        rb = self.right.run(ctx)
        joined, ovf = O.join(
            lb, rb, self.left_key, self.right_key, capacity=self.capacity
        )
        ctx.overflow = ctx.overflow or bool(
            to_host(ovf, "join_overflow", ctx.engine.events)
        )
        return joined


@dataclass
class CrossJoinExec(ExecNode):
    left: ExecNode
    right: ExecNode
    right_alias: str
    capacity: Optional[int] = None

    def children(self):
        return [self.left, self.right]

    def label(self):
        return f"CrossJoinExec(+{self.right_alias}, bounded)"

    def run(self, ctx):
        lb = self.left.run(ctx)
        rb = self.right.run(ctx)
        joined, ovf = O.cross_join(lb, rb, capacity=self.capacity)
        ctx.overflow = ctx.overflow or bool(
            to_host(ovf, "join_overflow", ctx.engine.events)
        )
        ctx.explain.append(f"cross join with {self.right_alias} (bounded)")
        return joined


def _epoch_signature(ctx, node) -> tuple:
    """Catalog epochs of every table/graph a subtree reads. Executor nodes
    are deterministic functions of (catalog state, bound params), so this
    signature plus the param values keys caches of their outputs."""
    sig = []
    stack = [node]
    eng = ctx.engine
    while stack:
        n = stack.pop()
        if isinstance(n, TableScanExec):
            sig.append(("t", n.source, eng.table_epoch(n.source)))
        elif isinstance(n, (VertexScanExec, EdgeScanExec)):
            vb = eng.views[n.source]
            sig.append(("t", vb.vertex_table, eng.table_epoch(vb.vertex_table)))
            sig.append(("t", vb.edge_table, eng.table_epoch(vb.edge_table)))
            sig.append(("g", n.source, eng.graph_epoch(n.source)))
        elif isinstance(n, PathScanExec):
            vb = eng.views[n.spec.graph]
            sig.append(("t", vb.vertex_table, eng.table_epoch(vb.vertex_table)))
            sig.append(("t", vb.edge_table, eng.table_epoch(vb.edge_table)))
            sig.append(("g", n.spec.graph, eng.graph_epoch(n.spec.graph)))
        stack.extend(n.children())
    return tuple(sorted(sig))


def _params_key(ctx) -> tuple:
    return tuple(sorted(ctx.params.items()))


def _cached_observed(ctx, key, epoch, build):
    """Epoch-keyed value caching for nodes that observe side channels
    while building — the overflow flag and explain lines. Both are
    captured alongside the value and replayed on cache hits, so cache
    warmth never changes what a query reports. Every caching exec node
    (PathScan anchor children, PathJoin joined batches) must go through
    this single implementation of that contract."""

    def build_observed():
        saved, ctx.overflow = ctx.overflow, False
        n0 = len(ctx.explain)
        value = build()
        ovf, ctx.overflow = ctx.overflow, saved
        lines = ctx.explain[n0:]
        del ctx.explain[n0:]
        return value, ovf, lines

    value, ovf, lines = ctx.runtime.cached(key, epoch, build_observed)
    ctx.overflow = ctx.overflow or ovf
    ctx.explain.extend(lines)
    return value


# --------------------------------------------------------------------------
# PathScan — the graph operator inside the relational tree
# --------------------------------------------------------------------------
@dataclass
class PathScanExec(ExecNode):
    spec: PathSpec
    child: Optional[ExecNode] = None

    def children(self):
        return [self.child] if self.child is not None else []

    def label(self):
        return f"PathScanExec({format_pathspec(self.spec)})"

    def cache_site_keys(self):
        """Base call-site keys for every PlanRuntime cache this node
        touches: vertex/edge masks extend ``("path", alias, ...)``, the
        prepared-anchor bundle lives under ``("prep", alias)``, the
        anchor-child batch under ``("child", alias)``. All derive from
        the FROM alias, so plan-wide key uniqueness (checked by the plan
        verifier) is exactly FROM-alias uniqueness."""
        a = self.spec.alias
        return (("path", a), ("prep", a), ("child", a))

    # -- compiled-mask access (epoch-keyed, cached on the plan) ------------
    def _vmask(self, ctx, vb, preds, kind):
        """Vertex-attr predicate mask via the plan's compiled-mask cache."""
        vt = ctx.engine.tables[vb.vertex_table]
        return ctx.runtime.mask(
            ("path", self.spec.alias, "v", kind), preds,
            table=vb.vertex_table,
            epoch=ctx.engine.table_epoch(vb.vertex_table),
            resolve=vt.col, base=vt.valid, colmap=vb.v_attrs,
            params=ctx.params,
        )

    def _emask(self, ctx, vb, preds, kind):
        et = ctx.engine.tables[vb.edge_table]
        return ctx.runtime.mask(
            ("path", self.spec.alias, "e", kind), preds,
            table=vb.edge_table,
            epoch=ctx.engine.table_epoch(vb.edge_table),
            resolve=et.col, base=et.valid, colmap=vb.e_attrs,
            params=ctx.params,
        )

    def _anchor_id(self, ctx, anchor):
        """Anchor value for const/param anchors (param resolves at bind)."""
        return anchor[1] if anchor[0] == "const" else ctx.param(anchor[1])

    def _child_batch(self, ctx):
        """Anchor child's batch, cached by the child subtree's epoch
        signature (its output is deterministic in catalog state + params)
        with overflow/explain capture-and-replay (``_cached_observed``)."""
        if self.child is None:
            return None
        epoch = (_epoch_signature(ctx, self.child), _params_key(ctx))
        return _cached_observed(
            ctx, ("child", self.spec.alias), epoch,
            lambda: self.child.run(ctx),
        )

    # -- anchor / mask preparation (paper §6.2 pushdown) -------------------
    def _start_positions(self, ctx, vb, R):
        spec, view = self.spec, vb.view
        if spec.start_anchor and spec.start_anchor[0] == "col":
            assert R is not None, "column start anchor needs an anchor child"
            ids = R.col(spec.start_anchor[1]).astype(jnp.int32)
            pos, found = view.id_index.lookup(ids)
            pos = jnp.where(R.valid & found, pos, -1)
            return pos, "rel"
        if spec.start_anchor and spec.start_anchor[0] in ("const", "param"):
            pos, found = view.id_index.lookup(
                jnp.asarray([self._anchor_id(ctx, spec.start_anchor)], jnp.int32)
            )
            pos = jnp.where(found, pos, -1)
            # per-lane const start + COLUMN end anchors have mismatched
            # widths ([1] vs [child rows]); broadcast the const start to
            # one lane per child row so both anchors align lane-for-lane
            # (origin == arange, the same contract as a column start)
            if (
                R is not None
                and spec.end_anchor
                and spec.end_anchor[0] == "col"
            ):
                return jnp.broadcast_to(pos, (R.capacity,)), "rel"
            return pos, "const"
        # §5.1.2: undefined start set = all vertices
        return jnp.arange(view.n_vertices, dtype=jnp.int32), "all"

    def _end_anchor_mask(self, ctx, vb, R):
        """End anchor as (mask [V]) or per-lane targets [S]."""
        spec, view = self.spec, vb.view
        if spec.end_anchor is None and not spec.end_attr_preds:
            return None, None
        mask = self._vmask(ctx, vb, spec.end_attr_preds, "end_attr")
        targets = None
        if spec.end_anchor:
            if spec.end_anchor[0] in ("const", "param"):
                pos, found = view.id_index.lookup(
                    jnp.asarray(
                        [self._anchor_id(ctx, spec.end_anchor)], jnp.int32
                    )
                )
                m2 = jnp.zeros((view.n_vertices,), jnp.bool_).at[pos].set(
                    found, mode="drop"
                )
                mask = mask & m2
            else:  # per-lane targets from the anchor child
                assert R is not None, "column end anchor needs an anchor child"
                ids = R.col(spec.end_anchor[1]).astype(jnp.int32)
                pos, found = view.id_index.lookup(ids)
                targets = jnp.where(R.valid & found, pos, -1)
        return mask, targets

    def _hop_masks(self, ctx, vb):
        """Per-hop edge masks; each distinct predicate set compiles once and
        its mask is cached by edge-table epoch. Hops with no positional
        predicate share the single ``uniform`` mask object, which lets
        ``run()`` skip re-ANDing identical masks on the hot path."""
        spec = self.spec
        uniform_preds = [
            pred for lo, hi, pred in spec.hop_edge_preds
            if lo == 0 and hi is None
        ]
        uniform = self._emask(ctx, vb, uniform_preds, "uniform")
        masks = []
        for h in range(spec.max_len):
            preds_h = []
            for lo, hi, pred in spec.hop_edge_preds:
                if lo == 0 and hi is None:
                    continue
                hi_eff = spec.max_len - 1 if hi is None else hi
                if lo <= h <= hi_eff:
                    preds_h.append(pred)
            if preds_h:
                masks.append(
                    uniform & self._emask(ctx, vb, preds_h, ("hop", h))
                )
            else:
                masks.append(uniform)
        return masks

    def _prepare(self, ctx, vb, R):
        """Shared anchor/mask preparation for both run() and run_count().

        The whole tuple is deterministic given the catalog epochs the scan
        (and its anchor child) reads plus the bound parameters, so it is
        cached on the plan runtime: the serving hot path re-resolves
        anchors/masks only when something actually changed."""
        def build():
            spec = self.spec
            view = vb.view
            start_pos, start_kind = self._start_positions(ctx, vb, R)
            smask = self._vmask(ctx, vb, spec.start_attr_preds, "start_attr")
            sp_c = jnp.clip(start_pos, 0, view.n_vertices - 1)
            sp = jnp.where(
                (start_pos >= 0) & jnp.take(smask, sp_c), start_pos, -1
            )
            gvmask = self._vmask(ctx, vb, spec.global_vertex_preds, "global")
            hop_masks = self._hop_masks(ctx, vb)
            end_mask, targets = self._end_anchor_mask(ctx, vb, R)
            return sp, start_kind, sp_c, gvmask, hop_masks, end_mask, targets

        with span("grf.path.prepare"):
            epoch = (
                _epoch_signature(ctx, self),
                R is None,
                _params_key(ctx),
            )
            return ctx.runtime.cached(("prep", self.spec.alias), epoch, build)

    # -- execution ---------------------------------------------------------
    def run(self, ctx) -> O.RelBatch:
        spec = self.spec
        eng = ctx.engine
        R = self._child_batch(ctx)
        vb = eng.views[spec.graph]
        view = vb.view
        et = eng.tables[vb.edge_table]

        (start_pos, start_kind, sp_c, gvmask, hop_masks,
         end_mask, targets) = self._prepare(ctx, vb, R)
        # only used by bfs/sssp paths; max_len == 0 (pure 0-hop self-reach)
        # has no hop masks, so fall back to bare edge validity. Hops that
        # share the cached uniform mask object need no re-ANDing.
        uniform_mask = (
            hop_masks[0] if hop_masks else self._emask(ctx, vb, [], "validity")
        )
        for m in hop_masks[1:]:
            if m is not uniform_mask:
                uniform_mask = uniform_mask & m

        if spec.physical in ("bfs", "sssp", "bfs_path"):
            backend = eng.traversal.resolve_backend(
                view, requested=spec.backend,
                n_sources=int(start_pos.shape[0]), graph=spec.graph,
            )
            ctx.explain.append(f"traversal backend: {backend}")
        elif spec.backend is not None:
            ctx.explain.append(
                "traversal backend: request ignored (enumeration has a "
                "single implementation)"
            )

        a = spec.alias
        if spec.physical == "bfs":
            if targets is None and end_mask is not None:
                # single const target; an unresolvable id (all-False mask)
                # must yield -1, not argmax's position 0
                tpos = jnp.where(
                    jnp.any(end_mask), jnp.argmax(end_mask), -1
                ).astype(jnp.int32)
                targets = jnp.broadcast_to(tpos, start_pos.shape)
            with span("grf.traverse"):
                dist = eng.traversal.bfs(
                    view, start_pos,
                    edge_mask_by_row=uniform_mask, vertex_mask=gvmask,
                    target_pos=targets,
                    max_hops=min(spec.max_len, eng.bfs_max_hops),
                    backend=backend, graph=spec.graph,
                )
                ctx.note_degraded(eng.traversal.consume_degraded())
            with span("grf.path.to_batch"):
                tc = jnp.clip(targets, 0, view.n_vertices - 1)
                d = jnp.take_along_axis(dist, tc[:, None], axis=1)[:, 0]
                # validity: the lane must have live anchors on BOTH ends, and the
                # distance must clear the minimum — OR be a 0-hop self-reach when
                # min_len == 0. The grouping is load-bearing: without the inner
                # parentheses a 0-distance lane with a dead anchor leaks through.
                ok = (targets >= 0) & (start_pos >= 0) & (
                    (d >= spec.min_len) | ((d == 0) & (spec.min_len == 0))
                )
                ok = ok & (d >= 0)
                cols = {
                    f"{a}.length": d,
                    f"{a}.exists": (d >= 0) & (targets >= 0),
                    f"{a}.startvertexid": jnp.take(view.v_ids, sp_c),
                    f"{a}.endvertexid": jnp.take(view.v_ids, tc),
                    f"{a}._start_pos": start_pos,
                    f"{a}._end_pos": targets,
                    f"{a}._origin": jnp.arange(start_pos.shape[0], dtype=jnp.int32),
                }
                pbatch = O.RelBatch(cols=cols, valid=ok)
        elif spec.physical in ("sssp", "bfs_path"):
            if spec.physical == "sssp":
                wcol = vb.e_attrs.get(spec.sp_weight_attr, spec.sp_weight_attr)
                if wcol in eng.negative_columns.get(vb.edge_table, ()):
                    raise ValueError(
                        f"shortest-path weight {vb.edge_table}.{wcol} holds a "
                        "negative value; shortest paths need non-negative weights"
                    )
                w = et.col(wcol).astype(jnp.float32)
            else:
                w = jnp.ones((et.capacity,), jnp.float32)
            with span("grf.traverse"):
                dist, parent = eng.traversal.sssp(
                    view, start_pos, w,
                    edge_mask_by_row=uniform_mask, vertex_mask=gvmask,
                    backend=backend, graph=spec.graph,
                )
                ctx.note_degraded(eng.traversal.consume_degraded())
            with span("grf.path.to_batch"):
                if targets is None and end_mask is not None and spec.end_anchor:
                    tpos = jnp.where(
                        jnp.any(end_mask), jnp.argmax(end_mask), -1
                    ).astype(jnp.int32)
                    targets = jnp.broadcast_to(tpos, start_pos.shape)
                if targets is not None:
                    tc = jnp.clip(targets, 0, view.n_vertices - 1)
                    d = jnp.take_along_axis(dist, tc[:, None], axis=1)[:, 0]
                    edges, verts, lens = eng.traversal.reconstruct_paths(
                        view, parent, jnp.where(targets >= 0, targets, 0),
                        max_len=min(max(spec.max_len, 8), 64),
                    )
                    ok = (targets >= 0) & (start_pos >= 0) & jnp.isfinite(d)
                    cols = {
                        f"{a}.length": lens,
                        f"{a}.distance": d,
                        f"{a}.startvertexid": jnp.take(view.v_ids, sp_c),
                        f"{a}.endvertexid": jnp.take(view.v_ids, tc),
                        f"{a}._edges": edges,
                        f"{a}._verts": verts,
                        f"{a}._start_pos": start_pos,
                        f"{a}._end_pos": targets,
                        f"{a}._origin": jnp.arange(start_pos.shape[0], dtype=jnp.int32),
                    }
                    pbatch = O.RelBatch(cols=cols, valid=ok)
                else:
                    # single-source, all destinations (Grail comparison shape)
                    d0 = dist[0]
                    ok = jnp.isfinite(d0) & view.v_valid
                    cols = {
                        f"{a}.distance": d0,
                        f"{a}.endvertexid": view.v_ids,
                        f"{a}.startvertexid": jnp.broadcast_to(
                            jnp.take(view.v_ids, sp_c[0]), (view.n_vertices,)
                        ),
                        f"{a}._end_pos": jnp.arange(view.n_vertices, dtype=jnp.int32),
                        f"{a}._origin": jnp.zeros((view.n_vertices,), jnp.int32),
                    }
                    pbatch = O.RelBatch(cols=cols, valid=ok)
        else:  # enumeration
            with span("grf.traverse"):
                ps = self._enumerate(ctx, vb, R, start_pos, end_mask, targets,
                                     gvmask, hop_masks, count_only=False)
                ctx.overflow = ctx.overflow or bool(
                    to_host(ps.overflow, "enum_overflow", eng.events)
                )
            # view/vb may have been compacted inside _enumerate
            vb = eng.views[spec.graph]
            view = vb.view
            with span("grf.path.to_batch"):
                any_names = [f"any_{i}" for i in range(len(spec.any_edge_preds))]
                pbatch = O.paths_to_batch(
                    ps, view, prefix=a + ".",
                    agg_names=[f"sum_{x}" for x in spec.agg_attrs],
                    any_names=any_names,
                )
                for an in any_names:  # ANY semantics: at least one edge passes
                    pbatch = pbatch.replace(
                        valid=pbatch.valid & pbatch.col(f"{a}.{an}")
                    )
                if targets is not None:
                    tgt_of_origin = jnp.take(
                        targets, jnp.clip(ps.origin, 0, targets.shape[0] - 1)
                    )
                    pbatch = pbatch.replace(
                        valid=pbatch.valid
                        & (pbatch.col(f"{a}._end_pos") == tgt_of_origin)
                    )

        if R is None:
            return pbatch
        # combine with the anchor child via the origin lane (§5.3). The
        # bfs/sssp target branches emit one output lane per child row with
        # origin == arange, so the gather is the identity there: merge the
        # child's columns directly instead of re-gathering every column.
        with span("grf.path.to_batch"):
            identity_origin = (
                start_kind == "rel"
                and spec.physical in ("bfs", "sssp", "bfs_path")
                and targets is not None
            )
            if identity_origin:
                cols = dict(pbatch.cols)
                cols.update(R.cols)
                return O.RelBatch(cols=cols, valid=pbatch.valid & R.valid)
            org = pbatch.col(f"{a}._origin")
            oc = jnp.clip(org, 0, R.capacity - 1)
            cols = dict(pbatch.cols)
            for k, v in R.cols.items():
                cols[k] = jnp.take(v, oc, axis=0)
            rv = (
                jnp.take(R.valid, oc)
                if start_kind == "rel"
                else jnp.ones_like(pbatch.valid)
            )
            return O.RelBatch(cols=cols, valid=pbatch.valid & rv)

    def run_count(self, ctx):
        """COUNT(*)-fused traversal (aggregate-pushdown rule): no PathSet
        materialization, returns (count, overflow)."""
        spec = self.spec
        vb = ctx.engine.views[spec.graph]
        start_pos, _, _, gvmask, hop_masks, _, _ = self._prepare(ctx, vb, None)
        if spec.backend is not None:
            ctx.explain.append(
                "traversal backend: request ignored (enumeration has a "
                "single implementation)"
            )
        with span("grf.traverse"):
            return self._enumerate(ctx, vb, None, start_pos, None, None,
                                   gvmask, hop_masks, count_only=True)

    def _enumerate(self, ctx, vb, R, start_pos, end_mask, targets, gvmask,
                   hop_masks, *, count_only):
        from repro.core import optimizer as OPT

        spec = self.spec
        eng = ctx.engine
        view = vb.view
        n_src = int(start_pos.shape[0])
        wcap = OPT.choose_work_capacity(
            spec, eng.traversal.fan_out(view, spec.graph), n_src,
            ctx.plan.query.bf_hint, max_cap=eng.max_work_capacity,
        )
        ctx.explain.append(f"enum work capacity: {wcap}")
        if to_host(jnp.any(view.delta_valid), "delta_check", eng.events):
            eng.compact(spec.graph)
            vb = eng.views[spec.graph]
            view = vb.view
        et = eng.tables[vb.edge_table]
        agg_w = None
        agg_b = None
        if spec.agg_attrs:
            agg_w = jnp.stack(
                [
                    et.col(vb.e_attrs.get(x, x)).astype(jnp.float32)
                    for x in spec.agg_attrs
                ]
            )
            if spec.agg_upper_bounds:
                agg_b = jnp.asarray(
                    [spec.agg_upper_bounds.get(x, np.inf) for x in spec.agg_attrs],
                    jnp.float32,
                )
        any_m = None
        if spec.any_edge_preds:
            any_m = jnp.stack(
                [
                    self._emask(ctx, vb, [p], ("any", i))
                    for i, p in enumerate(spec.any_edge_preds)
                ]
            )
        return eng.traversal.enumerate_paths(
            view, start_pos,
            min_len=spec.min_len, max_len=spec.max_len,
            hop_edge_masks=hop_masks,
            vertex_mask=gvmask,
            end_anchor=end_mask if targets is None else None,
            close_loop=spec.close_loop,
            agg_weights=agg_w, agg_upper_bounds=agg_b,
            any_masks=any_m,
            work_capacity=wcap,
            result_capacity=eng.result_capacity,
            count_only=count_only,
        )


# --------------------------------------------------------------------------
# PathJoin — two PATHS sources joining like relations (lifts the
# stacked-PATHS restrictions)
# --------------------------------------------------------------------------
@dataclass
class PathJoinExec(ExecNode):
    """Hash join of two path-producing subtrees on endpoint vertex ids.

    The seeded stack (PathScan over PathScan) requires the upper path to
    be start-anchored on a column of the plan below; this node is the
    symmetric alternative for the cases that cannot seed (end-only and
    const-start cross references): both sides execute independently and
    their output batches join on the ``{alias}.{which}vertexid`` lanes
    named by ``on`` — the same sort + binary-search + fanout-expansion
    join relational inputs use, so a path set is just another relation.

    ``build`` picks the sorted (build) side from the optimizer's
    traversal-cardinality estimates, and ``capacity`` sizes the output
    batch from the join estimate (never below the probe side's capacity,
    so estimates can only widen the join; overflow is detected and
    reported on the QueryResult). The whole joined batch is cached on the
    plan's ``PlanRuntime`` keyed by the subtree's catalog-epoch signature
    plus bound params — a warm prepared plan replays the join output
    without recompiling or even re-running the traversals, and replays
    the overflow/explain observations so cache warmth never changes what
    a query reports."""

    left: ExecNode
    right: ExecNode
    # [((left_alias, which), (right_alias, which)), ...]; first pair is
    # the hash key, the rest post-join equality filters
    on: List[tuple] = dfield(default_factory=list)
    capacity: Optional[int] = None
    build: str = "right"

    def children(self):
        return [self.left, self.right]

    def label(self):
        conds = " and ".join(
            f"{la}.{lw} == {ra}.{rw}" for (la, lw), (ra, rw) in self.on
        )
        cap = f", cap={self.capacity}" if self.capacity else ""
        return f"PathJoinExec({conds}, build={self.build}{cap})"

    @staticmethod
    def _key_col(alias: str, which: str) -> str:
        return f"{alias}.{which}vertexid"

    def cache_site_keys(self):
        """The joined-batch cache key: the full ``on`` condition list, so
        two PathJoins in one plan collide only if they join the same
        aliases on the same endpoints (which the verifier rejects)."""
        return (
            ("pathjoin",) + tuple(
                (la, lw, ra, rw) for (la, lw), (ra, rw) in self.on
            ),
        )

    def run(self, ctx) -> O.RelBatch:
        epoch = (_epoch_signature(ctx, self), _params_key(ctx))
        (key,) = self.cache_site_keys()
        return _cached_observed(ctx, key, epoch, lambda: self._join(ctx))

    def _join(self, ctx) -> O.RelBatch:
        lb = self.left.run(ctx)
        rb = self.right.run(ctx)
        (la, lw), (ra, rw) = self.on[0]
        lkey, rkey = self._key_col(la, lw), self._key_col(ra, rw)
        # estimates may widen the join output, never starve it below the
        # probe side's width (the PR 3 overflow contract)
        if self.build == "left":
            cap = max(self.capacity or 0, rb.capacity)
            joined, ovf = O.join(rb, lb, rkey, lkey, capacity=cap)
        else:
            cap = max(self.capacity or 0, lb.capacity)
            joined, ovf = O.join(lb, rb, lkey, rkey, capacity=cap)
        valid = joined.valid
        for (la2, lw2), (ra2, rw2) in self.on[1:]:
            valid = valid & (
                joined.col(self._key_col(la2, lw2))
                == joined.col(self._key_col(ra2, rw2))
            )
        ctx.overflow = ctx.overflow or bool(
            to_host(ovf, "join_overflow", ctx.engine.events)
        )
        ctx.explain.append(
            f"path join: {lkey} == {rkey} (build={self.build})"
        )
        return joined.replace(valid=valid)


@dataclass
class PathDisjointExec(ExecNode):
    """Cross-path vertex-disjointness filter (globally simple paths).

    For each alias pair ``(a, b, allowed)`` the combined batch row
    survives only if the two paths' materialized vertex lists share
    exactly ``allowed`` *distinct* vertices — the junction endpoints that
    the composition's equalities entitle them to — and nothing else.
    Counting distinct shared values (not occurrence pairs) matters for
    ``close_loop`` paths: a loop legitimately repeats exactly its junction
    vertex (start == end), which is still ONE shared vertex of the
    composition, not two. Vertex positions map to external ids per path
    (each path may traverse a different graph view), padding lanes (-1)
    never match."""

    child: ExecNode
    pairs: List[tuple] = dfield(default_factory=list)

    def children(self):
        return [self.child]

    def label(self):
        parts = ", ".join(f"{a}&{b} (allow {n})" for a, b, n in self.pairs)
        return f"PathDisjointExec({parts})"

    def _vert_ids(self, ctx, batch, alias):
        col = f"{alias}._verts"
        if col not in batch.cols:
            raise NotImplementedError(
                f"globally simple paths need materialized vertices for "
                f"'{alias}' (physical "
                f"{ctx.plan.specs[alias].physical!r} does not produce them)"
            )
        verts = batch.col(col)
        view = ctx.engine.views[ctx.plan.specs[alias].graph].view
        ids = jnp.take(view.v_ids, jnp.clip(verts, 0, view.n_vertices - 1))
        return jnp.where(verts >= 0, ids, -1)

    def run(self, ctx) -> O.RelBatch:
        batch = self.child.run(ctx)
        valid = batch.valid
        for a, b, allowed in self.pairs:
            ia = self._vert_ids(ctx, batch, a)
            ib = self._vert_ids(ctx, batch, b)
            # first occurrence of each vertex value within a's lane, so a
            # value repeated inside one path (close_loop junction) counts
            # once: shared = |values(a) & values(b)|, not occurrence pairs
            earlier = jnp.tril(
                jnp.ones((ia.shape[1], ia.shape[1]), jnp.bool_), k=-1
            )
            dup = jnp.any(
                (ia[:, :, None] == ia[:, None, :]) & earlier[None], axis=2
            )
            first = (ia >= 0) & ~dup
            in_b = jnp.any(
                (ia[:, :, None] == ib[:, None, :]) & (ib >= 0)[:, None, :],
                axis=2,
            )
            shared = jnp.sum((first & in_b).astype(jnp.int32), axis=1)
            valid = valid & (shared == allowed)
        return batch.replace(valid=valid)


# --------------------------------------------------------------------------
# post-combination shaping
# --------------------------------------------------------------------------
@dataclass
class ResidualFilterExec(ExecNode):
    child: ExecNode
    predicates: List[X.Expr]

    def children(self):
        return [self.child]

    def label(self):
        return f"ResidualFilterExec({len(self.predicates)} predicate(s))"

    def run(self, ctx):
        batch = self.child.run(ctx)
        for res in self.predicates:
            mask = eval_on_batch(ctx, res, batch)
            batch = batch.replace(valid=batch.valid & mask)
        return batch


@dataclass
class SortExec(ExecNode):
    child: ExecNode
    key: str
    descending: bool

    def children(self):
        return [self.child]

    def label(self):
        return f"SortExec({self.key}{' DESC' if self.descending else ''})"

    def run(self, ctx):
        return O.order_by(self.child.run(ctx), self.key, descending=self.descending)


@dataclass
class LimitExec(ExecNode):
    child: ExecNode
    n: int

    def children(self):
        return [self.child]

    def label(self):
        return f"LimitExec({self.n})"

    def run(self, ctx):
        return O.limit(self.child.run(ctx), self.n)


# --------------------------------------------------------------------------
# root finalizers
# --------------------------------------------------------------------------
@dataclass
class ProjectExec(ExecNode):
    child: ExecNode
    select_list: Dict[str, Any]

    def children(self):
        return [self.child]

    def label(self):
        names = ", ".join(self.select_list) if self.select_list else "*"
        return f"ProjectExec({names})"

    def finalize(self, ctx) -> QueryResult:  # lint: allow-host-sync
        # result assembly: the query is over, moving the surviving rows
        # to host numpy here is the point of the method
        combined = self.child.run(ctx)
        with span("grf.finalize"):
            sel = self.select_list
            if not sel:
                keep = [k for k in combined.cols if not k.split(".")[-1].startswith("_")]
                sel = {k: X.Col(k) for k in keep}
            out_cols = {}
            decode_info = {}
            for out_name, e in sel.items():
                vals, dec = eval_on_batch(ctx, e, combined, want_decode=True)
                out_cols[out_name] = vals
                decode_info[out_name] = dec

            validm, *vals = to_host(
                [combined.valid, *out_cols.values()], "finalize",
                ctx.engine.events,
            )
            order = np.argsort(~validm, kind="stable")  # valid rows first
            n = int(validm.sum())
            final = {}
            for k, v in zip(out_cols, vals):  # in SELECT order
                arr = np.asarray(v)[order][:n] if np.ndim(v) else np.asarray(v)
                dec = decode_info.get(k)
                if dec is not None and np.ndim(arr):
                    arr = ctx.engine.decode_column(dec[0], dec[1], arr)
                final[k] = arr
            return QueryResult(
                columns=final, count=n, explain=ctx.explain, overflow=ctx.overflow,
                degraded_backend=ctx.degraded_backend,
            )


@dataclass
class AggregateExec(ExecNode):
    child: ExecNode
    agg_select: Dict[str, tuple]

    def children(self):
        return [self.child]

    def label(self):
        parts = ", ".join(f"{k}={op}" for k, (op, _) in self.agg_select.items())
        return f"AggregateExec({parts})"

    def finalize(self, ctx) -> QueryResult:  # lint: allow-host-sync
        # result assembly: scalar aggregates land on host by design
        if isinstance(self.child, PathScanExec) and self.child.spec.count_only:
            cnt, ovf = self.child.run_count(ctx)
            with span("grf.finalize"):
                cnt, ovf = to_host((cnt, ovf), "finalize", ctx.engine.events)
                cols = {name: np.asarray(cnt) for name in self.agg_select}
                return QueryResult(
                    columns=cols, count=1, explain=ctx.explain,
                    overflow=ctx.overflow or bool(ovf),
                    degraded_backend=ctx.degraded_backend,
                )
        combined = self.child.run(ctx)
        with span("grf.finalize"):
            aggs = {}
            for name, (op, e) in self.agg_select.items():
                if op == "count":
                    aggs[name] = jnp.sum(combined.valid.astype(jnp.int32))
                    continue
                vals = eval_on_batch(ctx, e, combined)
                v = combined.valid
                if op == "sum":
                    aggs[name] = jnp.sum(jnp.where(v, vals, 0))
                elif op == "min":
                    aggs[name] = jnp.min(jnp.where(v, vals, jnp.inf))
                elif op == "max":
                    aggs[name] = jnp.max(jnp.where(v, vals, -jnp.inf))
            vals = to_host(list(aggs.values()), "finalize", ctx.engine.events)
            return QueryResult(
                columns=dict(zip(aggs, vals)), count=1, explain=ctx.explain, overflow=ctx.overflow,
                degraded_backend=ctx.degraded_backend,
            )


# --------------------------------------------------------------------------
# combined-batch expression evaluation (relational + path columns)
# --------------------------------------------------------------------------
def _alias_table(ctx, alias):
    for f in ctx.plan.query.froms:
        if f.alias == alias:
            if f.kind == "table":
                return f.name
            vb = ctx.engine.views.get(f.name)
            if vb:
                return vb.vertex_table if f.kind == "vertexes" else vb.edge_table
    return None


def _enc_for(ctx, node, value):
    if isinstance(node, X.Col) and "." in node.name:
        alias, cname = node.name.split(".", 1)
        tn = _alias_table(ctx, alias)
        if tn:
            return ctx.engine.encode_value(tn, cname, value)
    if isinstance(node, Q.PathVertexAttr):
        return value  # handled in resolve via dictionaries at decode
    return value


def eval_on_batch(ctx, e, batch: O.RelBatch, want_decode=False):
    """Evaluate an expression against a combined batch; PathExpr nodes
    resolve through their own alias's PathSpec (multi-PATHS aware)."""
    eng = ctx.engine
    decode = [None]

    def resolve_pathexpr(pe):
        a = pe.alias
        spec = ctx.plan.specs[a]
        vb = eng.views[spec.graph]
        if isinstance(pe, Q.PathLength):
            return batch.col(f"{a}.length")
        if isinstance(pe, Q.PathAgg):
            return batch.col(f"{a}.sum_{pe.attr}")
        if isinstance(pe, Q.PathVertexAttr):
            pos = batch.col(f"{a}._{pe.which}_pos")
            vt = eng.tables[vb.vertex_table]
            if pe.attr == "id":
                return jnp.take(
                    vb.view.v_ids, jnp.clip(pos, 0, vb.view.n_vertices - 1)
                )
            srccol = vb.v_attrs.get(pe.attr, pe.attr)
            decode[0] = (vb.vertex_table, srccol)
            return jnp.take(vt.col(srccol), jnp.clip(pos, 0, vt.capacity - 1))
        if isinstance(pe, Q.PathString):
            return batch.col(f"{a}._verts")  # decoded by caller/helpers
        raise NotImplementedError(repr(pe))

    def ev(node):
        if isinstance(node, Q.PathExpr):
            return resolve_pathexpr(node)
        if isinstance(node, X.Col):
            v = batch.col(node.name)
            if "." in node.name:
                alias, cname = node.name.split(".", 1)
                tn = _alias_table(ctx, alias)
                if tn and (tn, cname) in eng.rev_dicts:
                    decode[0] = (tn, cname)
            return v
        if isinstance(node, X.Const):
            return jnp.asarray(node.value)
        if isinstance(node, X.Param):
            return jnp.asarray(ctx.param(node.name))
        if isinstance(node, X.Cmp):
            lv, rv = ev_enc(node.left, node.right)
            return X._CMPS[node.op](lv, rv)
        if isinstance(node, X.BoolOp):
            if node.op == "and":
                out = ev(node.args[0])
                for x in node.args[1:]:
                    out = out & ev(x)
                return out
            if node.op == "or":
                out = ev(node.args[0])
                for x in node.args[1:]:
                    out = out | ev(x)
                return out
            return ~ev(node.args[0])
        if isinstance(node, X.Arith):
            av, bv = ev(node.left), ev(node.right)
            return {"+": av + bv, "-": av - bv, "*": av * bv}[node.op]
        if isinstance(node, X.In):
            item = ev(node.item)
            out = jnp.zeros(item.shape, jnp.bool_)
            for v in node.values:
                out = out | (item == jnp.asarray(_enc_for(ctx, node.item, v)))
            return out
        raise TypeError(type(node))

    def _raw_value(n):
        """Literal value of a Const/bound Param side, else None."""
        if isinstance(n, X.Const):
            return n.value
        if isinstance(n, X.Param):
            return ctx.param(n.name)
        return None

    def ev_enc(l, r):
        # encode string constants / parameters against the other side
        rv = _raw_value(r)
        if isinstance(rv, str):
            return ev(l), jnp.asarray(_enc_for(ctx, l, rv))
        lv = _raw_value(l)
        if isinstance(lv, str):
            return jnp.asarray(_enc_for(ctx, r, lv)), ev(r)
        return ev(l), ev(r)

    out = ev(e)
    if want_decode:
        return out, decode[0]
    return out


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------
def execute(plan, engine, params=None) -> QueryResult:
    """Walk the physical tree; the root finalizer assembles the QueryResult.

    This is the single execution entry point for ``GRFusion.run``,
    ``PreparedPlan.execute`` and ``QueryServer.flush_plans``: the plan's
    ``PlanRuntime`` (compiled predicate/mask cache with its epoch checks)
    is created here on first use and reused on every subsequent execution
    of the same plan object.
    """
    from repro.core.compiled import PlanRuntime

    with span("grf.execute"):
        params = dict(params or {})
        missing = [p for p in getattr(plan, "param_names", ()) if p not in params]
        if missing:
            raise ValueError(
                f"unbound parameter(s) {missing}; call PreparedPlan.bind(...) "
                "before executing"
            )
        rt = plan.runtime
        if rt is None or rt.engine is not engine:
            rt = PlanRuntime(engine)
            plan.runtime = rt
        ctx = ExecContext(
            engine=engine, plan=plan, runtime=rt, params=params,
            explain=list(plan.explain_lines()),
        )
        root = plan.root
        if not hasattr(root, "finalize"):
            raise TypeError(f"plan root {type(root).__name__} is not a finalizer")
        return root.finalize(ctx)
