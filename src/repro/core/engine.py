"""GRFusion engine facade: graphs as first-class database objects (paper §2-§5).

Owns the catalog (tables, graph views, string dictionaries, statistics),
executes declarative graph-relational queries through cross-model pipelines,
and maintains graph views under online updates (§3.3):

  * attribute updates touch only the columnar tables (decoupling, §3.2),
  * edge inserts write the edge table AND the view's delta buffer in the
    same call (the paper's transactional view maintenance); delta-only
    inserts bump just the plain topology epoch, so packing caches and
    shard packs stay warm (every traversal backend consults the delta
    stream at query time),
  * deletes are tombstones — traversals see them through the eid/position
    mask gathers with zero structural work,
  * compaction folds delta + tombstones into main: the scheduled path is
    the GRAPHITE-style incremental merge (``compact`` /
    ``merge_compact_view``, O(delta log delta + V + E)), taken when the
    delta buffer reaches ``compact_threshold`` of capacity or an insert
    batch would not fit — never silently dropping edges; the full
    ``compact_view`` rebuild is reserved for structural invalidations
    (vertex-set changes, id updates, tombstoned-row reuse). Both paths
    produce bit-identical views, and either bumps the packing epoch
    exactly once. ``events`` counts every transition for tests and the
    ingest benchmark gate.
"""
from __future__ import annotations

import collections
from dataclasses import dataclass, field as dfield
from typing import Any, Dict, List, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core import executor as EX
from repro.core import expr as X
from repro.core import optimizer as OPT
from repro.core import query as Q
from repro.core.compiled import (
    EpochRegistry, PreparedPlanCache, query_shape_key, table_key,
)
from repro.core.executor import QueryResult  # re-export (public result type)
from repro.core.graphview import GraphView, build_graph_view, merge_compact_view
from repro.core.logical import DEFAULT_MAX_LEN
from repro.core.table import Table, TableStats
from repro.core.traversal_engine import TraversalEngine
from repro.tracing import span

__all__ = ["GRFusion", "QueryResult", "ViewBundle", "PreparedPlan", "GraphStats"]


@dataclass
class ViewBundle:
    view: GraphView
    vertex_table: str
    edge_table: str
    v_id: str
    e_src: str
    e_dst: str
    v_attrs: Dict[str, str]  # alias -> source column
    e_attrs: Dict[str, str]
    directed: bool
    delta_capacity: int


@dataclass(frozen=True)
class GraphStats:
    """Live topology statistics for one graph view (keyed by graph epoch)."""

    name: str
    n_vertices: int
    n_edges: int
    avg_fan_out: float

    @property
    def edge_selectivity(self) -> float:
        """Live edge slots over total slots (tombstone density complement)."""
        return self.n_edges / max(self.n_vertices * self.n_vertices, 1)


@dataclass
class PreparedPlan:
    """A query planned once; ``execute()`` re-walks the physical tree
    against the live catalog without re-invoking the optimizer.

    The plan carries its compiled runtime (``repro.core.compiled``): scan
    filters and traversal masks compile to fused column programs on first
    execution and their masks are cached keyed by table/topology epoch, so
    the serving hot path re-resolves only live column views. ``bind``
    re-binds ``Param`` placeholders (anchor ids, predicate constants)
    without re-planning — parameterized queries no longer need a side
    anchor table. ``bind`` returns a NEW ``PreparedPlan`` sharing the
    physical plan and its compiled runtime, so differently-bound handles
    (e.g. several queued in one ``QueryServer`` flush) never alias each
    other's parameter values.
    """

    engine: "GRFusion"
    plan: OPT.PhysicalPlan
    params: Dict[str, Any] = dfield(default_factory=dict)

    def bind(self, **params) -> "PreparedPlan":
        with span("grf.bind"):
            unknown = sorted(set(params) - set(self.plan.param_names))
            if unknown:
                raise KeyError(
                    f"unknown parameter(s) {unknown}; this plan declares "
                    f"{sorted(self.plan.param_names) or 'none'}"
                )
            return PreparedPlan(
                engine=self.engine, plan=self.plan,
                params={**self.params, **params},
            )

    def execute(self) -> QueryResult:
        return EX.execute(self.plan, self.engine, params=self.params)

    # historical alias (pre-bind API)
    def run(self) -> QueryResult:
        return self.execute()

    @property
    def runtime(self):
        """The plan's compiled-mask cache (None before first execution)."""
        return self.plan.runtime

    def pretty(self) -> str:
        return self.plan.pretty()


class GRFusion:
    def __init__(
        self,
        *,
        default_max_path_len: int = DEFAULT_MAX_LEN,
        max_work_capacity: int = 1 << 18,
        result_capacity: int = 1 << 14,
        bfs_max_hops: int = 32,
        traversal_backend: str = "auto",
        compact_threshold: float = 0.75,
    ):
        self.tables: Dict[str, Table] = {}
        self.views: Dict[str, ViewBundle] = {}
        self.dicts: Dict[tuple, Dict[str, int]] = {}
        self.rev_dicts: Dict[tuple, Dict[int, str]] = {}
        self.default_max_path_len = default_max_path_len
        self.max_work_capacity = max_work_capacity
        self.result_capacity = result_capacity
        self.bfs_max_hops = bfs_max_hops
        # compaction policy: fold the delta into main once it fills past
        # this fraction of capacity (plus whenever an incoming batch would
        # not fit). Scheduled compaction keeps the write path from ever
        # dropping edges AND bounds re-pack churn to once per compaction.
        self.compact_threshold = compact_threshold
        # ingest/compaction lifecycle counters (tests + BENCH_ingest gate):
        # delta_inserts, compactions_merge, compactions_full,
        # threshold_compactions, delta_overflow_compactions,
        # stats_incremental
        self.events = collections.Counter()
        # one epoch registry answers every "did this change?" question:
        # graph names key topology epochs (packing cache), table:<name>
        # keys relational state (compiled predicate-mask cache). Shared
        # with the TraversalEngine so both caches see the same counters.
        self.epochs = EpochRegistry()
        # all BFS/SSSP/path dispatch goes through the TraversalEngine; the
        # backend knob here is the engine-wide default ('auto' = planner
        # density policy), overridable per query via Query.traversal_backend.
        # `events` is shared so backend faults/failovers/retries surface in
        # engine.events alongside the compaction lifecycle counters.
        self.traversal = TraversalEngine(
            default_backend=traversal_backend, epochs=self.epochs,
            events=self.events,
        )
        # per-epoch catalog statistics caches (cost-based optimizer rules)
        self._table_stats: Dict[str, Tuple[int, TableStats]] = {}
        self._graph_stats: Dict[str, Tuple[int, GraphStats]] = {}
        # engine-wide compiled-predicate cache shared by every PlanRuntime,
        # keyed by structural expression identity (LRU-bounded)
        self.predicate_cache: "collections.OrderedDict" = (
            collections.OrderedDict()
        )
        # engine-wide prepared-plan cache keyed by structural query shape;
        # shared by the serving loop and the QueryServer admission path so
        # concurrent clients plan each shape once and bind() per request
        self.plan_cache = PreparedPlanCache()
        self._serving_loop = None
        # table -> its numeric columns that have held a negative value,
        # noted where data enters; a shortest path weighed by one of them
        # is refused (its rounds need non-negative weights to converge)
        self.negative_columns: Dict[str, set] = collections.defaultdict(set)

    def _note_negatives(self, table: str, cols: Mapping[str, np.ndarray]) -> None:
        for k, v in cols.items():
            v = np.asarray(v)
            if v.dtype.kind in "if" and v.size and np.min(v) < 0:
                self.negative_columns[table].add(k)

    # ------------------------------------------------------------- catalog
    def create_table(self, name: str, data: Mapping[str, np.ndarray], capacity=None) -> Table:
        enc = {}
        for k, v in data.items():
            v = np.asarray(v)
            if v.dtype.kind in ("U", "S", "O"):
                codes, d = self._encode_column(name, k, v)
                enc[k] = codes
            else:
                enc[k] = v
        t = Table.create(name, enc, capacity)
        self.negative_columns.pop(name, None)
        self._note_negatives(name, enc)
        self.tables[name] = t
        self.epochs.bump(table_key(name))
        return t

    # ----------------------------------------------------- epochs and stats
    def table_epoch(self, name: str) -> int:
        """Change counter for one table (compiled-mask cache key)."""
        return self.epochs.get(table_key(name))

    def graph_epoch(self, name: str) -> int:
        """Topology change counter for one graph view — bumps on every
        change, delta inserts included (query/value-cache key; the
        coarser packing epoch lives under ``pack:<name>``)."""
        return self.epochs.get(name)

    def table_stats(self, name: str) -> TableStats:
        """Catalog statistics for ``name``, recomputed only on epoch change."""
        ep = self.table_epoch(name)
        ent = self._table_stats.get(name)
        if ent is not None and ent[0] == ep:
            return ent[1]
        s = self.tables[name].compute_stats()
        self._table_stats[name] = (ep, s)
        return s

    def graph_stats(self, name: str) -> GraphStats:
        """Live vertex/edge counts + fan-out for one view (epoch-cached)."""
        ep = self.graph_epoch(name)
        ent = self._graph_stats.get(name)
        if ent is not None and ent[0] == ep:
            return ent[1]
        view = self.views[name].view
        s = GraphStats(
            name=name,
            n_vertices=int(jnp.sum(view.v_valid.astype(jnp.int32))),
            n_edges=int(view.num_edges),
            avg_fan_out=float(view.avg_fan_out),
        )
        self._graph_stats[name] = (ep, s)
        return s

    def _encode_column(self, table, colname, values):
        key = (table, colname)
        d = self.dicts.setdefault(key, {})
        rd = self.rev_dicts.setdefault(key, {})
        codes = np.empty(len(values), np.int32)
        for i, s in enumerate(values):
            s = str(s)
            if s not in d:
                d[s] = len(d)
                rd[d[s]] = s
            codes[i] = d[s]
        return codes, d

    def encode_value(self, table, colname, value):
        key = (table, colname)
        if key in self.dicts and isinstance(value, str):
            return self.dicts[key].get(value, -1)
        return value

    def decode_column(self, table, colname, codes):
        key = (table, colname)
        if key not in self.rev_dicts:
            return codes
        rd = self.rev_dicts[key]
        return np.array([rd.get(int(c), "?") for c in np.asarray(codes).ravel()]).reshape(
            np.shape(codes)
        )

    def create_graph_view(
        self,
        name: str,
        *,
        vertexes: str,
        edges: str,
        v_id: str,
        e_src: str,
        e_dst: str,
        v_attrs: Optional[Mapping[str, str]] = None,
        e_attrs: Optional[Mapping[str, str]] = None,
        directed: bool = True,
        delta_capacity: int = 256,
    ) -> GraphView:
        """CREATE [UNDIRECTED] GRAPH VIEW ... (paper Listing 1)."""
        vt, et = self.tables[vertexes], self.tables[edges]
        view = build_graph_view(
            name, vt, et, v_id=v_id, e_src=e_src, e_dst=e_dst,
            directed=directed, delta_capacity=delta_capacity,
        )
        va = dict(v_attrs or {c: c for c in vt.colnames})
        va.setdefault("id", v_id)
        ea = dict(e_attrs or {c: c for c in et.colnames})
        self.views[name] = ViewBundle(
            view=view, vertex_table=vertexes, edge_table=edges,
            v_id=v_id, e_src=e_src, e_dst=e_dst, v_attrs=va, e_attrs=ea,
            directed=directed, delta_capacity=delta_capacity,
        )
        self.traversal.register_view(name)
        return view

    # ------------------------------------------------------------- updates
    #
    # Atomicity contract (tests/robust crash-point sweep): every mutation
    # below is STAGE-THEN-COMMIT. All risky work — table copies, delta
    # placement, merge compaction, full rebuilds, and therefore every
    # registered fault-injection site — runs against pure inputs with the
    # catalog untouched; the new state then lands through ``_commit``,
    # which is plain assignments and counter bumps only. A fault at any
    # step index leaves the old tables/views queryable and bit-identical
    # to the mutation log with the failed mutation excluded.
    def _commit(
        self,
        *,
        tables: Mapping[str, Table] = {},
        views: Mapping[str, GraphView] = {},
        events: Optional[Mapping[str, int]] = None,
        epoch_ops: Tuple[Tuple[str, str], ...] = (),
    ) -> None:
        """The atomic swap. No compute, no fault sites, nothing that can
        raise — staged state either commits in full or (a staging fault)
        not at all. Keep it that way."""
        for name, t in tables.items():
            self.tables[name] = t
            self.epochs.bump(table_key(name))
        for vname, v in views.items():
            self.views[vname].view = v
        for kind, vname in epoch_ops:
            if kind == "main":
                self.traversal.bump_epoch(vname)
            else:
                self.traversal.bump_delta_epoch(vname)
        if events:
            self.events.update(events)

    def _stage_rebuild(self, vname: str, vb: ViewBundle, table_of) -> GraphView:
        """Full view rebuild against (possibly staged) source tables."""
        return build_graph_view(
            vname, table_of(vb.vertex_table), table_of(vb.edge_table),
            v_id=vb.v_id, e_src=vb.e_src, e_dst=vb.e_dst,
            directed=vb.directed, delta_capacity=vb.delta_capacity,
        )

    def _stage_merge(self, vb: ViewBundle, view: GraphView, table_of) -> GraphView:
        """Incremental merge compaction of ``view`` against (possibly
        staged) source tables."""
        return merge_compact_view(
            view, table_of(vb.vertex_table), table_of(vb.edge_table),
            v_id=vb.v_id, e_src=vb.e_src, e_dst=vb.e_dst,
            directed=vb.directed,
        )

    def insert(self, table_name: str, rows: Mapping[str, np.ndarray]):
        """Insert rows; graph views over this source update transactionally.

        Edge inserts take the streaming path: rows land in each view's
        delta buffer under ``bump_delta_epoch`` (packs stay warm). When
        the batch would not fit the remaining delta capacity, the engine
        compacts FIRST-ish — the batch is already in the staged edge
        table, so one merge compaction folds buffer + batch into main
        together and no edge is ever dropped. Two hazards force the full
        rebuild instead: a vertex-table insert (id index changes) and
        tombstoned-row reuse (a stale main slot with the recycled eid
        would come back to life; ``Table.used`` fresh-first allocation
        makes this rare, and the ``prev_used`` check below makes it safe).

        The whole update is staged off to the side and committed in one
        swap (see the atomicity contract above): a fault anywhere in the
        staging — including inside a merge compaction — leaves table AND
        views exactly as they were.
        """
        t = self.tables[table_name]
        enc_rows = {}
        for k, v in rows.items():
            v = np.asarray(v)
            if v.dtype.kind in ("U", "S", "O"):
                enc_rows[k], _ = self._encode_column(table_name, k, v)
            else:
                enc_rows[k] = v
        self._note_negatives(table_name, enc_rows)
        prev_used = t.used
        prev_epoch = self.table_epoch(table_name)
        t2, slots, overflow = t.insert(enc_rows)
        if bool(overflow):
            raise RuntimeError(f"table {table_name} capacity exceeded")
        reused = bool(
            jnp.any(
                (slots >= 0)
                & jnp.take(prev_used, jnp.clip(slots, 0, t.capacity - 1))
            )
        )

        def table_of(name: str) -> Table:
            return t2 if name == table_name else self.tables[name]

        staged: Dict[str, GraphView] = {}
        ev: collections.Counter = collections.Counter()
        epoch_ops: List[Tuple[str, str]] = []
        for vname, vb in self.views.items():
            if vb.edge_table == table_name:
                if reused:
                    # resurrection hazard: the recycled rows' stale main
                    # slots must be rewritten, which only a rebuild does
                    staged[vname] = self._stage_rebuild(vname, vb, table_of)
                    ev["compactions_full"] += 1
                    epoch_ops.append(("main", vname))
                    continue
                src_ids = jnp.asarray(enc_rows[vb.e_src], jnp.int32)
                dst_ids = jnp.asarray(enc_rows[vb.e_dst], jnp.int32)
                sp, sf = vb.view.id_index.lookup(src_ids)
                dp, df = vb.view.id_index.lookup(dst_ids)
                ok = sf & df & (slots >= 0)
                # capacity precheck: insert_delta placement is positional
                # (entry j consumes the j-th free slot, valid or not), so
                # the batch fits iff its LENGTH fits — and the undirected
                # reverse pass starts after n_ok slots were consumed
                k_len = int(slots.shape[0])
                n_ok = int(jnp.sum(ok.astype(jnp.int32)))
                free0 = vb.view.delta_capacity - int(
                    jnp.sum(vb.view.delta_valid.astype(jnp.int32))
                )
                need = k_len if vb.directed else k_len + n_ok
                if need > free0:
                    # batch is already in the staged edge table: one merge
                    # folds the current buffer AND this batch into main
                    ev["delta_overflow_compactions"] += 1
                    staged[vname] = self._stage_merge(vb, vb.view, table_of)
                    ev["compactions_merge"] += 1
                    epoch_ops.append(("main", vname))
                    continue
                view2, _ = vb.view.insert_delta(sp, dp, slots, ok)
                if vb.directed is False:
                    view2, _ = view2.insert_delta(dp, sp, slots, ok)
                ev["delta_inserts"] += 1
                epoch_ops.append(("delta", vname))
                fill = int(jnp.sum(view2.delta_valid.astype(jnp.int32)))
                if fill >= self.compact_threshold * vb.view.delta_capacity:
                    ev["threshold_compactions"] += 1
                    ev["compactions_merge"] += 1
                    staged[vname] = self._stage_merge(vb, view2, table_of)
                    epoch_ops.append(("main", vname))
                else:
                    staged[vname] = view2
            if vb.vertex_table == table_name:
                # vertex inserts change the id index: compact (rebuild) now
                staged[vname] = self._stage_rebuild(vname, vb, table_of)
                ev["compactions_full"] += 1
                epoch_ops.append(("main", vname))

        self._commit(
            tables={table_name: t2}, views=staged, events=ev,
            epoch_ops=tuple(epoch_ops),
        )
        self._update_stats_incremental(table_name, prev_epoch, enc_rows)
        return np.asarray(slots)

    def _update_stats_incremental(self, table_name, prev_epoch, enc_rows):
        """Fold a pure-insert batch into cached sketch-bearing stats.

        Only fires when the cache is exactly one epoch behind (the batch
        is the only change) and the previous stats carry sketches; the
        register max-merge then lands on the same registers a full rescan
        would (see ``TableStats``), so the cache skips the O(rows) pass.
        """
        ent = self._table_stats.get(table_name)
        if ent is None or ent[0] != prev_epoch or ent[1].sketches is None:
            return
        if not all(c in enc_rows for c in ent[1].sketches):
            return
        s2 = self.tables[table_name].compute_stats(
            prev=ent[1], appended=enc_rows
        )
        self._table_stats[table_name] = (self.table_epoch(table_name), s2)
        self.events["stats_incremental"] += 1

    def delete_where(self, table_name: str, predicate: X.Expr):
        """Tombstone deletes; views see them via validity-mask gathers.
        Staged and committed atomically like ``insert``."""
        t = self.tables[table_name]
        mask = X.evaluate(
            predicate,
            lambda c: t.col(c),
            encode=lambda c, v: self.encode_value(table_name, c, v),
        )
        t2 = t.delete(mask & t.valid)

        def table_of(name: str) -> Table:
            return t2 if name == table_name else self.tables[name]

        staged: Dict[str, GraphView] = {}
        ev: collections.Counter = collections.Counter()
        epoch_ops: List[Tuple[str, str]] = []
        for vname, vb in self.views.items():
            if vb.vertex_table == table_name:
                # keep referential integrity stats fresh (§3.3.1)
                staged[vname] = self._stage_rebuild(vname, vb, table_of)
                ev["compactions_full"] += 1
                epoch_ops.append(("main", vname))
        self._commit(
            tables={table_name: t2}, views=staged, events=ev,
            epoch_ops=tuple(epoch_ops),
        )

    def update_where(self, table_name: str, predicate: X.Expr, col: str, value):
        t = self.tables[table_name]
        mask = X.evaluate(
            predicate, lambda c: t.col(c),
            encode=lambda c, v: self.encode_value(table_name, c, v),
        )
        value = self.encode_value(table_name, col, value)
        self._note_negatives(table_name, {col: np.asarray(value).reshape(-1)})
        t2 = t.update(mask & t.valid, col, value)

        def table_of(name: str) -> Table:
            return t2 if name == table_name else self.tables[name]

        staged: Dict[str, GraphView] = {}
        ev: collections.Counter = collections.Counter()
        epoch_ops: List[Tuple[str, str]] = []
        # identifier updates must be reflected in the topology (§3.3.1)
        for vname, vb in self.views.items():
            hits_id = table_name == vb.vertex_table and col == vb.v_id
            hits_endpoint = table_name == vb.edge_table and col in (
                vb.e_src, vb.e_dst
            )
            if hits_id or hits_endpoint:
                staged[vname] = self._stage_rebuild(vname, vb, table_of)
                ev["compactions_full"] += 1
                epoch_ops.append(("main", vname))
        self._commit(
            tables={table_name: t2}, views=staged, events=ev,
            epoch_ops=tuple(epoch_ops),
        )

    def compact(self, name: str, *, full: bool = False):
        """Fold the delta buffer and tombstones into the view's main arrays.

        The default path is the GRAPHITE-style incremental merge
        (``merge_compact_view``): main stays sorted, only new rows sort,
        tombstoned slots drop in the same pass — bit-identical to the
        full rebuild (the property suite asserts it) at
        O(delta log delta + V + E) instead of O(E log E). ``full=True``
        forces the rebuild (``compact_view``). Either path bumps the
        packing epoch exactly once, and the new view is built off to the
        side then swapped in one commit — a fault at any merge step
        leaves the old view queryable.
        """
        with span("grf.compact"):
            if full:
                return self.compact_view(name)
            vb = self.views[name]
            new_view = self._stage_merge(vb, vb.view, lambda n: self.tables[n])
            self._commit(
                views={name: new_view}, events={"compactions_merge": 1},
                epoch_ops=(("main", name),),
            )

    def compact_view(self, name: str):
        """Full rebuild compaction (vertex-set changes, id updates, row
        reuse — every case the incremental merge's preconditions exclude).
        Staged then committed like ``compact``."""
        vb = self.views[name]
        new_view = self._stage_rebuild(name, vb, lambda n: self.tables[n])
        self._commit(
            views={name: new_view}, events={"compactions_full": 1},
            epoch_ops=(("main", name),),
        )

    # ---------------------------------------------- interpreted mask path
    # The executor evaluates all predicate masks through the plan's
    # compiled runtime (repro.core.compiled). These interpreted versions
    # are the semantic reference the differential suite checks the
    # compiled programs against bit-for-bit; they re-walk the AST per call.
    def _vertex_mask(self, vb: ViewBundle, preds: List[X.Expr], params=None):
        """Interpret vertex-attr predicates to a mask-by-position."""
        vt = self.tables[vb.vertex_table]
        mask = vt.valid
        for p in preds:
            m = X.evaluate(
                p,
                lambda c: vt.col(vb.v_attrs.get(c, c)),
                encode=lambda c, v: self.encode_value(
                    vb.vertex_table, vb.v_attrs.get(c, c), v
                ),
                params=params,
            )
            mask = mask & m
        return mask

    def _edge_mask(self, vb: ViewBundle, preds: List[X.Expr], params=None):
        et = self.tables[vb.edge_table]
        mask = et.valid
        for p in preds:
            m = X.evaluate(
                p,
                lambda c: et.col(vb.e_attrs.get(c, c)),
                encode=lambda c, v: self.encode_value(
                    vb.edge_table, vb.e_attrs.get(c, c), v
                ),
                params=params,
            )
            mask = mask & m
        return mask

    # ------------------------------------------------------------- execution
    def plan(self, query: Q.Query) -> OPT.PhysicalPlan:
        """builder -> logical tree -> rule pipeline -> physical tree."""
        if query.max_path_len is None and any(
            f.kind == "paths" for f in query.froms
        ):
            query.max_path_len = self.default_max_path_len
        return OPT.optimize(query, self.views, stats=self)

    def run(self, query: Q.Query) -> QueryResult:
        # ad-hoc queries ride the same prepared path (plan + compiled
        # runtime + execute); the plan object is simply not retained
        return self.prepare(query).execute()

    def explain(self, query: Q.Query) -> OPT.PhysicalPlan:
        """Typed physical plan for ``query`` (no execution). ``str(plan)``
        prints the operator tree plus one line per applied rewrite rule."""
        return self.plan(query)

    def prepare(self, query: Q.Query) -> PreparedPlan:
        """Plan once, execute many (parameterized / repeated serving)."""
        return PreparedPlan(engine=self, plan=self.plan(query))

    def query_shape(self, query: Q.Query):
        """Structural plan-shape key of ``query`` (the plan-cache key)."""
        return query_shape_key(
            query, default_max_path_len=self.default_max_path_len
        )

    def prepare_cached(self, query: Q.Query) -> PreparedPlan:
        """``prepare`` through the engine-wide shape-keyed plan cache:
        structurally identical queries (same shape, any ``Param``
        bindings) share one plan and its warm compiled runtime across
        every client of this engine."""
        return self.plan_cache.get_or_prepare(
            self.query_shape(query), lambda: self.prepare(query)
        )

    def serving_loop(self, **kwargs):
        """The engine's continuous-batching admission loop
        (``repro.serve.loop.QueryLoop``), created on first use; keyword
        arguments configure the first creation (lane_width,
        flush_deadline_us, max_pending, clock) and are rejected on later
        calls so two callers cannot silently race on configuration.
        ``loop.submit(query, **params)`` is the serving entry point."""
        from repro.serve.loop import QueryLoop

        if self._serving_loop is None:
            self._serving_loop = QueryLoop(self, **kwargs)
        elif kwargs:
            raise RuntimeError(
                "serving loop already configured; construct QueryLoop "
                "directly for a second independently-configured loop"
            )
        return self._serving_loop

    def path_string(self, result: QueryResult, verts_col: str, i: int = 0) -> str:
        v = np.asarray(result.columns[verts_col])[i]
        ids = [int(x) for x in v if x >= 0]
        return "->".join(str(x) for x in ids)
