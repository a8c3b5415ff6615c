"""Batched serving engines.

`LMServer`: slot-based continuous batching for decode — fixed B slots each
with its own KV-cache lane and position; requests occupy free slots, decode
advances all active slots in one jitted step, finished slots are recycled.
(The production analogue runs the same jitted step on the sharded mesh;
the slot logic is host-side control plane.)

`QueryServer`: the paper-side serving path — batches reachability /
shortest-path queries into fixed-width lanes and executes them as one
frontier sweep (the multi-source BFS is the batched query executor).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as TF


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # int32 [T]
    max_new: int = 16
    out: Optional[List[int]] = None


class LMServer:
    def __init__(self, params, cfg: TF.LMConfig, *, n_slots: int = 4, max_len: int = 256):
        self.params = params
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.cache = TF.init_cache(cfg, n_slots, max_len)
        self.pos = np.zeros(n_slots, np.int32)
        self.active: List[Optional[Request]] = [None] * n_slots
        self.remaining = np.zeros(n_slots, np.int32)
        self.last_tok = np.zeros(n_slots, np.int32)
        self._step = jax.jit(
            lambda p, c, t, pos: TF.decode_step(p, c, t, pos, cfg)
        )

    def _free_slot(self) -> Optional[int]:
        for i, a in enumerate(self.active):
            if a is None:
                return i
        return None

    def submit(self, req: Request) -> bool:
        slot = self._free_slot()
        if slot is None:
            return False
        req.out = []
        self.active[slot] = req
        # prefill token-by-token through the decode path (slot-local).
        # pos goes in as a copy: the host-to-device transfer of a numpy
        # array may still be pending when the += below mutates it
        self.pos[slot] = 0
        for t in req.prompt:
            logits, self.cache = self._step(
                self.params, self.cache,
                jnp.asarray(self.last_tok)[:, None].at[slot].set(int(t)),
                jnp.asarray(self.pos.copy()),
            )
            self.pos[slot] += 1
        self.last_tok[slot] = int(np.argmax(np.asarray(logits)[slot, 0]))
        req.out.append(int(self.last_tok[slot]))
        self.remaining[slot] = req.max_new - 1
        return True

    def step(self) -> List[Request]:
        """One decode step for all active slots; returns finished requests."""
        if not any(a is not None for a in self.active):
            return []
        logits, self.cache = self._step(
            self.params, self.cache,
            jnp.asarray(self.last_tok)[:, None], jnp.asarray(self.pos.copy()),
        )
        nxt = np.argmax(np.asarray(logits)[:, 0], axis=-1)
        done = []
        for i, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[i] += 1
            self.last_tok[i] = int(nxt[i])
            req.out.append(int(nxt[i]))
            self.remaining[i] -= 1
            if self.remaining[i] <= 0 or self.pos[i] >= self.max_len - 1:
                done.append(req)
                self.active[i] = None
        return done


class QueryServer:
    """Batches graph-relational reachability queries into one BFS sweep.

    Thin admission shim over ``TraversalEngine``'s batched multi-query
    path: external vertex ids are resolved to positions, enqueued, and one
    ``flush`` merges every pending query into [S, V] frontier sweeps (the
    traversal engine buckets lane counts to bound retracing). ``backend``
    pins a physical traversal backend; None keeps the engine default.

    Beyond raw (src, dst) reachability pairs, the server admits
    *pre-optimized physical plans*: ``prepare(query)`` runs the optimizer's
    rule pipeline once and returns a ``PreparedPlan`` whose executor tree
    is re-walked on every ``submit_plan``/``flush_plans`` — repeated
    parameterized queries skip re-planning entirely and still see live
    catalog state (delta inserts, tombstones) because the tree resolves
    views and masks at execution time.

    There is exactly one plan-cache code path: ``PreparedPlan.execute``
    (shared with ``GRFusion.run``/``prepare``) owns the compiled-mask
    runtime and its epoch checks (``repro.core.compiled.PlanRuntime``);
    this server adds only queueing and error isolation on top. Re-bind
    parameters with ``plan.bind(...)`` between submissions — no
    re-planning, and cached masks survive across bind calls whose values
    don't feed them.
    """

    def __init__(
        self, engine, graph: str, *, lane_width: int = 64,
        max_hops: int = 16, backend: Optional[str] = None,
    ):
        self.engine = engine
        self.graph = graph
        self.lane_width = lane_width
        self.max_hops = max_hops
        self.backend = backend
        self.trav = engine.traversal
        self.pending: List[Dict] = []
        self.pending_plans: List = []

    def submit(self, src_id: int, dst_id: int):
        self.pending.append({"src": src_id, "dst": dst_id})

    # -- pre-optimized plan admission -------------------------------------
    def prepare(self, query):
        """Run the rule pipeline once — through the engine-wide
        shape-keyed plan cache (``GRFusion.plan_cache``), so this server,
        the continuous-batching ``QueryLoop``, and direct
        ``prepare_cached`` callers all share one plan (and its warm
        compiled runtime) per structural query shape."""
        return self.engine.prepare_cached(query)

    def submit_plan(self, plan_or_query):
        """Enqueue a PreparedPlan (a bare Query is planned on admission,
        through the shared shape-keyed plan cache)."""
        from repro.core.engine import PreparedPlan
        from repro.core.query import Query

        if isinstance(plan_or_query, PreparedPlan):
            prepared = plan_or_query
        elif isinstance(plan_or_query, Query):
            prepared = self.engine.prepare_cached(plan_or_query)
        else:
            raise TypeError(
                "submit_plan takes a PreparedPlan or a Query, got "
                f"{type(plan_or_query).__name__} (pass GRFusion.prepare(q), "
                "not GRFusion.plan(q))"
            )
        self.pending_plans.append(prepared)
        return prepared

    def flush_plans(self) -> List:
        """Execute every queued prepared plan (no re-planning). The queue
        is drained up front and every plan runs even if an earlier one
        fails: each entry in the returned list is either the plan's
        QueryResult or the exception its execution raised, so one bad plan
        can neither wedge the queue nor discard its neighbors' results.
        Epoch checks and compiled-mask reuse happen inside
        ``PreparedPlan.execute`` — the same path ``GRFusion`` uses — so a
        plan submitted N times evaluates its masks at most once per
        catalog change, not once per submission."""
        plans, self.pending_plans = self.pending_plans, []
        out = []
        for p in plans:
            try:
                out.append(p.execute())
            except Exception as e:  # noqa: BLE001 - reported to the caller
                self.engine.events["serving_plan_failures"] += 1
                out.append(e)
        return out

    def flush(self) -> List[Dict]:
        if not self.pending:
            return []
        vb = self.engine.views[self.graph]
        ids = jnp.asarray(
            [[q["src"], q["dst"]] for q in self.pending], jnp.int32
        )
        pos, found = vb.view.id_index.lookup(ids.reshape(-1))
        pos = np.asarray(jnp.where(found, pos, -1)).reshape(-1, 2)
        handles = [
            self.trav.submit_reachability(
                vb.view, int(sp), int(tp), graph=self.graph
            )
            for sp, tp in pos
        ]
        # flush only OUR handles: other servers sharing this engine keep
        # their queue (and their own edge mask / hop budget / backend)
        self.trav.flush(
            max_hops=self.max_hops,
            edge_mask_by_row=self.engine.tables[vb.edge_table].valid,
            backend=self.backend,
            lane_width=self.lane_width,
            handles=handles,
        )
        out = [
            {**q, **h.result} for q, h in zip(self.pending, handles)
        ]
        self.pending = []
        return out
