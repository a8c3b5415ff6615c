"""Continuous-batching admission loop for graph-relational serving.

``QueryServer`` batches well but the caller must drive ``flush_plans()``
by hand; ``QueryLoop`` closes that gap the way ``LMServer`` does for
decode slots: the loop owns a shared engine and drives itself. Requests
enqueue into per-structure buckets keyed by *plan shape*
(``repro.core.compiled.query_shape_key``) — each shape is planned at most
once through the engine-wide cross-client ``PreparedPlanCache`` and every
request only ``bind()``s its parameters onto the shared handle, so the
steady-state hot path touches warm compiled masks and re-plans nothing.

Control plane, in the order the paper's serving story needs them:

  * **adaptive flush** — a bucket becomes *ready* when it holds
    ``lane_width`` tickets (a full lane: flush now, latency is already
    paid) or when ``flush_deadline_us`` has elapsed since its oldest
    ticket (a cold shape must not wait forever for a lane to fill);
  * **bounded-queue backpressure** — admission rejects (status
    ``rejected`` with a ``retry_after_us`` hint) once ``max_pending``
    tickets are queued, rather than growing the queue without bound and
    converting overload into unbounded latency;
  * **round-robin fairness** — each ``pump()`` services ready buckets
    starting *after* the last-served shape and takes at most
    ``lane_width`` tickets per bucket per rotation, so one hot
    tenant/shape cannot starve cold shapes out of the loop;
  * **per-ticket deadlines** — a ticket submitted with ``deadline_us``
    that is still queued past its budget finishes ``timed_out`` instead
    of executing late (the client already gave up — don't spend a lane
    on it);
  * **bounded retry with backoff** — a transient failure
    (``repro.robust.faults.TransientFault``: a fault the injection
    harness marks retryable) re-queues the ticket up to ``max_retries``
    times with exponentially growing ``retry_backoff_us`` spacing before
    it fails for real;
  * **per-shape circuit breaker** — ``breaker_threshold`` consecutive
    failures of one shape open its breaker for ``breaker_window_us``:
    submissions are shed with a ``retry_after_us`` hint covering the
    open window, queued tickets wait, and the first ticket after the
    window runs as a half-open probe (success closes the breaker, another
    failure reopens it with the window doubled). A poison shape costs
    one probe per window instead of burning every pump rotation.

Every failure/timeout/retry counter in ``stats`` is mirrored into the
engine's ``events`` under a ``serving_`` prefix, so a silently failing
warm loop is visible next to the compaction/traversal counters.

The clock is injectable (microseconds) so tests and the closed-loop
benchmark drive deadlines deterministically; the default reads
``time.monotonic``.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, field as dfield
from typing import Any, Callable, Dict, List, Optional

from repro.robust.faults import TransientFault
from repro.tracing import span

__all__ = ["Ticket", "QueryLoop"]

_INF = float("inf")


def _monotonic_us() -> float:
    return time.monotonic() * 1e6


@dataclass
class Ticket:
    """One admitted (or rejected) request.

    ``status`` walks ``queued -> done | failed | timed_out``; admission
    overload or an open circuit breaker short-circuits to ``rejected``
    (never enqueued — retry after ``retry_after_us``). ``result`` holds
    the QueryResult for ``done`` tickets, ``error`` the execution
    exception for ``failed`` ones — one bad bind can neither wedge its
    bucket nor discard neighbors. ``deadline_at_us`` is the absolute
    instant after which the ticket times out instead of executing;
    ``not_before_us`` defers a transient-failure retry until its backoff
    elapses."""

    tid: int
    shape: Any
    params: Dict[str, Any] = dfield(default_factory=dict)
    status: str = "queued"
    result: Any = None
    error: Optional[Exception] = None
    retry_after_us: Optional[float] = None
    submitted_us: float = 0.0
    done_us: Optional[float] = None
    deadline_at_us: Optional[float] = None
    retries: int = 0
    not_before_us: Optional[float] = None

    @property
    def latency_us(self) -> Optional[float]:
        if self.done_us is None:
            return None
        return self.done_us - self.submitted_us


class QueryLoop:
    """Self-driving admission loop over one shared ``GRFusion`` engine."""

    def __init__(
        self,
        engine,
        *,
        lane_width: int = 16,
        flush_deadline_us: float = 2000.0,
        max_pending: int = 1024,
        clock: Optional[Callable[[], float]] = None,
        max_retries: int = 2,
        retry_backoff_us: float = 500.0,
        breaker_threshold: int = 3,
        breaker_window_us: float = 10_000.0,
    ):
        self.engine = engine
        self.lane_width = int(lane_width)
        self.flush_deadline_us = float(flush_deadline_us)
        self.max_pending = int(max_pending)
        self.clock = clock or _monotonic_us
        # hardening knobs: transient-failure retry budget + backoff base,
        # and the per-shape circuit breaker's trip streak / open window
        self.max_retries = int(max_retries)
        self.retry_backoff_us = float(retry_backoff_us)
        self.breaker_threshold = int(breaker_threshold)
        self.breaker_window_us = float(breaker_window_us)
        # shape -> {streak, open_until, window} (created on first failure)
        self._breaker: Dict[Any, Dict[str, Any]] = {}
        # shared cross-client plan cache (one plan per structural shape,
        # engine-wide — NOT per loop, so QueryServer admissions and direct
        # prepare_cached callers warm the same entries)
        self.plans = engine.plan_cache
        self._prepared: Dict[Any, Any] = {}  # shape -> PreparedPlan
        self._buckets: "collections.OrderedDict[Any, List[Ticket]]" = (
            collections.OrderedDict()
        )
        self._deadline: Dict[Any, float] = {}  # shape -> oldest-ticket due
        self._rr: List[Any] = []  # shape service order (rotates)
        self._rr_next = 0
        self.pending = 0
        self._next_tid = 0
        self.stats = collections.Counter()

    # ------------------------------------------------------------ admission
    def _count(self, key: str) -> None:
        """Failure-path counter: stats key + the ``serving_<key>`` mirror
        in the engine's events (so a silently failing warm loop shows up
        next to the compaction/traversal counters)."""
        self.stats[key] += 1
        self.engine.events[f"serving_{key}"] += 1

    def submit(self, query, *, deadline_us: Optional[float] = None, **params) -> Ticket:
        """Admit one request: shape-key the query, plan on first sight of
        the shape (shared cache), enqueue a ticket carrying only the
        parameter bindings. Over ``max_pending`` — or while the shape's
        circuit breaker is open — the ticket comes back ``rejected`` with
        a retry hint instead of growing the queue. ``deadline_us`` is the
        client's latency budget: a ticket still queued past it finishes
        ``timed_out`` instead of executing late."""
        with span("grf.submit"):
            now = self.clock()
            tid = self._next_tid
            self._next_tid += 1
            shape = self.engine.query_shape(query)
            br = self._breaker.get(shape)
            if (
                br is not None and br["open_until"] is not None
                and now < br["open_until"]
            ):
                # shed the poison shape while its breaker is open; the first
                # ticket admitted after the window passes (or one already
                # queued) runs as the half-open probe
                self._count("breaker_shed")
                self.stats["rejected"] += 1
                return Ticket(
                    tid=tid, shape=shape, params=dict(params),
                    status="rejected", submitted_us=now,
                    retry_after_us=self._retry_after(now, shape),
                )
            if self.pending >= self.max_pending:
                self.stats["rejected"] += 1
                return Ticket(
                    tid=tid, shape=shape, params=dict(params),
                    status="rejected", submitted_us=now,
                    retry_after_us=self._retry_after(now, shape),
                )
            prepared = self.plans.get_or_prepare(
                shape, lambda: self.engine.prepare(query)
            )
            self._prepared[shape] = prepared
            t = Ticket(
                tid=tid, shape=shape, params=dict(params), submitted_us=now,
                deadline_at_us=None if deadline_us is None else now + deadline_us,
            )
            bucket = self._buckets.get(shape)
            if bucket is None:
                bucket = self._buckets[shape] = []
                self._rr.append(shape)
            if not bucket:
                self._deadline[shape] = now + self.flush_deadline_us
            bucket.append(t)
            self.pending += 1
            self.stats["admitted"] += 1
            return t

    def _retry_after(self, now: float, shape: Any = None) -> float:
        """Backpressure hint: the earliest queued bucket flushes by its
        deadline, freeing lane_width slots — retry then. A shape shed by
        an open circuit breaker must additionally wait out the breaker
        window (the hint used to ignore the breaker, telling rejected
        tickets to retry straight into a still-open one)."""
        due = min(self._deadline.values(), default=now)
        hint = max(due - now, 0.0) + self.flush_deadline_us
        if shape is not None:
            br = self._breaker.get(shape)
            if br is not None and br["open_until"] is not None:
                hint = max(hint, br["open_until"] - now)
        return hint

    # ------------------------------------------------------------- service
    def next_due(self) -> Optional[float]:
        """Earliest bucket flush deadline, or None when nothing is queued.
        Discrete-event drivers (the fig13 closed-loop benchmark) advance
        their virtual clock to this instant between arrivals instead of
        busy-polling ``pump``."""
        return min(self._deadline.values(), default=None)

    def _ready(self, shape, now: float) -> bool:
        bucket = self._buckets.get(shape)
        if not bucket:
            return False
        return (
            len(bucket) >= self.lane_width
            or now >= self._deadline[shape]
        )

    # ------------------------------------------------- circuit breaker
    def _shape_failure(self, shape: Any, now: float) -> None:
        """One real (post-retry) failure: grow the streak; trip the
        breaker at the threshold, and re-open with a doubled window when
        a half-open probe fails."""
        br = self._breaker.get(shape)
        if br is None:
            br = self._breaker[shape] = {
                "streak": 0, "open_until": None,
                "window": self.breaker_window_us,
            }
        br["streak"] += 1
        if br["open_until"] is not None:
            br["window"] *= 2.0
            br["open_until"] = now + br["window"]
            self._count("breaker_reopened")
        elif br["streak"] >= self.breaker_threshold:
            br["open_until"] = now + br["window"]
            self._count("breaker_opened")

    def _shape_success(self, shape: Any) -> None:
        br = self._breaker.get(shape)
        if br is None:
            return
        if br["open_until"] is not None:
            self._count("breaker_closed")
        br["streak"] = 0
        br["open_until"] = None
        br["window"] = self.breaker_window_us

    def pump(self, *, force: bool = False) -> List[Ticket]:
        """One loop iteration: serve every *ready* bucket once, round-robin
        from just past the shape served first last time. Each bucket
        yields at most ``lane_width`` tickets per rotation; a hot shape's
        remainder re-queues behind every other ready shape with a fresh
        deadline (a still-full remainder stays ready by size, but only
        gets its next turn after the rest of the rotation). ``force=True``
        treats every non-empty bucket as ready (drain semantics).

        Hardening: tickets past their ``deadline_at_us`` finish
        ``timed_out`` without executing; a ``TransientFault`` re-queues
        the ticket with exponential backoff up to ``max_retries``; a
        shape whose breaker is open is skipped whole (one half-open probe
        per window once it elapses) so a poison shape cannot burn the
        rotation."""
        now = self.clock()
        done: List[Ticket] = []
        n = len(self._rr)
        if n == 0:
            return done
        order = [self._rr[(self._rr_next + i) % n] for i in range(n)]
        rotated = False
        for shape in order:
            if not (force or self._ready(shape, now)):
                continue
            probing = False
            br = self._breaker.get(shape)
            if br is not None and br["open_until"] is not None:
                if now < br["open_until"] and not force:
                    # open: shed the whole rotation for this shape, and
                    # push its wakeup out to the window edge
                    self._count("breaker_skipped")
                    if self._buckets.get(shape):
                        self._deadline[shape] = br["open_until"]
                    continue
                probing = True  # half-open: serve exactly one probe
            if not rotated:
                # next pump starts after the first shape served this time
                self._rr_next = (self._rr.index(shape) + 1) % n
                rotated = True
            width = 1 if probing else self.lane_width
            batch: List[Ticket] = []
            rest: List[Ticket] = []
            for t in self._buckets[shape]:
                if len(batch) < width and (
                    force or t.not_before_us is None or now >= t.not_before_us
                ):
                    batch.append(t)
                else:
                    rest.append(t)
            self._buckets[shape] = rest
            if rest:
                nb = [t.not_before_us for t in rest]
                if all(x is not None for x in nb):
                    # nothing but deferred retries: wake at the earliest
                    # backoff instead of a (possibly earlier) empty flush
                    self._deadline[shape] = max(now, min(nb))
                else:
                    self._deadline[shape] = now + self.flush_deadline_us
            else:
                self._deadline.pop(shape, None)
            if not batch:
                continue
            prepared = self._prepared[shape]
            for t in batch:
                with span("grf.ticket"):
                    if t.deadline_at_us is not None and now >= t.deadline_at_us:
                        # client budget already blown: don't spend a lane on it
                        t.status = "timed_out"
                        t.done_us = self.clock()
                        self.pending -= 1
                        self._count("timed_out")
                        done.append(t)
                        continue
                    try:
                        t.result = prepared.bind(**t.params).execute()
                    except TransientFault as e:
                        self._count("transient_faults")
                        if t.retries < self.max_retries:
                            # bounded retry with exponential backoff: the
                            # ticket stays pending, deferred past its backoff
                            t.retries += 1
                            t.not_before_us = now + self.retry_backoff_us * (
                                2 ** (t.retries - 1)
                            )
                            self._buckets[shape].append(t)
                            self._deadline[shape] = min(
                                self._deadline.get(shape, _INF), t.not_before_us
                            )
                            self._count("retries")
                            continue
                        t.error = e
                        t.status = "failed"
                        t.done_us = self.clock()
                        self.pending -= 1
                        self._count("failed")
                        self._shape_failure(shape, now)
                        done.append(t)
                    except Exception as e:  # noqa: BLE001 - per-ticket isolation
                        t.error = e
                        t.status = "failed"
                        t.done_us = self.clock()
                        self.pending -= 1
                        self._count("failed")
                        self._shape_failure(shape, now)
                        done.append(t)
                    else:
                        t.status = "done"
                        t.done_us = self.clock()
                        self.pending -= 1
                        self.stats["executed"] += 1
                        self._shape_success(shape)
                        done.append(t)
            self.stats["flushes"] += 1
        return done

    def drain(self) -> List[Ticket]:
        """Service everything queued regardless of deadlines (shutdown /
        test convenience); fairness rotation still applies per pass."""
        out: List[Ticket] = []
        while self.pending:
            out.extend(self.pump(force=True))
        return out

    def run_until_idle(self, *, max_iters: int = 1_000_000) -> List[Ticket]:
        """Drive ``pump`` until the queue is empty, honoring deadlines
        (busy-waits on the injected clock between due times)."""
        out: List[Ticket] = []
        it = 0
        while self.pending and it < max_iters:
            out.extend(self.pump())
            it += 1
        return out
