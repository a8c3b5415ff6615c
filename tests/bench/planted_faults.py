"""Faults planted under the benchmark's timed path, for the tests that show
the check catches them. Each installer patches the serving path through
``monkeypatch`` once set-up is done, so warm-up runs clean and only the
window is broken.

* ``answer_altered``: the traversal's answer is altered where it is
  produced (one path dropped from each enumeration; every hop distance
  one too long).
* ``state_unchanged``: every execution of a plan returns the answer of its
  first execution, as a step that never updates its state would.
* ``half_batch_dropped``: every second ticket admitted is left out of the
  batches the loop serves; those tickets never get an answer.

The fault of the exchange between chips does not apply: both cells run on
one chip.
"""
from __future__ import annotations

import jax.numpy as jnp


def answer_altered(monkeypatch):
    from repro.core.traversal_engine import TraversalEngine

    enum, bfs = TraversalEngine.enumerate_paths, TraversalEngine.bfs

    def enumerate_paths(self, *a, **k):
        ps = enum(self, *a, **k)
        return ps.replace(count=jnp.maximum(ps.count - 1, 0))

    def bfs_longer(self, *a, **k):
        dist = bfs(self, *a, **k)
        return jnp.where(dist > 0, dist + 1, dist)

    monkeypatch.setattr(TraversalEngine, "enumerate_paths", enumerate_paths)
    monkeypatch.setattr(TraversalEngine, "bfs", bfs_longer)


def state_unchanged(monkeypatch):
    from repro.core.engine import PreparedPlan

    execute, first = PreparedPlan.execute, {}

    def frozen(self):
        key = id(self.plan)
        if key not in first:
            first[key] = execute(self)
        return first[key]

    monkeypatch.setattr(PreparedPlan, "execute", frozen)


def half_batch_dropped(monkeypatch):
    from repro.serve.loop import QueryLoop

    pump, seen = QueryLoop.pump, {}

    def half_pump(self, *a, **k):
        for shape, bucket in self._buckets.items():
            for t in bucket:
                seen.setdefault(t.tid, len(seen))
            self._buckets[shape] = [t for t in bucket if seen[t.tid] % 2 == 0]
        return pump(self, *a, **k)

    monkeypatch.setattr(QueryLoop, "pump", half_pump)


FAULTS = {
    "answer_altered": answer_altered,
    "state_unchanged": state_unchanged,
    "half_batch_dropped": half_batch_dropped,
}


def after_setup(monkeypatch, install):
    """Patch ``bench.run.setup`` so ``install`` runs once set-up is done."""
    from bench import run

    setup = run.setup

    def broken_setup(*a, **k):
        out = setup(*a, **k)
        install(monkeypatch)
        return out

    monkeypatch.setattr(run, "setup", broken_setup)
