"""Spans and host-sync counts on the served path.

``span(name)`` opens a ``jax.profiler.TraceAnnotation``: a host span on the
profiler's own clock, beside the device ops of the same trace. Names are
constants under the ``grf.`` prefix, so a trace reduction finds them by
name and a refactor that keeps a span keeps its name. With no profiler
running a span costs about a microsecond.

``to_host(x, site, events)`` makes a blocking device-to-host read: it
waits for ``x`` (an array, or a tuple or list of them), copies it to host
numpy and counts the call under ``events["host_sync.<site>"]``, so a
ticket's host syncs are a counter delta rather than a guess. The executor
and the traversal engine read through it; the ``pallas_frontier`` hop loop
(``kernels/frontier/ops.py``) still reads outside it.

The spans, outermost first (``serve/loop.py``, ``core/engine.py``,
``core/executor.py``):

  grf.submit            QueryLoop.submit: shape key, plan cache, enqueue
  grf.ticket            one ticket inside QueryLoop.pump (also one that
                        times out or fails)
    grf.bind            PreparedPlan.bind
    grf.execute         executor.execute
      grf.path.prepare  PathScanExec._prepare (cache hit or build)
      grf.traverse      the TraversalEngine call, compaction included
        grf.compact     GRFusion.compact
      grf.path.to_batch paths/distances to a RelBatch, origin combine
      grf.finalize      ProjectExec/AggregateExec result assembly

and the named scopes inside the jitted sweeps (``core/traversal.py``), which
the device ops' metadata carries: ``grf.bfs.hop`` (one BFS hop),
``grf.bfs.block`` (its scatter over one edge block) and ``grf.enum.hop``
(one expansion of ``enumerate_paths``).
"""
from __future__ import annotations

import jax

__all__ = ["span", "to_host"]

SYNC_PREFIX = "host_sync."


def span(name: str):
    """A host span named ``name`` (a constant starting ``grf.``)."""
    return jax.profiler.TraceAnnotation(name)


def to_host(x, site: str, events):
    """Block on ``x``, return it as host numpy, count one read at ``site``.

    Pass a tuple or list, not a dict: a dict comes back in sorted key order.
    """
    events[SYNC_PREFIX + site] += 1
    return jax.device_get(x)
