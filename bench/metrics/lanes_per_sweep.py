"""Query lanes per traversal-backend call: sources answered in the window
over the calls ``TraversalEngine`` made to a BFS/SSSP backend (the
``backend_<name>`` counters of ``TraversalEngine.stats``, as deltas over
the window). One lane per call means no batching across tickets.
"""
LAYER = "traversal engine admission"
MOVES = "queries_per_s"
CALLS = ("backend_xla_coo", "backend_pallas_frontier", "backend_sharded",
         "backend_reference")


def read(window):
    calls = sum(window.counters.get(f"traversal.{c}", 0) for c in CALLS)
    return len(window.finished) / calls if calls else None
