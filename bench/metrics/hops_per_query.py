"""Hops the ``xla_coo`` sweep ran per answered query: the delta of
``TraversalEngine.stats["hops_xla_coo"]`` (the jitted BFS returns its final
hop count, summed on the host only when ``stats`` is read) over the queries
answered in the window.
"""
LAYER = "xla_coo sweep"
MOVES = "queries_per_s"
COUNTER = "traversal.hops_xla_coo"


def read(window):
    hops = window.counters.get(COUNTER, 0)
    n = len(window.finished)
    return hops / n if hops and n else None
