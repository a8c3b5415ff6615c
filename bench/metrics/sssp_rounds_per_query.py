"""Rounds the ``xla_coo`` SSSP ran per answered query: the delta of
``TraversalEngine.stats["sssp_rounds_xla_coo"]`` (summed on the device,
read when ``stats`` is) over the queries answered in the window. A query
runs until no distance falls, so this is the depth of its shortest-path
tree plus the round that finds nothing left to lower.
"""
LAYER = "xla_coo sssp"
MOVES = "queries_per_s"
COUNTER = "traversal.sssp_rounds_xla_coo"


def read(window):
    rounds = window.counters.get(COUNTER, 0)
    n = len(window.finished)
    return rounds / n if rounds and n else None
