"""Share of the ``xla_coo`` SSSP's rounds that relaxed only the changed
vertices' CSR rows (the frontier-sparse form): the delta of
``TraversalEngine.stats["sssp_rounds_xla_coo_sparse"]`` over that of
``sssp_rounds_xla_coo``. None where no round ran, or where the program
counts none (it predates the counters: the window keeps only counters that
moved).
"""
LAYER = "xla_coo sssp"
MOVES = "queries_per_s"
COUNTER = "traversal.sssp_rounds_xla_coo"
SPARSE = "traversal.sssp_rounds_xla_coo_sparse"


def read(window):
    rounds = window.counters.get(COUNTER, 0)
    sparse = window.counters.get(SPARSE, 0)
    return sparse / rounds if rounds and sparse else None
