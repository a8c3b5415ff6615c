"""Share of the ``xla_coo`` sweep's hops that ran frontier-sparse: the
delta of ``TraversalEngine.stats["hops_xla_coo_sparse"]`` over that of
``hops_xla_coo``. None where no hop ran, or where the program counts no
sparse hops (it predates the sparse form, or none ran: the window keeps
only counters that moved).
"""
LAYER = "xla_coo sweep"
MOVES = "queries_per_s"
COUNTER = "traversal.hops_xla_coo"
SPARSE = "traversal.hops_xla_coo_sparse"


def read(window):
    hops = window.counters.get(COUNTER, 0)
    sparse = window.counters.get(SPARSE, 0)
    return sparse / hops if hops and sparse else None
