"""Device time of the ``xla_coo`` sweep per hop it ran: the summed duration
of the ``jit_bfs`` XLA module in the traced window over the delta of
``TraversalEngine.stats["hops_xla_coo"]``. With ``hops_per_query`` it
splits ``sweep_ms_per_query`` into how many hops and what each costs.
"""
LAYER = "xla_coo sweep"
MOVES = "queries_per_s"
MODULE = "jit_bfs"
COUNTER = "traversal.hops_xla_coo"


def read(window):
    hops = window.counters.get(COUNTER, 0)
    if window.trace is None or MODULE not in window.trace.module_s or not hops:
        return None
    return 1e3 * window.trace.module_s[MODULE] / hops
