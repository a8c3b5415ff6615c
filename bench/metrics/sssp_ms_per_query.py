"""Device time of the ``xla_coo`` SSSP per answered query: the summed
duration of the ``jit_sssp`` XLA module (``core/traversal.py``
``sssp_rounds``, the name ``TraversalEngine`` jits it under; its rounds and
the parent pass) in the traced window, over the queries answered in it.
"""
LAYER = "xla_coo sssp"
MOVES = "queries_per_s"
MODULE = "jit_sssp"


def read(window):
    if window.trace is None or MODULE not in window.trace.module_s:
        return None
    n = len(window.finished)
    return 1e3 * window.trace.module_s[MODULE] / n if n else None
