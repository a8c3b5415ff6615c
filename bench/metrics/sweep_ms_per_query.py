"""Device time of the ``xla_coo`` sweep per answered query: the summed
duration of the ``jit_bfs`` XLA module (``core/traversal.py`` ``bfs``, the
name ``TraversalEngine`` jits it under) in the traced window, over the
queries answered in it.
"""
LAYER = "xla_coo sweep"
MOVES = "queries_per_s"
MODULE = "jit_bfs"


def read(window):
    if window.trace is None or MODULE not in window.trace.module_s:
        return None
    n = len(window.finished)
    return 1e3 * window.trace.module_s[MODULE] / n if n else None
