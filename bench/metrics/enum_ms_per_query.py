"""Device time of path enumeration per answered query: the summed duration
of the ``jit_enumerate_paths`` XLA module (``core/traversal.py``
``enumerate_paths``) in the traced window, over the queries answered in it.
"""
LAYER = "path enumeration"
MOVES = "p95_ms"
MODULE = "jit_enumerate_paths"


def read(window):
    if window.trace is None or MODULE not in window.trace.module_s:
        return None
    n = len(window.finished)
    return 1e3 * window.trace.module_s[MODULE] / n if n else None
