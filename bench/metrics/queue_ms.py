"""Mean wait in the serving loop: from when a request was due to the start
of the ``QueryLoop.pump`` that served it (host clock).
"""
LAYER = "serve loop"
MOVES = "p95_ms"


def read(window):
    waits = [p.start - r.due for p in window.pumps for r in p.served]
    return 1e3 * sum(waits) / len(waits) if waits else None
