"""Host wall time per executed ticket inside ``QueryLoop.pump``: plan
binding, the executor and the device work it waits for, over the pumps that
served a ticket (the benchmark's ``bench.pump`` span, on the host clock).
"""
LAYER = "plan, bind and executor"
MOVES = "p95_ms"


def read(window):
    served = sum(len(p.served) for p in window.pumps)
    busy = sum(p.end - p.start for p in window.pumps if p.served)
    return 1e3 * busy / served if served else None
