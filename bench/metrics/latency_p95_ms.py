"""95th percentile (nearest rank) of the latency of every request of the
window, timed from when it was due; a failed or missing request misses any
limit (host clock). The tail of an open loop under host stalls: one stall
of a tenth of a second queues the requests behind it.
"""
from bench.window import nearest_rank

LAYER = "serve loop"
MOVES = "p50_ms"


def read(window):
    lat = window.latencies_ms()
    return nearest_rank(lat, 95.0) if lat else None
