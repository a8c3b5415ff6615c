"""Blocking device-to-host reads per executed ticket: the summed deltas of
the engine's ``host_sync.<site>`` counters (each read of the served path
goes through ``repro.tracing.to_host``) over the tickets ``QueryLoop``
executed in the window. Every neighbourhood ticket takes one path, so the
reading is the same integer on every seed.
"""
LAYER = "plan, bind and executor"
MOVES = "p50_ms"
PREFIX = "events.host_sync."


def read(window):
    syncs = sum(v for k, v in window.counters.items() if k.startswith(PREFIX))
    executed = window.counters.get("loop.executed", 0)
    return syncs / executed if syncs and executed else None
