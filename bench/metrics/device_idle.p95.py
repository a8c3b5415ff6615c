"""Share of the traced window in which no op ran on the device, in the
open-loop neighbourhood cell: what the host path leaves of the chip.

Reduced from the profiler trace (``bench/trace.py``): 1 - the union of the
TPU op intervals over the ``bench.window`` span.
"""
LAYER = "device"
MOVES = "p50_ms"


def read(window):
    return window.trace.idle_pct() if window.trace is not None else None
