"""The ``xla_coo`` SSSP's share of the chip's HBM roofline, in %: the bytes
any exact SSSP must move for the window's answers over what the chip's
peak bandwidth moves in the ``jit_sssp`` module's device time.

The bytes count the answers, not the work: every reached vertex's out-slots
read once (destination int32, weight float32: 8 bytes a slot) and its CSR
offset read and its distance and parent written (12 bytes a vertex), from
the deltas of ``TraversalEngine.stats["sssp_reached_slots"]`` and
``["sssp_reached_vertices"]``. Redoing work cannot raise it; a
Bellman-Ford that relaxes each slot several times reads far below 100%.
None where the counters or the module are absent, or the chip has no
published peak (``bench/peaks.py``).
"""
LAYER = "xla_coo sssp"
MOVES = "queries_per_s"
MODULE = "jit_sssp"
SLOTS = "traversal.sssp_reached_slots"
VERTICES = "traversal.sssp_reached_vertices"
SLOT_BYTES, VERTEX_BYTES = 8, 12


def read(window, device_kind=None):
    import jax

    from bench.peaks import PEAKS

    slots = window.counters.get(SLOTS, 0)
    vertices = window.counters.get(VERTICES, 0)
    if window.trace is None or not window.trace.module_s.get(MODULE) or not slots:
        return None
    peak = PEAKS.get(device_kind or jax.devices()[0].device_kind)
    if peak is None:
        return None
    moved = SLOT_BYTES * slots + VERTEX_BYTES * vertices
    return 100.0 * moved / (window.trace.module_s[MODULE] * peak["hbm_bytes_per_s"])
