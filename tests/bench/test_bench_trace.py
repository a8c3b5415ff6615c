"""The trace reduction: on hand-made events whose answers are known, and on
a small trace recorded on a TPU v5e (``data/``)."""
import json
from pathlib import Path

import numpy as np
import pytest

import tiny_tree  # noqa: F401  (puts the benchmark on the path)
from bench.trace import TraceEvents, summarize

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns


def test_union_modules_and_labelled_gaps():
    dev = "/device:TPU:0"
    ev = TraceEvents(
        ops=[
            (dev, "jit_bfs(3)", "scatter", 10 * MS, 20 * MS),
            (dev, "jit_bfs(3)", "fusion", 25 * MS, 10 * MS),  # overlaps: union 10-35
            (dev, "jit_enumerate_paths(7)", "gather", 60 * MS, 10 * MS),
            (dev, "jit_x", "early", 0, 2 * MS),  # before the window: ignored
        ],
        modules=[(dev, "jit_bfs", 10 * MS, 25 * MS),
                 (dev, "jit_enumerate_paths", 60 * MS, 10 * MS)],
        spans=[("bench.window", 5 * MS, 105 * MS),
               ("bench.pump", 5 * MS, 50 * MS),
               ("bench.wait", 50 * MS, 58 * MS),
               ("bench.pump", 58 * MS, 105 * MS)],
    )
    s = summarize(ev)
    assert s.window_s == pytest.approx(0.100)
    assert s.busy_s == pytest.approx(0.035)  # 10-35 and 60-70
    assert s.idle_pct() == pytest.approx(65.0)
    assert s.module_s == pytest.approx({"jit_bfs": 0.025, "jit_enumerate_paths": 0.010})
    assert s.top_ops[0] == ("jit_bfs/scatter", pytest.approx(0.020))
    # gaps: 5-10 (pump), 35-60 (pump 35-50, wait 50-58, pump 58-60), 70-105 (pump)
    totals = dict(g for g in s.idle_gaps if g[0].endswith("(all gaps)"))
    assert totals == pytest.approx({"pump (all gaps)": 0.065})
    longest = [g for g in s.idle_gaps if g[0].endswith("(one gap)")]
    assert longest[0] == ("pump (one gap)", pytest.approx(0.035))


def test_ops_without_a_module_take_the_module_around_them():
    from bench.trace import _attribute

    dev = "/device:TPU:0"
    ops = _attribute([(dev, "", "%fusion.1", 62 * MS, MS), (dev, "", "%copy", 80 * MS, MS)],
                     [(dev, "jit_enumerate_paths", 60 * MS, 10 * MS)])
    assert [o[1] for o in ops] == ["jit_enumerate_paths", ""]


def test_no_device_events_reads_nothing():
    s = summarize(TraceEvents(spans=[("bench.window", 0, 10 * MS)]))
    assert s.idle_pct() is None and s.module_s == {}


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_recorded_trace(name):
    """Each recorded trace keeps, beside its events, the numbers the
    reduction gave when it was recorded."""
    rec = json.loads((DATA / name).read_text())
    s = summarize(TraceEvents.from_json(rec["events"]))
    want = rec["summary"]
    assert s.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert s.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert s.module_s == pytest.approx(want["module_s"], rel=1e-9)
    assert 0.0 < s.busy_s <= s.window_s
    assert [x[0] for x in s.idle_gaps] == [x[0] for x in want["idle_gaps"]]
    assert [x[1] for x in s.idle_gaps] == pytest.approx([x[1] for x in want["idle_gaps"]])
    # the union of op intervals, counted again on a 1 us grid
    lo, hi = next((a, b) for n, a, b in rec["events"]["spans"] if n == "bench.window")
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _plane, _m, _n, start, dur in rec["events"]["ops"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(-(-(b - lo) // 1000))] = True
    assert abs(grid.sum() * 1e-6 - s.busy_s) < 1e-6 * 2 * len(rec["events"]["ops"])
