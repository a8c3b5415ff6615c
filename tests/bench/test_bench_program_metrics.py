"""The readers of the program's own counters and spans: the three per-layer
metrics on hand-built windows, the span reduction (``bench/program_spans.py``)
on hand-made events, and both on the tiny cells on the CPU."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

import tiny_tree
from bench import program_spans
from bench.registry import Registry
from bench.trace import Summary, TraceEvents, read_xplane, summarize
from bench.window import Drive, Request, Window

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000  # ns
DEV = "/device:TPU:0"


def _window(counters, n_finished=4, module_s=None):
    reqs = [Request(params={}, due=0.0,
                    ticket=SimpleNamespace(done_us=0.5e6, status="done"))
            for _ in range(n_finished)]
    trace = None if module_s is None else Summary(1.0, 2.0, module_s, [], [], 10)
    return Window(drive=Drive(0.0, 1.0, 1.0, reqs, []), in_window=reqs, missing=0,
                  counters=counters, trace=trace)


def _read(name, window):
    return Registry(tiny_tree.REPO).metric(name).read(window)


SYNCS = {"events.host_sync.finalize": 8, "events.host_sync.delta_check": 4,
         "events.traversal_faults": 5, "loop.executed": 4}


@pytest.mark.parametrize("name, window, value", [
    ("host_syncs_per_query", _window(SYNCS), 3.0),
    ("host_syncs_per_query", _window({"loop.executed": 4}), None),
    ("host_syncs_per_query", _window({"events.host_sync.finalize": 4}), None),
    ("hops_per_query", _window({"traversal.hops_xla_coo": 12}), 3.0),
    ("hops_per_query", _window({"traversal.backend_xla_coo": 4}), None),
    ("hops_per_query", _window({"traversal.hops_xla_coo": 12}, n_finished=0), None),
    ("sweep_ms_per_hop", _window({"traversal.hops_xla_coo": 4}, module_s={"jit_bfs": 6.0}),
     1500.0),
    ("sweep_ms_per_hop", _window({}, module_s={"jit_bfs": 6.0}), None),
    ("sweep_ms_per_hop", _window({"traversal.hops_xla_coo": 4}), None),
    ("sweep_ms_per_hop", _window({"traversal.hops_xla_coo": 4}, module_s={}), None),
])
def test_reader(name, window, value):
    assert _read(name, window) == value


def test_reduce_times_spans_and_the_idle_under_them():
    ev = TraceEvents(
        ops=[(DEV, "jit_bfs", "%a", 25 * MS, 10 * MS),
             (DEV, "jit_bfs", "%b", 45 * MS, 3 * MS)],
        spans=[("bench.window", 0, 100 * MS)],
    )
    program = program_spans.Program(
        spans=[("grf.ticket", 10 * MS, 50 * MS), ("grf.bind", 10 * MS, 12 * MS),
               ("grf.execute", 12 * MS, 50 * MS), ("grf.traverse", 20 * MS, 40 * MS),
               ("grf.ticket", 150 * MS, 160 * MS)],  # after the window
        scoped_ops=[(DEV, "grf.bfs.hop", 25 * MS, 10 * MS),
                    (DEV, "grf.bfs.hop", 30 * MS, 5 * MS),
                    (DEV, "grf.bfs.block", 26 * MS, 4 * MS)],
    )
    out = program_spans.reduce(ev, program)
    want = {  # count, span_s, self_s, idle_s, idle_under (ms)
        "grf.ticket": (1, 40, 0, 27, 0),
        "grf.bind": (1, 2, 2, 2, 2),
        "grf.execute": (1, 38, 18, 25, 15),
        "grf.traverse": (1, 20, 20, 10, 10),
    }
    assert set(out["spans"]) == set(want)
    for name, (count, span, own, idle, under) in want.items():
        got = out["spans"][name]
        assert got["count"] == count
        assert [got[k] * 1e3 for k in ("span_s", "self_s", "idle_s", "idle_under")] \
            == pytest.approx([span, own, idle, under])
    assert out["scope_s"] == pytest.approx({"grf.bfs.hop": 0.010, "grf.bfs.block": 0.004})


@pytest.mark.parametrize("name", sorted(p.name for p in DATA.glob("*.json")))
def test_reduce_leaves_the_benchmark_summary_alone(name):
    rec = json.loads((DATA / name).read_text())
    ev = TraceEvents.from_json(rec["events"])
    before = summarize(ev)
    lo, hi = next((a, b) for n, a, b in ev.spans if n == "bench.window")
    out = program_spans.reduce(ev, program_spans.Program(
        spans=[("grf.ticket", lo, (lo + hi) / 2)]))
    assert 0 < out["spans"]["grf.ticket"]["idle_s"] <= (hi - lo) / 2 * 1e-9
    assert summarize(ev) == before


def test_program_spans_stay_out_of_the_benchmark_spans(tmp_path):
    import jax
    import numpy as np

    from repro.core.engine import GRFusion
    from repro.core.query import P, Query, param

    eng = GRFusion()
    eng.create_table("V", {"vid": np.arange(4, dtype=np.int32)})
    eng.create_table("E", {"src": np.array([0, 1, 2], np.int32),
                           "dst": np.array([1, 2, 3], np.int32)})
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst")
    PS = P("PS")
    q = (Query().from_paths("G", "PS").where(PS.start.id == param("src"))
         .hint_max_length(2).select(end=PS.end.id))
    loop = eng.serving_loop()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.submit"):
                loop.submit(q, src=0)
            with jax.profiler.TraceAnnotation("bench.pump"):
                loop.drain()
    finally:
        jax.profiler.stop_trace()
    (path,) = sorted(tmp_path.rglob("*.xplane.pb"))
    assert {n for n, _, _ in read_xplane(path).spans} == \
        {"bench.window", "bench.submit", "bench.pump"}
    names = {n for n, _, _ in program_spans.read_program(path).spans}
    assert {"grf.submit", "grf.ticket", "grf.bind", "grf.execute", "grf.path.prepare",
            "grf.traverse", "grf.path.to_batch", "grf.finalize"} <= names


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_tree.build(tmp_path_factory.mktemp("bench"), rate=50.0)


def _traced(root, cell, capsys, monkeypatch):
    import jax

    from bench import run

    monkeypatch.setattr(run, "require_accelerator", lambda chips: jax.devices()[:chips])
    rc = program_spans.main(["--workload", cell, "--seed", str(2**31 + 5),
                             "--seconds", "1", "--trace", "1"], root=root)
    captured = capsys.readouterr()
    spans = next(line for line in captured.err.splitlines()
                 if line.startswith("program spans: "))
    return rc, json.loads(captured.out.strip().splitlines()[-1]), \
        json.loads(spans[len("program spans: "):])


def test_tiny_neighbourhood_cell_reports_its_syncs_and_spans(root, capsys, monkeypatch):
    rc, out, found = _traced(root, tiny_tree.NBR2, capsys, monkeypatch)
    assert rc == 0 and out["correct"] is True
    # delta check, overflow, result columns: one path per ticket
    assert out["metrics"]["host_syncs_per_query"]["value"] == 3.0
    spans = found["spans"]
    assert spans["grf.ticket"]["count"] >= 1
    assert spans["grf.ticket"]["span_s"] >= spans["grf.execute"]["span_s"] > 0


def test_tiny_reach_cell_reports_its_hops(root, capsys, monkeypatch):
    rc, out, found = _traced(root, tiny_tree.REACH, capsys, monkeypatch)
    assert rc == 0 and out["correct"] is True
    # targets 2, 3 or 4 hops out: one BFS of that many hops per answer
    assert 2.0 <= out["metrics"]["hops_per_query"]["value"] <= 4.0
    assert found["spans"]["grf.traverse"]["count"] >= 1
