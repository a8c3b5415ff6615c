"""``BENCHMARK.json`` against the benchmark contract's rules, and the harness
finding a new configuration, traffic mix and metric by name alone."""
import json
import re

import pytest

import tiny_tree
from bench.registry import Registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(tiny_tree.REPO / "BENCHMARK.json") as f:
        return json.load(f)


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_benchmark_json_keeps_the_contract(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= int(spec["run_seconds"]) <= 51
    for p in spec["paths"]:
        assert (tiny_tree.REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/configs/")
        cfg = json.load(open(tiny_tree.REPO / c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        names.add(c["name"])
    cells = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and _line(w["why"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in {(c, t) for _, c, t in cells}
        cells.add((w["name"], w["config"], w["traffic"]))
    cell_names = {c for c, _, _ in cells}
    metrics = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in spec["end_to_end"])
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", [])) <= cell_names
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and _line(m["layer"])
    reg = Registry(tiny_tree.REPO)
    for cell in cell_names:  # every cell reports setup_s, one more, one layer
        c = reg.cell(cell)
        assert "setup_s" in {m["name"] for m in c.end_to_end}
        assert len(c.end_to_end) >= 2 and c.per_layer


def test_metric_files_name_their_layer_and_end_to_end_metric(spec):
    reg = Registry(tiny_tree.REPO)
    for m in spec["per_layer"]:
        mod = reg.metric(m["name"])
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])


NEW_FILES = {
    "metrics/answered_per_pump.py": '''
LAYER = "serve loop"
MOVES = "p95_ms"


def read(window):
    pumps = [p for p in window.pumps if p.served]
    return sum(len(p.served) for p in pumps) / len(pumps) if pumps else None
''',
    "queries/first_hop.py": '''
import numpy as np

from bench.reference import Adjacency


def build(q):
    from repro.core.query import P, Query, param

    PS = P("PS")
    return (Query().from_paths("G", "PS").where(PS.start.id == param("src"))
            .hint_max_length(1).select(end=PS.end.id, length=PS.length))


def answers(dep, q, params):
    adj = Adjacency(dep)
    out = []
    for p in params:
        ends = np.sort(adj.expand(np.asarray([p["src"]]))[1])
        ends = ends[ends != p["src"]]  # paths are simple: no self-loop
        out.append(np.stack([ends, np.ones_like(ends)], 1))
    return out


def served(q, result):
    ends = np.sort(np.asarray(result.columns["end"], np.int64))
    return np.stack([ends, np.ones_like(ends)], 1)
''',
    "keys/uniform_vertex.py": '''
def draw(spec, query, dep, rng, warm_rng, n_streams, length, n_warm):
    def one(size, r):
        return [{"src": int(v)} for v in r.integers(0, dep.n_vertices, size)]
    return [one(length, rng) for _ in range(n_streams)], one(n_warm, warm_rng)
''',
    "arrivals/burst.py": '''
import time

from bench.window import Drive, Pump, Request


def shape(spec, seconds):
    return 1, int(spec["size"])


def drive(loop, query, wl, seconds, span, grace_s):
    t0 = time.monotonic()
    reqs = [Request(params=p, due=t0) for p in wl.streams[0]]
    for r in reqs:
        r.ticket = loop.submit(query, **r.params)
    owner = {id(r.ticket): r for r in reqs}
    start = time.monotonic()
    done = loop.drain()
    pumps = [Pump(start, time.monotonic(), [owner[id(t)] for t in done])]
    return Drive(t0, time.monotonic(), time.monotonic(), reqs, pumps)


def in_window(d):
    return d.requests


def missing(d, window):
    return sum(1 for r in window if not r.answered)
''',
    "controls/no_edges.py": '''
import dataclasses


def broken(dep, spec):
    return dataclasses.replace(dep, edge={k: v[:0] for k, v in dep.edge.items()})
''',
}


def test_new_files_are_found_by_name(tmp_path, capsys):
    """A configuration, a traffic mix with a query kind, a key chooser and
    an arrival process of its own, a control and a metric, each added as a
    new file plus entries, editing no file that was there: the run and the
    control pick all of them up."""
    from bench import control

    root = tiny_tree.build(tmp_path)  # adds two configs, two mixes, two cells
    bench = root / "bench"
    for rel, text in NEW_FILES.items():
        (bench / rel).write_text(text.lstrip())
    cfg = json.loads((bench / "configs" / "ur-tiny.json").read_text())
    cfg.update(name="ur-tiny-b", control={"break": "no_edges"})
    (bench / "configs" / "ur-tiny-b.json").write_text(json.dumps(cfg))
    (bench / "traffic" / "hop1_burst.json").write_text(json.dumps(
        {"name": "hop1_burst", "query": {"kind": "first_hop"},
         "keys": {"kind": "uniform_vertex"},
         "arrivals": {"kind": "burst", "size": 24}, "warmup": 2}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "ur-tiny-b.hop1", "config": "ur-tiny-b",
                              "traffic": "hop1_burst", "chips": 1, "why": "test"})
    for m in spec["end_to_end"]:
        if m["name"] == "queries_per_s":
            m["workloads"].append("ur-tiny-b.hop1")
    spec["per_layer"].append(
        {"name": "answered_per_pump", "unit": "tickets", "better": "higher",
         "source": "program_counter", "layer": "serve loop", "moves": "queries_per_s",
         "workloads": [tiny_tree.NBR2, "ur-tiny-b.hop1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = Registry(root).cell("ur-tiny-b.hop1")
    assert cell.config["scale"] == 10 and cell.traffic["name"] == "hop1_burst"
    rc, out = tiny_tree.run_cell(root, "ur-tiny-b.hop1", seed=3, capsys=capsys)
    assert rc == 0 and out["correct"] is True and out["attempted"] == 24
    assert set(out["metrics"]) == {"setup_s", "queries_per_s", "peak_hbm_gib"}
    rc, out = tiny_tree.run_cell(root, "ur-tiny-b.hop1", seed=4, trace=1, capsys=capsys)
    assert rc == 0 and out["correct"] is True
    assert out["metrics"]["answered_per_pump"]["value"] == 24.0
    rc, out = tiny_tree.run_cell(root, tiny_tree.NBR2, seed=3, seconds=0.5,
                                 trace=1, capsys=capsys)
    assert rc == 0 and out["correct"] is True
    assert out["metrics"]["answered_per_pump"]["value"] >= 1.0
    assert {"service_ms", "queue_ms"} <= set(out["metrics"])
    assert "breakdown" in out and out["device"]["window_s"] > 0
    assert control.main(["--workload", "ur-tiny-b.hop1", "--seeds", "5"], root=root) == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["compared"] == 24 and line["wrong_answers"] > 0


def test_run_off_the_tpu_exits_nonzero_and_prints_no_result(capsys):
    from bench import run

    rc = run.main(["--workload", "gap-urand-s22.nbr2_open", "--seed", "1",
                   "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
