"""The Graph500 Kernel 3 cell at a tiny size on the CPU: its configuration,
generator, float32 reference and check, its control, and the readers of its
per-layer metrics."""
import dataclasses
import heapq
import json
from types import SimpleNamespace

import numpy as np
import pytest

import tiny_tree
from bench import control, reference, reference_sssp
from bench.generators import kronecker
from bench.reference import Adjacency
from bench.registry import Registry
from bench.trace import Summary
from bench.window import Drive, Request, Window

CELL = "graph500-k3-s18.sssp_p2p8"
TINY = "g5k3-tiny.sssp"
LAYER = ["sssp_ms_per_query", "sssp_rounds_per_query", "sparse_round_share",
         "sssp_roofline"]


def build_tree(root):
    """``tiny_tree``'s copy with one more cell: Kernel 3 at SCALE 10."""
    tiny_tree.build(root, clients=8)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "graph500-k3-s18.json").read_text())
    cfg.update(name="g5k3-tiny", scale=10)
    (bench / "configs" / "g5k3-tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "sssp_p2p8.json").read_text())
    mix["name"] = "sssp-tiny"
    (bench / "traffic" / "sssp-tiny.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": TINY, "config": "g5k3-tiny",
                              "traffic": "sssp-tiny", "chips": 1, "why": "CPU test cell"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if CELL in m.get("workloads", []):
            m["workloads"].append(TINY)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return build_tree(tmp_path_factory.mktemp("bench"))


def test_registry_loads_the_kernel3_cell():
    cell = Registry(tiny_tree.REPO).cell(CELL)
    assert cell.chips == 1
    assert cell.config["generator"] == "kronecker_k3" and cell.config["scale"] == 18
    assert cell.traffic["query"]["kind"] == "sssp"
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "queries_per_s",
                                                    "peak_hbm_gib"}
    assert {m["name"] for m in cell.per_layer} == {"device_idle.qps", *LAYER}


def test_kronecker_k3_draws_float32_weights_over_the_kronecker_edges():
    cfg = dict(Registry(tiny_tree.REPO).config("graph500-k3-s18"), scale=9)
    gen = Registry(tiny_tree.REPO).generator("kronecker_k3")
    seed = 2**33 + 7
    dep, again, other = gen.generate(cfg, seed), gen.generate(cfg, seed), gen.generate(cfg, seed + 1)
    w = dep.edge["weight"]
    assert w.dtype == np.float32 and w.min() >= 0.0 and w.max() < 1.0
    assert np.array_equal(w, again.edge["weight"])
    assert not np.array_equal(w, other.edge["weight"])
    k2 = kronecker.generate(cfg, seed)
    for col in ("eid", "src", "dst", "sel", "label"):
        assert np.array_equal(dep.edge[col], k2.edge[col])
    assert dep.n_vertices == 2**9 and not dep.directed


def _dijkstra_f32(n, src, dst, w, root, directed):
    adj = {}
    for a, b, x in zip(src, dst, w):
        adj.setdefault(int(a), []).append((int(b), x))
        if not directed:
            adj.setdefault(int(b), []).append((int(a), x))
    d = np.full(n, np.inf, np.float32)
    d[root] = 0.0
    pq = [(np.float32(0.0), root)]
    while pq:
        du, u = heapq.heappop(pq)
        if du > d[u]:
            continue
        for v, x in adj.get(u, ()):
            nd = np.float32(du + x)
            if nd < d[v]:
                d[v] = nd
                heapq.heappush(pq, (nd, v))
    return d


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("directed", [False, True])
def test_reference_matches_a_heapq_dijkstra(seed, directed):
    rng = np.random.default_rng(seed)
    n, e = 40, 120
    src, dst = rng.integers(0, n, e), rng.integers(0, n, e)
    w = rng.random(e, dtype=np.float32)
    w[rng.random(e) < 0.1] = 0.0
    from bench.graphdata import Deployment

    dep = Deployment(n_vertices=n, directed=directed, vertex={},
                     edge={"src": src.astype(np.int32), "dst": dst.astype(np.int32),
                           "weight": w})
    graph = reference_sssp.Weighted(Adjacency(dep), w)
    for root in range(0, n, 7):
        got = graph.distances(root)
        want = _dijkstra_f32(n, src, dst, w, root, directed)
        assert got.dtype == np.float32 and got.tobytes() == want.tobytes()


def _chain(n, directed=False):
    from bench.graphdata import Deployment

    return Deployment(n_vertices=n, directed=directed, vertex={},
                      edge={"src": np.arange(n - 1, dtype=np.int32),
                            "dst": np.arange(1, n, dtype=np.int32),
                            "weight": np.ones(n - 1, np.float32)})


def test_reference_keeps_one_graph_a_deployment_and_its_searches():
    dep = _chain(5)
    graph = reference_sssp.weighted(dep, "weight")
    assert reference_sssp.weighted(dep, "weight") is graph
    assert reference_sssp.weighted(_chain(5), "weight") is not graph
    dist, fired = graph.search(0)
    assert dist.tolist() == [0, 1, 2, 3, 4]
    assert fired == [1, 2, 2, 2, 1]  # the last round finds no distance falls
    assert graph.distances(0) is dist  # kept, not searched again


def test_key_work_counts_rounds_and_capped_relaxations():
    keys = Registry(tiny_tree.REPO).keys("search_keys_by_work")
    spec = {"round_cost": 1 / 64, "round_cap": 0.25}
    graph = reference_sssp.weighted(_chain(5), "weight")  # 8 slots, both ways
    work, rounds = keys.work(spec, graph, 0)
    assert rounds == 5
    assert work == pytest.approx(5 / 64 + 1 / 8 + 0.25 + 0.25 + 0.25 + 1 / 8)


def test_nearest_takes_each_target_its_nearest_unused_point():
    keys = Registry(tiny_tree.REPO).keys("search_keys_by_work")
    points = np.asarray([[0.0, 10], [1.0, 20], [1.1, 20], [5.0, 30], [9.0, 40]])
    targets = np.asarray([[1.0, 20], [1.0, 20], [9.0, 40]])
    got = keys.nearest(points, targets)
    assert got[2] == 4 and sorted(got[:2]) == [1, 2]
    assert len(set(got)) == 3


def test_work_draw_is_the_seeds_and_asks_every_stratum_each_round():
    from bench import traffic

    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config("graph500-k3-s18"), scale=10)
    mix = reg.traffic("sssp_p2p8")
    seed = 2**32 + 5
    dep = reg.generator(cfg["generator"]).generate(cfg, seed)
    wl = traffic.make(reg, mix, dep, seed, 20.0)
    again = traffic.make(reg, mix, dep, seed, 20.0)
    assert wl.streams == again.streams and wl.warmup == again.warmup
    keys = reg.keys("search_keys_by_work")
    graph = reference_sssp.weighted(dep, "weight")
    work = {p["src"]: keys.work(mix["keys"], graph, p["src"])[0]
            for s in wl.streams for p in s}
    assert len(work) == 64
    rank = {k: i for i, k in enumerate(sorted(work, key=work.get))}
    for j in range(8):  # round j asks one key of each stratum of 8
        assert sorted(rank[s[j]["src"]] // 8 for s in wl.streams) == list(range(8))
    # the check's reference answers are the draw's searches, kept
    searched = set(graph._dist)
    reg.query("sssp").answers(dep, mix["query"], [p for s in wl.streams for p in s])
    assert set(graph._dist) == searched


@pytest.fixture(scope="module")
def served():
    """A tiny Kernel 3 deployment in the engine, asked the cell's queries."""
    from bench import traffic
    from repro.core.engine import GRFusion

    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config("graph500-k3-s18"), scale=9)
    seed = 2**34 + 3
    dep = reg.generator(cfg["generator"]).generate(cfg, seed)
    eng = GRFusion(**cfg["engine"])
    eng.create_table("V", dep.vertex)
    eng.create_table("E", dep.edge)
    eng.create_graph_view("G", vertexes="V", edges="E", v_id="vid",
                          e_src="src", e_dst="dst", directed=dep.directed)
    mix = reg.traffic("sssp_p2p8")
    q = mix["query"]
    kind = reg.query(q["kind"])
    wl = traffic.make(reg, mix, dep, seed, 1.0)
    params = [s[j] for s in wl.streams for j in range(2)]
    loop = eng.serving_loop()
    tickets = [loop.submit(kind.build(q), **p) for p in params]
    loop.drain()
    assert all(t.status == "done" for t in tickets)
    return kind, dep, q, params, [t.result for t in tickets]


def test_served_answers_pass_the_check(served):
    kind, dep, q, params, results = served
    expected = kind.answers(dep, q, params)
    assert all(reference.same(kind, q, r, e) for r, e in zip(results, expected))
    assert all(r.count == 1 and r.columns["length"][0] >= 1 for r in results)


def _planted(result, fault):
    cols = {k: np.array(v, copy=True) for k, v in result.columns.items()}
    n = int(cols["length"][0])
    if fault == "distance_one_ulp":
        cols["distance"] = np.nextafter(cols["distance"], np.float32(np.inf))
    elif fault == "witness_edge_missing":
        cols["edges"][0, : n - 1] = cols["edges"][0, 1:n]
        cols["edges"][0, n - 1] = -1
        cols["length"] = cols["length"] - 1
    elif fault == "witness_wrong_end":
        cols["end"] = cols["end"] + 1
    return dataclasses.replace(result, columns=cols)


@pytest.mark.parametrize("fault", ["distance_one_ulp", "witness_edge_missing",
                                   "witness_wrong_end"])
def test_check_rejects_a_planted_fault(served, fault):
    kind, dep, q, params, results = served
    expected = kind.answers(dep, q, params)
    long = [i for i, r in enumerate(results) if r.columns["length"][0] >= 2]
    assert long
    for i in long:
        assert not reference.same(kind, q, _planted(results[i], fault), expected[i])


def test_check_rejects_a_witness_that_is_not_shortest(served):
    kind, dep, q, params, results = served
    expected = kind.answers(dep, q, params)
    r, e = results[0], expected[0]
    cols = {k: np.array(v, copy=True) for k, v in r.columns.items()}
    first = int(cols["edges"][0, 0])  # the witness's last hop, doubled back
    cols["edges"][0, 1:] = np.concatenate([[first, first], cols["edges"][0, 1:-2]])
    cols["length"] = cols["length"] + 2
    assert not reference.same(kind, q, dataclasses.replace(r, columns=cols), e)


def test_witness_is_walked_on_the_deployment_whose_distance_it_has(served):
    kind, dep, q, params, results = served
    broken = Registry(tiny_tree.REPO).control("one_direction").broken(
        dep, {"break": "one_direction", "guarantee": "direction"})
    expected = kind.answers(dep, q, params)
    kind.answers(broken, q, params)  # as the control runs it: answered last
    assert all(reference.same(kind, q, r, e) for r, e in zip(results, expected))
    # the same graph with its edge rows rolled: the same distances, and the
    # witness's rows name other edges, so the two judge it apart
    rolled = dataclasses.replace(dep, edge={k: np.roll(v, 1) for k, v in dep.edge.items()})
    kind.answers(rolled, q, params)
    i = next(i for i, r in enumerate(results) if r.columns["length"][0] >= 2)
    with pytest.raises(RuntimeError, match="no single answered deployment"):
        kind.served(q, results[i])
    del rolled, broken
    assert reference.same(kind, q, results[i], expected[i])


def test_clean_run_is_correct(root, capsys):
    rc, out = tiny_tree.run_cell(root, TINY, seed=2**33 + 1, capsys=capsys)
    assert rc == 0 and out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert set(out["metrics"]) == {"setup_s", "queries_per_s", "peak_hbm_gib"}


def test_traced_run_reads_the_sssp_counters(root, capsys):
    rc, out = tiny_tree.run_cell(root, TINY, seed=2**31 + 9, trace=1, capsys=capsys)
    assert rc == 0 and out["correct"] is True
    metrics = out["metrics"]
    assert metrics["sssp_rounds_per_query"]["value"] >= 2  # a target 2+ hops out
    assert 0 < metrics["sparse_round_share"]["value"] <= 1
    assert "sssp_roofline" not in metrics  # the CPU has no published peak


def test_control_fails_the_check_on_every_seed(root, capsys):
    assert control.main(["--workload", TINY, "--seeds", "21,22,23"], root=root) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 3
    for r in lines:
        assert r["compared"] > 0 and r["wrong_answers"] > 0


def _window(counters, n_finished=4, module_s=None):
    reqs = [Request(params={}, due=0.0,
                    ticket=SimpleNamespace(done_us=0.5e6, status="done"))
            for _ in range(n_finished)]
    trace = None if module_s is None else Summary(1.0, 2.0, module_s, [], [], 10)
    return Window(drive=Drive(0.0, 1.0, 1.0, reqs, []), in_window=reqs, missing=0,
                  counters=counters, trace=trace)


ROUNDS = {"traversal.sssp_rounds_xla_coo": 80, "traversal.sssp_rounds_xla_coo_sparse": 50}
REACHED = {"traversal.sssp_reached_slots": 10**9, "traversal.sssp_reached_vertices": 10**8}


@pytest.mark.parametrize("name, window, value", [
    ("sssp_ms_per_query", _window({}, module_s={"jit_sssp": 8.0}), 2000.0),
    ("sssp_ms_per_query", _window({}, module_s={"jit_bfs": 8.0}), None),
    ("sssp_ms_per_query", _window({}), None),
    ("sssp_ms_per_query", _window({}, n_finished=0, module_s={"jit_sssp": 8.0}), None),
    ("sssp_rounds_per_query", _window(ROUNDS), 20.0),
    ("sssp_rounds_per_query", _window({"traversal.hops_xla_coo": 9}), None),
    ("sparse_round_share", _window(ROUNDS), 0.625),
    ("sparse_round_share", _window({"traversal.sssp_rounds_xla_coo": 8}), None),
    ("sparse_round_share", _window({}), None),
    ("sssp_roofline", _window(REACHED), None),
    ("sssp_roofline", _window({}, module_s={"jit_sssp": 2.0}), None),
    ("sssp_roofline", _window(REACHED, module_s={"jit_bfs": 2.0}), None),
])
def test_reader(name, window, value):
    assert Registry(tiny_tree.REPO).metric(name).read(window) == value


def test_roofline_counts_the_answers_bytes_at_the_chips_peak():
    read = Registry(tiny_tree.REPO).metric("sssp_roofline").read
    window = _window(REACHED, module_s={"jit_sssp": 2.0})
    want = 100.0 * (8 * 10**9 + 12 * 10**8) / (2.0 * 819e9)
    assert read(window, device_kind="TPU v5 lite") == pytest.approx(want)
    assert read(window, device_kind="cpu") is None
