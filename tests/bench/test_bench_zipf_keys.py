"""The neighbourhood cell's start draw on a tiny urand graph: scrambled
Zipf ranks whose holders are drawn from the seed, each rank held by a
vertex of the same answer size for every seed (sizes checked by a plain
loop), and the tail reader that reports the cell's 95th percentile."""
import numpy as np
import pytest

import tiny_tree
from bench import traffic
from bench.graphdata import OPS
from bench.keys import zipf_scrambled as zipf
from bench.registry import Registry
from bench.window import Drive, Request, Window

SEEDS = [7, 2**35 + 1]


def _deployment(seed, scale=10):
    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config("gap-urand-s22"), scale=scale)
    return reg, reg.generator(cfg["generator"]).generate(cfg, seed)


def _plain_sizes(dep, query):
    """Per vertex: walks of one and two passing non-loop edges from it."""
    attr, op, value = query["edge_predicate"]
    nbrs = [[] for _ in range(dep.n_vertices)]
    for a, b, x in zip(dep.edge["src"].tolist(), dep.edge["dst"].tolist(),
                       dep.edge[attr].tolist()):
        if a != b and OPS[op](x, value):
            nbrs[a].append(b)
            nbrs[b].append(a)
    return np.array([len(n) + sum(len(nbrs[u]) for u in n) for n in nbrs])


@pytest.fixture(scope="module", params=SEEDS)
def graph(request):
    reg, dep = _deployment(request.param)
    return request.param, reg, dep, reg.traffic("nbr2_open")


def _rng(seed):
    return np.random.default_rng(np.random.SeedSequence([seed, 2]))


@pytest.mark.parametrize("case", ["sizes", "permutation", "deterministic",
                                  "same_size_each_rank", "seed_moves_vertices",
                                  "streams"])
def test_zipf_draw(graph, case):
    seed, reg, dep, mix = graph
    query = mix["query"]
    sizes = zipf.answer_sizes(dep, query)
    holder = zipf.holders(dep, query, _rng(seed))
    if case == "sizes":
        assert np.array_equal(sizes, _plain_sizes(dep, query))
    elif case == "permutation":
        assert np.array_equal(np.sort(holder), np.arange(dep.n_vertices))
    elif case == "deterministic":
        assert np.array_equal(holder, zipf.holders(dep, query, _rng(seed)))
    elif case == "same_size_each_rank":
        # another seed's order over the same graph: every rank keeps its size
        other = zipf.holders(dep, query, _rng(seed + 1))
        assert np.array_equal(sizes[holder], sizes[other])
    elif case == "seed_moves_vertices":
        other = zipf.holders(dep, query, _rng(seed + 1))
        assert np.mean(holder != other) > 0.5
    elif case == "streams":
        wl = traffic.make(reg, mix, dep, seed, 2.0)
        n = int(round(mix["arrivals"]["rate_per_s"] * 2.0))
        assert [len(s) for s in wl.streams] == [n]
        assert len(wl.warmup) == mix["warmup"]
        ranks = np.argsort(holder)[[p["src"] for p in wl.streams[0]]]
        want = zipf.ranks(dep.n_vertices, mix["keys"]["theta"], n, np.random.default_rng(0))
        assert np.array_equal(np.sort(ranks), np.sort(want))


def _window(latencies_ms, failed=()):
    reqs = []
    for i, ms in enumerate(latencies_ms):
        ticket = type("T", (), {})()
        ticket.status = "failed" if i in failed else "done"
        ticket.done_us = (1.0 + ms * 1e-3) * 1e6
        ticket.result = type("R", (), {"degraded_backend": None, "overflow": False})()
        reqs.append(Request(params={}, due=1.0, ticket=ticket))
    return Window(drive=Drive(0.0, 2.0, 2.0, reqs, []), in_window=reqs, missing=0,
                  grace_s=60.0)


@pytest.mark.parametrize("latencies,failed,want", [
    ([], (), None),
    ([5.0], (), 5.0),
    (list(range(1, 101)), (), 95.0),
    (list(range(100, 0, -1)), (), 95.0),
    # failed requests count as answered at the grace limit: 61 s from due
    (list(range(1, 21)), (0, 1), 61_000.0),
])
def test_latency_p95_reader(latencies, failed, want):
    mod = Registry(tiny_tree.REPO).metric("latency_p95_ms")
    got = mod.read(_window(latencies, failed))
    assert got == pytest.approx(want) if want is not None else got is None
