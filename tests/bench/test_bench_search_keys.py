"""The reachability cell's key chooser on a tiny Graph500 graph: 64 search
keys a seed, the first eligible ones in the seed's order, stratified by
their 2-hop edge count and asked in a Latin schedule, one root a request,
each target at its stated distance (checked by plain loops)."""
from collections import Counter, deque

import numpy as np
import pytest

import tiny_tree
from bench import reference, traffic
from bench.registry import Registry

SEEDS = [7, 2**35 + 1]
CLIENTS = 8


def _deployment(seed, scale=10):
    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config("graph500-s21"), scale=scale)
    return reg, reg.generator(cfg["generator"]).generate(cfg, seed)


def _mix(reg, **keys):
    mix = reg.traffic("reach_p2p8")
    mix["keys"] = dict(mix["keys"], **keys)
    mix["arrivals"] = dict(mix["arrivals"], clients=CLIENTS)
    return mix


def _bfs(nbrs, root):
    dist, todo = {root: 0}, deque([root])
    while todo:
        u = todo.popleft()
        for v in nbrs[u]:
            if v not in dist:
                dist[v] = dist[u] + 1
                todo.append(v)
    return dist


@pytest.fixture(scope="module", params=SEEDS)
def drawn(request):
    """The workload of one seed, with what plain loops say it must be: the
    first 64 eligible keys in the seed's order, and each key's stratum and
    rank by its 2-hop edge count."""
    seed = request.param
    reg, dep = _deployment(seed)
    mix = _mix(reg)
    wl = traffic.make(reg, mix, dep, seed, 51.0)
    spec = mix["keys"]
    V = dep.n_vertices
    nbrs = [[] for _ in range(V)]
    for a, b in zip(dep.edge["src"].tolist(), dep.edge["dst"].tolist()):
        nbrs[a].append(b)
        nbrs[b].append(a)
    degree = [len(n) for n in nbrs]  # each edge counted at both ends
    rng = np.random.default_rng(np.random.SeedSequence([seed, 2]))  # traffic.make's
    eligible = rng.permutation([v for v in range(V) if degree[v] >= spec["min_degree"]])
    needed = set(spec["pattern"]) | {1}
    keys, dist = [], {}
    for v in eligible.tolist():
        d = _bfs(nbrs, v)
        if needed <= set(d.values()):
            keys.append(v)
            dist[v] = d
            if len(keys) == spec["roots"]:
                break
    two_hop = [sum(degree[u] for u, k in dist[v].items() if k <= 2) for v in keys]
    ranked = sorted(range(len(keys)), key=lambda i: (two_hop[i], i))
    m = len(keys) // CLIENTS
    place = {keys[i]: divmod(pos, m) for pos, i in enumerate(ranked)}  # (stratum, rank)
    return dict(seed=seed, reg=reg, dep=dep, mix=mix, wl=wl, keys=keys,
                dist=dist, degree=degree, place=place)


def _rounds(wl):
    """Round j: the j-th request of every client."""
    return [[s[j] for s in wl.streams] for j in range(len(wl.streams[0]))]


def test_draw_is_deterministic_for_a_seed(drawn):
    again = traffic.make(drawn["reg"], drawn["mix"], drawn["dep"], drawn["seed"], 51.0)
    assert again.streams == drawn["wl"].streams and again.warmup == drawn["wl"].warmup
    other = traffic.make(drawn["reg"], drawn["mix"], drawn["dep"], drawn["seed"] + 1, 51.0)
    assert other.streams != drawn["wl"].streams


def test_keys_are_the_first_eligible_in_the_seeds_order(drawn):
    wl = drawn["wl"]
    asked = {p["src"] for s in wl.streams for p in s}
    assert asked == set(drawn["keys"])
    assert wl.warmup[0]["src"] == drawn["keys"][0]
    assert drawn["dist"][drawn["keys"][0]][wl.warmup[0]["dst"]] == 1


def test_keys_are_64_distinct_of_min_degree(drawn):
    keys = {p["src"] for s in drawn["wl"].streams for p in s}
    assert len(keys) == 64
    assert all(drawn["degree"][k] >= drawn["mix"]["keys"]["min_degree"] for k in keys)


def test_targets_lie_at_their_stated_distance(drawn):
    wl, pattern = drawn["wl"], drawn["mix"]["keys"]["pattern"]
    roots = sorted({p["src"] for s in wl.streams for p in s})
    d = reference.hop_distances(reference.Adjacency(drawn["dep"], reverse=True),
                                drawn["dep"].n_vertices, roots, 8)
    lane = {r: i for i, r in enumerate(roots)}
    for j, rnd in enumerate(_rounds(wl)):
        for c, p in enumerate(rnd):
            s = (c + j) % CLIENTS
            assert d[lane[p["src"]], p["dst"]] == pattern[(s + 3 * j) % len(pattern)]


def test_strata_and_ranks_follow_two_hop_edge_counts(drawn):
    place = drawn["place"]
    for j, rnd in enumerate(_rounds(drawn["wl"])):
        for c, p in enumerate(rnd):
            s = (c + j) % CLIENTS
            assert place[p["src"]] == (s, (s + j) % CLIENTS)


def test_every_round_asks_each_stratum_rank_and_pattern_entry_once(drawn):
    place, dist = drawn["place"], drawn["dist"]
    pattern = drawn["mix"]["keys"]["pattern"]
    for rnd in _rounds(drawn["wl"]):
        strata = sorted(place[p["src"]][0] for p in rnd)
        ranks = sorted(place[p["src"]][1] for p in rnd)
        hops = sorted(dist[p["src"]][p["dst"]] for p in rnd)
        assert strata == ranks == list(range(CLIENTS))
        assert hops == sorted(pattern)


def test_eight_rounds_ask_every_key_once_and_pair_strata_with_pattern(drawn):
    place, dist = drawn["place"], drawn["dist"]
    pattern = drawn["mix"]["keys"]["pattern"]
    rounds = _rounds(drawn["wl"])
    pairs = Counter((s, k) for s in range(CLIENTS) for k in pattern)
    for j0 in range(len(rounds) - 7):
        reqs = [p for rnd in rounds[j0:j0 + 8] for p in rnd]
        assert sorted(p["src"] for p in reqs) == sorted(drawn["keys"])
        assert Counter((place[p["src"]][0], dist[p["src"]][p["dst"]])
                       for p in reqs) == pairs


@pytest.mark.parametrize("scale, keys", [
    (5, {}),  # 32 vertices: fewer than 64 keys
    (10, {"roots": 60}),  # 60 keys fill no 8 strata alike
])
def test_chooser_refuses_what_it_cannot_stratify(scale, keys):
    reg, dep = _deployment(2**35 + 1, scale=scale)
    with pytest.raises(ValueError):
        traffic.make(reg, _mix(reg, **keys), dep, 3, 51.0)
