"""The benchmark's deployment generators at a small size on the CPU."""
import numpy as np

import tiny_tree
from bench.graphdata import seed_streams
from bench.registry import Registry


def _gen(kind, config, seed, **sizes):
    reg = Registry(tiny_tree.REPO)
    cfg = dict(reg.config(config), **sizes)
    return reg.generator(kind).generate(cfg, seed)


def test_kronecker_edge_count_and_skewed_degree():
    dep = _gen("kronecker", "graph500-s21", 2**40 + 3, scale=12)
    V = 1 << 12
    assert dep.n_vertices == V and dep.n_edges == 16 * V
    assert not dep.directed
    src, dst = dep.edge["src"], dep.edge["dst"]
    assert src.min() >= 0 and max(src.max(), dst.max()) < V
    deg = np.bincount(src, minlength=V) + np.bincount(dst, minlength=V)
    assert deg.sum() == 2 * dep.n_edges
    assert deg.max() > 20 * deg.mean()  # Kronecker hubs
    assert (deg == 0).sum() > V // 10  # and the isolated vertices it leaves
    np.testing.assert_array_equal(dep.edge["eid"], np.arange(dep.n_edges))
    assert set(dep.edge) == {"eid", "src", "dst", "weight", "sel", "label"}
    assert 0 <= dep.edge["sel"].min() and dep.edge["sel"].max() <= 99


def test_kronecker_quadrant_probabilities():
    # one level: endpoints equal with probability A + D, under any labelling
    dep = _gen("kronecker", "graph500-s21", 5, scale=1, edgefactor=1 << 16)
    same = np.mean(dep.edge["src"] == dep.edge["dst"])
    assert abs(same - (0.57 + 0.05)) < 0.01


def test_generation_follows_the_whole_seed():
    a = _gen("kronecker", "graph500-s21", 5, scale=8)
    b = _gen("kronecker", "graph500-s21", 5, scale=8)
    c = _gen("kronecker", "graph500-s21", 2**33 + 5, scale=8)
    np.testing.assert_array_equal(a.edge["src"], b.edge["src"])
    assert not np.array_equal(a.edge["src"], c.edge["src"])
    assert not np.array_equal(seed_streams(5)[0].integers(0, 2**62, 4),
                              seed_streams(2**33 + 5)[0].integers(0, 2**62, 4))


def test_uniform_edge_count_and_poisson_degrees():
    dep = _gen("uniform", "gap-urand-s22", 2**36 + 9, scale=12)
    V = 1 << 12
    src, dst = dep.edge["src"], dep.edge["dst"]
    assert not dep.directed and dep.n_vertices == V and dep.n_edges == 16 * V
    assert src.min() >= 0 and max(src.max(), dst.max()) < V
    deg = np.bincount(src, minlength=V) + np.bincount(dst, minlength=V)
    # each endpoint uniform: degrees Poisson(32), no hubs
    assert abs(deg.mean() - 32) < 1e-9 and abs(deg.var() - 32) < 4
    assert deg.max() < 32 + 8 * np.sqrt(32)
    assert np.all(dep.vertex["vattr"] == (np.arange(V) * 7) % 100)
    assert set(dep.edge) == {"eid", "src", "dst", "weight", "sel", "label"}
