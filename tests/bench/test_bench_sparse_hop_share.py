"""The ``sparse_hop_share`` reader on hand-built windows: the share of the
``xla_coo`` sweep's hops that ran frontier-sparse, and None where the window
holds no hops or no sparse-hop counter."""
from types import SimpleNamespace

import pytest

import tiny_tree
from bench.registry import Registry
from bench.window import Drive, Request, Window


def _window(counters, n_finished=4):
    reqs = [Request(params={}, due=0.0,
                    ticket=SimpleNamespace(done_us=0.5e6, status="done"))
            for _ in range(n_finished)]
    return Window(drive=Drive(0.0, 1.0, 1.0, reqs, []), in_window=reqs, missing=0,
                  counters=counters, trace=None)


@pytest.mark.parametrize("counters, value", [
    ({"traversal.hops_xla_coo": 8, "traversal.hops_xla_coo_sparse": 6}, 0.75),
    ({"traversal.hops_xla_coo": 4}, None),
    ({"traversal.hops_xla_coo_sparse": 2}, None),
    ({}, None),
])
def test_sparse_hop_share_reader(counters, value):
    metric = Registry(tiny_tree.REPO).metric("sparse_hop_share")
    assert metric.read(_window(counters)) == value
