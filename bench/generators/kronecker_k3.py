"""Graph500 Kernel 3's graph: the Kernel 2 Kronecker graph of
``kronecker.py`` (the same edges for the same seed), each edge weighing a
float32 drawn uniformly from [0, 1), as the specification's SSSP kernel
draws them.

The weights come from the seed's numpy stream, which the Kronecker edges
leave unused; the other attributes stay as ``graphdata`` synthesizes them.

Config keys: those of ``kronecker.py``.
"""
from __future__ import annotations

import numpy as np

from bench.generators import kronecker
from bench.graphdata import Deployment, seed_streams


def generate(cfg, seed: int) -> Deployment:
    dep = kronecker.generate(cfg, seed)
    rng, _ = seed_streams(seed)
    dep.edge["weight"] = rng.random(dep.n_edges, dtype=np.float32)
    return dep
