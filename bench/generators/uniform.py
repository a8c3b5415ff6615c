"""Uniform random (Erdos-Renyi) generator, the GAP Benchmark Suite's
``urand``, generated in bulk on the device.

``edgefactor * 2**scale`` edges, both endpoints of each drawn uniformly
over the ``2**scale`` vertices. Self-loops and repeated pairs stay in the
edge list (about 2**-scale and ``edgefactor**2 / 2`` of them).

Config keys: ``scale``, ``edgefactor``, ``directed``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.graphdata import Deployment, deployment, seed_streams


@functools.partial(jax.jit, static_argnames=("n_vertices", "n_edges"))
def _edges(key, *, n_vertices: int, n_edges: int):
    k_src, k_dst = jax.random.split(key)
    return (jax.random.randint(k_src, (n_edges,), 0, n_vertices, jnp.int32),
            jax.random.randint(k_dst, (n_edges,), 0, n_vertices, jnp.int32))


def generate(cfg, seed: int) -> Deployment:
    scale = int(cfg["scale"])
    _, key = seed_streams(seed)
    k_edges, k_attr = jax.random.split(key)
    src, dst = _edges(k_edges, n_vertices=1 << scale,
                      n_edges=int(cfg["edgefactor"]) << scale)
    return deployment(1 << scale, bool(cfg["directed"]), src, dst, k_attr)
