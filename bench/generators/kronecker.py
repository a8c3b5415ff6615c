"""Graph500 Kronecker generator (specification v3, the reference code's
``kronecker_generator``), generated in bulk on the device.

``edgefactor * 2**scale`` edges; each of the ``scale`` bits of an edge's
endpoints picks one quadrant of the initiator ``[[A, B], [C, D]]``
(``D = 1 - A - B - C``) from one 32-bit draw; then the vertex labels are
permuted and the edge list shuffled, as the specification requires.
Self-loops and repeated edges stay, as they do in the specification's edge
list.

Config keys: ``scale``, ``edgefactor``, ``initiator`` ([A, B, C]),
``directed``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.graphdata import Deployment, deployment, seed_streams


@functools.partial(jax.jit, static_argnames=("scale", "n_edges"))
def _edges(key, thresholds, *, scale: int, n_edges: int):
    k_bits, k_label, k_order = jax.random.split(key, 3)
    t_a, t_ab, t_abc = thresholds[0], thresholds[1], thresholds[2]

    def level(b, ij):
        u = jax.random.bits(jax.random.fold_in(k_bits, b), (n_edges,), jnp.uint32)
        row = u >= t_ab  # quadrants C and D
        col = ((u >= t_a) & (u < t_ab)) | (u >= t_abc)  # quadrants B and D
        return (ij[0] | (row.astype(jnp.int32) << b),
                ij[1] | (col.astype(jnp.int32) << b))

    zeros = jnp.zeros((n_edges,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    label = jax.random.permutation(k_label, jnp.arange(1 << scale, dtype=jnp.int32))
    order = jax.random.permutation(k_order, n_edges)
    return label[src][order], label[dst][order]


def thresholds(initiator):
    """Cumulative quadrant probabilities A, A+B, A+B+C as 32-bit thresholds."""
    a, b, c = (float(x) for x in initiator)
    return jnp.asarray(
        [round(x * 2.0 ** 32) for x in (a, a + b, a + b + c)], jnp.uint32
    )


def generate(cfg, seed: int) -> Deployment:
    scale = int(cfg["scale"])
    n_edges = int(cfg["edgefactor"]) << scale
    _, key = seed_streams(seed)
    k_edges, k_attr = jax.random.split(key)
    src, dst = _edges(
        k_edges, thresholds(cfg["initiator"]), scale=scale, n_edges=n_edges
    )
    return deployment(1 << scale, bool(cfg["directed"]), src, dst, k_attr)
