"""What every deployment generator shares: the seed streams, the edge
attributes the paper synthesizes, and the generated deployment itself.

The edge attributes copy the semantics of the repository's power-law
generator (GRFusion §7.3, "synthesized edge attributes to control the
selectivity"): ``weight`` uniform in [0.1, 10), ``sel`` uniform in 0..99
(a predicate ``sel < s`` keeps s% of the edges), ``label`` uniform in
{0, 1, 2}; the vertex table has ``vid`` and ``vattr = vid * 7 % 100``.
"""
from __future__ import annotations

import functools
import operator
from dataclasses import dataclass
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


# comparison operators of the traffic files' edge predicates
OPS = {"<": operator.lt, "<=": operator.le, ">": operator.gt,
       ">=": operator.ge, "==": operator.eq, "!=": operator.ne}


@dataclass
class Deployment:
    """A generated graph in table form, on the host (numpy)."""

    n_vertices: int
    directed: bool
    vertex: Dict[str, np.ndarray]  # vid, vattr
    edge: Dict[str, np.ndarray]  # eid, src, dst, weight, sel, label

    @property
    def n_edges(self) -> int:
        return int(self.edge["src"].shape[0])


def seed_streams(seed: int):
    """(numpy Generator, JAX key), both from the whole of ``seed``: a
    ``PRNGKey(seed)`` would drop the bits above 32."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0]))
    words = np.random.SeedSequence([seed, 1]).generate_state(2, np.uint32)
    key = jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")
    return rng, key


@functools.partial(jax.jit, static_argnames=("n_edges",))
def edge_attributes(key, n_edges: int):
    kw, ks, kl = jax.random.split(key, 3)
    return {
        "weight": jax.random.uniform(kw, (n_edges,), jnp.float32, 0.1, 10.0),
        "sel": jax.random.randint(ks, (n_edges,), 0, 100, jnp.int32),
        "label": jax.random.randint(kl, (n_edges,), 0, 3, jnp.int32),
    }


def deployment(n_vertices: int, directed: bool, src, dst, key) -> Deployment:
    """Tables around generated endpoint arrays (device or host), with the
    synthesized attributes; everything lands on the host once."""
    n_edges = int(src.shape[0])
    attrs = edge_attributes(key, n_edges)
    vid = np.arange(n_vertices, dtype=np.int32)
    edge = {
        "eid": np.arange(n_edges, dtype=np.int32),
        "src": np.asarray(src, np.int32),
        "dst": np.asarray(dst, np.int32),
        **{k: np.asarray(v) for k, v in attrs.items()},
    }
    return Deployment(
        n_vertices=n_vertices, directed=directed,
        vertex={"vid": vid, "vattr": (vid * 7) % 100}, edge=edge,
    )
