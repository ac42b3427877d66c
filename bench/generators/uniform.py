"""GAP's urand: 2^``scale`` vertices, ``edge_factor`` edge draws per
vertex, both endpoints of every draw uniform over the vertices.
Relabelling, weights, self-loops and duplicates: ``bench/graphgen.py``'s
``seeded_edges``.
"""
import jax
import jax.numpy as jnp

from bench import graphgen

KEYS = ("scale", "edge_factor", "structure_seed")


def num_vertices(config: dict) -> int:
    return 1 << config["scale"]


def _draw(key, n, m):
    k_s, k_d = jax.random.split(key)
    return (jax.random.randint(k_s, (m,), 0, n, jnp.int32),
            jax.random.randint(k_d, (m,), 0, n, jnp.int32))


def edges(config: dict, seed: int):
    n = num_vertices(config)
    return graphgen.seeded_edges(_draw, n, n * config["edge_factor"],
                                 config["structure_seed"], seed)
