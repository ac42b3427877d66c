"""Graph500 R-MAT: 2^``scale`` vertices, ``edge_factor`` edge draws per
vertex, each id drawn bit by bit from the quadrant probabilities
``rmat_abcd`` (default Graph500's (0.57, 0.19, 0.19, 0.05)), the rules of
``repro.graphs.rmat``. Relabelling, weights, self-loops and duplicates:
``bench/graphgen.py``'s ``seeded_edges``.
"""
import jax
import jax.numpy as jnp
from jax import lax

from bench import graphgen
from bench.loader import BenchError

KEYS = ("scale", "edge_factor", "structure_seed", "rmat_abcd")
G500 = (0.57, 0.19, 0.19, 0.05)


def num_vertices(config: dict) -> int:
    return 1 << config["scale"]


def _draw(key, n, m, scale, abcd):
    a, b, c, _ = abcd

    def bit(i, carry):
        src, dst = carry
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        down = u >= a + b
        right = ((u >= a) & ~down) | (u >= a + b + c)
        return ((src << 1) | down.astype(jnp.int32),
                (dst << 1) | right.astype(jnp.int32))

    zero = jnp.zeros((m,), jnp.int32)
    return lax.fori_loop(0, scale, bit, (zero, zero))


def edges(config: dict, seed: int):
    abcd = tuple(float(p) for p in config.get("rmat_abcd", G500))
    if len(abcd) != 4 or min(abcd) < 0 or abs(sum(abcd) - 1) > 1e-9:
        raise BenchError(f"rmat_abcd {abcd} is not four probabilities")
    n = num_vertices(config)
    return graphgen.seeded_edges(_draw, n, n * config["edge_factor"],
                                 config["structure_seed"], seed,
                                 (config["scale"], abcd))
