"""Seeded graph generators of the benchmark (its own copy of the rules).

Two families, chosen by a configuration's ``generator`` key:

* ``rmat``: Graph500 R-MAT, (a, b, c, d) = (0.57, 0.19, 0.19, 0.05), the
  rules of ``repro.graphs.rmat``: per bit one quadrant draw, a random
  relabelling of the vertices, self-loops dropped, duplicates removed.
* ``uniform``: both endpoints of every edge uniform over the vertices
  (GAP's urand), self-loops dropped, duplicates removed.

The edge draws and the random relabelling come from the configuration's
``structure_seed``; the run's seed draws only the weights. So every seed
serves the same edges with the same vertex ids: the same depth, degrees,
partitions and lane shapes, so the same compiled programs. A seed
changes the weights and, through the traffic, the roots and sources,
not how much work a request is. Every edge carries a dyadic weight
(256 + k) / 256 with k uniform in [0, 256), so float32 path sums stay
exact. The draws, relabelling, weights, the (src, dst) sort and the
duplicate mask all run on the default device in one jitted call; only
the compaction runs on the host. The same seeds give the same graph on
every backend.
"""
from __future__ import annotations

import functools

import jax
import numpy as np

G500 = (0.57, 0.19, 0.19, 0.05)


def prng_key(seed: int):
    """A threefry key from a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


@functools.partial(jax.jit,
                   static_argnames=("generator", "scale", "edge_factor"))
def _draw(k_edge, k_w, *, generator: str, scale: int, edge_factor: int):
    import jax.numpy as jnp
    from jax import lax

    n = 1 << scale
    m = n * edge_factor
    k_edge, k_perm = jax.random.split(k_edge)
    if generator == "rmat":
        a, b, c, _ = G500

        def bit(i, carry):
            src, dst = carry
            u = jax.random.uniform(jax.random.fold_in(k_edge, i), (m,))
            down = u >= a + b
            right = ((u >= a) & ~down) | (u >= a + b + c)
            return ((src << 1) | down.astype(jnp.int32),
                    (dst << 1) | right.astype(jnp.int32))

        zero = jnp.zeros((m,), jnp.int32)
        src, dst = lax.fori_loop(0, scale, bit, (zero, zero))
    elif generator == "uniform":
        k_s, k_d = jax.random.split(k_edge)
        src = jax.random.randint(k_s, (m,), 0, n, jnp.int32)
        dst = jax.random.randint(k_d, (m,), 0, n, jnp.int32)
    else:
        raise ValueError(f"unknown generator {generator!r}")
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    k = jax.random.randint(k_w, (m,), 0, 256, jnp.int32)
    src, dst, k = lax.sort((src, dst, k), num_keys=2, is_stable=True)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    return src, dst, k, new & (src != dst)


def edges(generator: str, scale: int, edge_factor: int, structure_seed: int,
          seed: int):
    """``(src, dst, weights)`` numpy arrays: deduplicated, without
    self-loops, sorted by (src, dst); weights float32 dyadic."""
    src, dst, k, keep = (np.asarray(x) for x in _draw(
        prng_key(structure_seed), prng_key(seed), generator=generator,
        scale=scale, edge_factor=edge_factor))
    w = ((256 + k[keep]) / 256).astype(np.float32)
    return src[keep], dst[keep], w


def make_graph(config: dict, seed: int):
    """The configuration's graph as the program's ``Graph`` container
    (canonical: sorted by (src, dst), arrays read-only)."""
    from repro.graphs.formats import Graph, freeze

    src, dst, w = edges(config["generator"], config["scale"],
                        config["edge_factor"], config["structure_seed"], seed)
    return freeze(Graph(num_vertices=1 << config["scale"], src=src,
                        dst=dst, weights=w, name=config["name"]))
