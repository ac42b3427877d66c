"""Seeded graphs of the benchmark (its own copy of the rules).

A configuration's ``generator`` key names its family, the file
``bench/generators/<generator>.py``, found as the metric readers are
(``bench/loader.py``). A family file holds:

* ``KEYS``: the configuration keys it reads;
* ``num_vertices(config)``;
* ``edges(config, seed) -> (src, dst, w)``: numpy arrays, deduplicated,
  without self-loops, sorted by (src, dst); ``w`` float32.

Besides its family's ``KEYS``, a configuration holds ``name``,
``generator``, optionally ``directed`` (default true) and ``precision``
(float32, the precision the comparison's limits are set for), and notes
for people (``NOTES``). Any other key is refused before set-up: a size
that no code reads would be a graph the run does not serve.

``"directed": false`` serves each undirected edge once in each direction
with one weight, the one the family drew for the edge from its lower
vertex id; self-loops and duplicates are dropped after the edges are
made symmetric.

The families share ``seeded_edges``: the edge draws and a random
relabelling come from the configuration's ``structure_seed``, and the
run's seed draws only the weights. So every seed serves the same edges
with the same vertex ids: the same depth, degrees, partitions and lane
shapes, so the same compiled programs. A seed changes the weights and,
through the traffic, the roots and sources, not how much work a request
is. Every edge carries a dyadic weight (256 + k) / 256 with k uniform in
[0, 256), so float32 path sums stay exact. The draws, relabelling,
weights, the (src, dst) sort and the duplicate mask all run on the
default device in one jitted call; only the compaction runs on the
host. The same seeds give the same graph on every backend.
"""
from __future__ import annotations

import functools
from pathlib import Path
from types import ModuleType

import jax
import numpy as np

from .loader import BenchError, load_file

ROOT = Path(__file__).resolve().parents[1]

# keys that only describe the configuration to a reader
NOTES = frozenset({"source", "weights", "plan", "reduced", "published",
                   "why_reduced", "assumed"})
PRECISION = "float32"


def prng_key(seed: int):
    """A threefry key from a seed of any size up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    words = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


def family(config: dict, root: Path = ROOT) -> ModuleType:
    """The configuration's family file, once every key of the
    configuration is one that some code reads."""
    fam = load_file(root, "generators", config.get("generator"))
    known = NOTES | {"name", "generator", "directed", "precision"} \
        | set(fam.KEYS)
    unread = sorted(set(config) - known)
    if unread:
        raise BenchError(f"configuration {config.get('name')!r}: no code "
                         f"reads {unread} (family {config['generator']!r} "
                         f"reads {list(fam.KEYS)})")
    if not isinstance(config.get("directed", True), bool):
        raise BenchError(f"directed must be true or false, got "
                         f"{config['directed']!r}")
    if config.get("precision", PRECISION) != PRECISION:
        raise BenchError(f"precision {config['precision']!r}: the limits "
                         f"are set for {PRECISION}")
    return fam


@functools.partial(jax.jit, static_argnames=("draw", "n", "m", "params"))
def _seeded(k_struct, k_w, *, draw, n: int, m: int, params: tuple):
    import jax.numpy as jnp
    from jax import lax

    k_edge, k_perm = jax.random.split(k_struct)
    src, dst = draw(k_edge, n, m, *params)
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    src, dst = perm[src], perm[dst]
    k = jax.random.randint(k_w, (m,), 0, 256, jnp.int32)
    src, dst, k = lax.sort((src, dst, k), num_keys=2, is_stable=True)
    new = jnp.concatenate([jnp.ones((1,), bool),
                           (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    return src, dst, k, new & (src != dst)


def seeded_edges(draw, n: int, m: int, structure_seed: int, seed: int,
                 params: tuple = ()):
    """A family's ``edges``: ``draw(key, n, m, *params)`` gives ``m``
    int32 (src, dst) draws over ``n`` vertices from the structure key (a
    module-level function, traced once per process); then the shared
    relabelling, dyadic weights from ``seed``, sort and dedup."""
    src, dst, k, keep = (np.asarray(x) for x in _seeded(
        prng_key(structure_seed), prng_key(seed), draw=draw, n=n, m=m,
        params=params))
    w = ((256 + k[keep]) / 256).astype(np.float32)
    return src[keep], dst[keep], w


@jax.jit
def _symmetric(src, dst, w):
    import jax.numpy as jnp
    from jax import lax

    lo, hi = jnp.minimum(src, dst), jnp.maximum(src, dst)
    lo, hi, w = lax.sort((lo, hi, w), num_keys=2, is_stable=True)
    keep = jnp.concatenate([jnp.ones((1,), bool),
                            (lo[1:] != lo[:-1]) | (hi[1:] != hi[:-1])])
    keep = keep & (lo != hi)
    both = (jnp.concatenate([lo, hi]), jnp.concatenate([hi, lo]),
            jnp.concatenate([w, w]), jnp.concatenate([keep, keep]))
    return lax.sort(both, num_keys=2, is_stable=True)


def symmetric(src, dst, w):
    """Each undirected edge once in each direction, sorted by (src, dst),
    with the weight of the edge drawn from its lower vertex id (the first
    of the pair in (src, dst) order)."""
    src, dst, w, keep = (np.asarray(x) for x in _symmetric(src, dst, w))
    return src[keep], dst[keep], w[keep]


def edges(config: dict, seed: int, root: Path = ROOT):
    """``(num_vertices, src, dst, w)`` of the configuration's graph."""
    fam = family(config, root)
    src, dst, w = fam.edges(config, seed)
    if not config.get("directed", True):
        src, dst, w = symmetric(src, dst, w)
    return fam.num_vertices(config), src, dst, w


def make_graph(config: dict, seed: int, root: Path = ROOT):
    """The configuration's graph as the program's ``Graph`` container
    (canonical: sorted by (src, dst), arrays read-only)."""
    from repro.graphs.formats import Graph, freeze

    n, src, dst, w = edges(config, seed, root)
    return freeze(Graph(num_vertices=n, src=src, dst=dst, weights=w,
                        name=config["name"]))
