"""Gather-Apply-Scatter programming interface (paper §V-B, Listing 1).

Users supply three UDFs, exactly like ReGraph's accScatter/accGather/
accApply. The scatter UDF runs inside the Pallas kernels (traceable jnp
on (E_BLK,) vectors); gather is one of the supported associative modes
(the MXU/VPU "router" implements it); apply is a vertex-wise jnp function.

Built-in applications mirror the paper's benchmarks (PR, BFS, CC) plus
SSSP and WCC (both supported by ThunderGP, the paper's main baseline).
CC here is Closeness Centrality computed via 32-source bit-parallel BFS
(OR-aggregation), the standard accelerator formulation.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax.numpy as jnp
import numpy as np

INF = np.float32(3.0e38)

# gather modes and their identity elements
GATHER_IDENTITY = {
    "sum": 0.0,
    "min": INF,
    "max": -INF,
    "or": 0,           # int32 bitwise OR
}


@dataclasses.dataclass(frozen=True)
class GASApp:
    """A graph application in the GAS model.

    prop is a scalar per-vertex property (f32, or i32 for 'or' mode).
    scatter(src_prop, edge_weight) -> update value        [runs in-kernel]
    gather mode in {'sum','min','max','or'}               [the router]
    apply(accum, prop, aux, iteration) -> new prop        [vertex-wise]
    init(graph_aux) -> initial prop                        (numpy)
    converged(old_prop, new_prop, iteration) -> bool
    """

    name: str
    gather: str
    scatter: Callable
    apply: Callable
    init: Callable
    converged: Callable
    needs_weights: bool = False
    prop_dtype: str = "float32"
    max_iters: int = 64


# ---------------------------------------------------------------------------
# PageRank (paper Listing 1): pull model. The stored property is
# rank/out_degree so scatter is the identity — exactly the paper's UDF.
# ---------------------------------------------------------------------------

def make_pagerank(damping: float = 0.85, max_iters: int = 16) -> GASApp:
    def scatter(src_prop, w):
        return src_prop

    def apply(accum, prop, aux, it):
        outdeg, num_v = aux["outdeg"], aux["num_v"]
        rank = (1.0 - damping) / num_v + damping * accum
        return rank / jnp.maximum(outdeg, 1.0)

    def init(aux):
        v = aux["outdeg"].shape[0]
        return (np.full(v, 1.0 / aux["num_v"], np.float32)
                / np.maximum(aux["outdeg"], 1.0)).astype(np.float32)

    def converged(old, new, it):
        return bool(jnp.max(jnp.abs(old - new)) < 1e-7)

    return GASApp("pagerank", "sum", scatter, apply, init, converged,
                  max_iters=max_iters)


# ---------------------------------------------------------------------------
# BFS: pull-based level propagation; prop = level (INF = unvisited).
# ---------------------------------------------------------------------------

def make_bfs(root: int = 0, max_iters: int = 64) -> GASApp:
    def scatter(src_prop, w):
        return src_prop

    def apply(accum, prop, aux, it):
        reachable = accum < INF
        return jnp.where((prop >= INF) & reachable, accum + 1.0, prop)

    def init(aux):
        p = np.full(aux["num_v_pad"], INF, np.float32)
        perm = aux.get("perm")
        p[int(perm[root]) if perm is not None else root] = 0.0
        return p

    def converged(old, new, it):
        return bool(jnp.all(old == new))

    return GASApp("bfs", "min", scatter, apply, init, converged,
                  max_iters=max_iters)


# ---------------------------------------------------------------------------
# SSSP: prop = distance; scatter adds edge weight; gather = min.
# ---------------------------------------------------------------------------

def make_sssp(root: int = 0, max_iters: int = 64) -> GASApp:
    def scatter(src_prop, w):
        return src_prop + w

    def apply(accum, prop, aux, it):
        return jnp.minimum(prop, accum)

    def init(aux):
        p = np.full(aux["num_v_pad"], INF, np.float32)
        perm = aux.get("perm")
        p[int(perm[root]) if perm is not None else root] = 0.0
        return p

    def converged(old, new, it):
        return bool(jnp.all(old == new))

    return GASApp("sssp", "min", scatter, apply, init, converged,
                  needs_weights=True, max_iters=max_iters)


# ---------------------------------------------------------------------------
# WCC: prop = component label, gather = min label.
# ---------------------------------------------------------------------------

def make_wcc(max_iters: int = 64) -> GASApp:
    def scatter(src_prop, w):
        return src_prop

    def apply(accum, prop, aux, it):
        return jnp.minimum(prop, accum)

    def init(aux):
        return np.arange(aux["num_v_pad"], dtype=np.float32)

    def converged(old, new, it):
        return bool(jnp.all(old == new))

    return GASApp("wcc", "min", scatter, apply, init, converged,
                  max_iters=max_iters)


# ---------------------------------------------------------------------------
# CC (Closeness Centrality): 32-source bit-parallel BFS with OR gather.
# prop = int32 visited bitmask; aux accumulates per-iteration coverage.
# The final centrality is derived by the engine from the per-iteration
# newly-visited counts (sum over sources of distances).
# ---------------------------------------------------------------------------

def make_closeness(sources: Optional[np.ndarray] = None,
                   max_iters: int = 32) -> GASApp:
    def scatter(src_prop, w):
        return src_prop

    def apply(accum, prop, aux, it):
        return prop | accum

    def init(aux):
        p = np.zeros(aux["num_v_pad"], np.int32)
        srcs = sources
        if srcs is None:
            srcs = np.arange(min(32, int(aux["num_v"])), dtype=np.int64)
        perm = aux.get("perm")
        for bit, s in enumerate(np.asarray(srcs)[:32]):
            s = int(perm[int(s)]) if perm is not None else int(s)
            mask = (1 << bit) & 0xFFFFFFFF
            if mask >= (1 << 31):      # wrap to signed int32
                mask -= 1 << 32
            p[s] |= np.int32(mask)
        return p

    def converged(old, new, it):
        return bool(jnp.all(old == new))

    return GASApp("closeness", "or", scatter, apply, init, converged,
                  prop_dtype="int32", max_iters=max_iters)


BUILTIN_APPS = {
    "pagerank": make_pagerank,
    "bfs": make_bfs,
    "sssp": make_sssp,
    "wcc": make_wcc,
    "closeness": make_closeness,
}

# the factory kwargs that reach only ``init``: they set where a run
# starts, not the iteration (scatter, apply, converged), so apps that
# differ only in them run one compiled program
START_KWARGS = {
    "bfs": ("root",),
    "sssp": ("root",),
    "closeness": ("sources",),
}
