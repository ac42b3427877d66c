"""Plain references of the apps (numpy / scipy), and their control.

Nothing here imports the program or takes anything it made. Each
function takes the benchmark's own graph arrays and the request's
parameters, and returns what the served answer should be, in original
vertex ids. Each app's file (``bench/apps/<app>.py``) says which of
them is its reference and its control:

* pagerank: pull power iteration on rank / out-degree, float64, stopped
  by the app's own rule (no rank / out-degree moves by 1e-7 or more, at
  most 16 iterations), so the reference fixes how many iterations a
  converged answer takes;
* bfs / sssp: ``scipy.sparse.csgraph`` levels and dyadic-weight
  distances, exact, with unreached vertices at ``INF``;
* wcc: the app's labels are the fixpoint of label[v] = min(label[v], min
  over edges u->v of label[u]) from the store's own vertex numbering,
  which the benchmark does not know. ``wcc_violations`` counts how far
  served labels are from that fixpoint under every numbering at once;
* closeness: bit b of vertex v set iff v is reachable from sources[b].

The control (``control_pagerank``, ``control_fixpoint``) is the same
semantics computed in bfloat16 with ``jax.numpy``, the nearest precision
below the float32 that the configurations state.
"""
from __future__ import annotations

import numpy as np

# the program's marker for an unreached vertex (largest float32 used)
INF = np.float32(3.0e38)

# the pagerank app's stopping rule
PAGERANK_TOL = 1e-7
PAGERANK_MAX_ITERS = 16


class Graph:
    """Host copy of the benchmark's edges: ``n`` vertices, int arrays
    ``src``/``dst`` and float32 ``w``."""

    def __init__(self, n: int, src, dst, w):
        self.n, self.src, self.dst, self.w = int(n), src, dst, w
        self._mats = {}

    def matrix(self, weighted: bool):
        import scipy.sparse as sp
        if weighted not in self._mats:
            data = (self.w.astype(np.float64) if weighted
                    else np.ones(self.src.size, np.float64))
            self._mats[weighted] = sp.csr_matrix(
                (data, (self.src, self.dst)), shape=(self.n, self.n))
        return self._mats[weighted]


def pagerank(g: Graph, damping: float = 0.85):
    """``(rank / out-degree, iterations)``: the power iteration stopped
    after the first iteration in which no entry moves by
    ``PAGERANK_TOL`` or more, or after ``PAGERANK_MAX_ITERS``."""
    n = g.n
    outdeg = np.maximum(np.bincount(g.src, minlength=n), 1).astype(np.float64)
    at = g.matrix(False).T.tocsr()
    p = np.full(n, 1.0 / n) / outdeg
    for it in range(1, PAGERANK_MAX_ITERS + 1):
        new = ((1 - damping) / n + damping * (at @ p)) / outdeg
        done = np.max(np.abs(new - p)) < PAGERANK_TOL
        p = new
        if done:
            break
    return p, it


def _levels(dist):
    return np.where(np.isinf(dist), INF, dist).astype(np.float32)


def bfs(g: Graph, root: int):
    from scipy.sparse import csgraph
    return _levels(csgraph.shortest_path(g.matrix(False), directed=True,
                                         unweighted=True, indices=root))


def sssp(g: Graph, root: int):
    from scipy.sparse import csgraph
    return _levels(csgraph.dijkstra(g.matrix(True), directed=True,
                                    indices=root))


def wcc_violations(g: Graph, labels) -> int:
    """0 iff ``labels`` are the min-label fixpoint for some numbering of
    the vertices, else a positive count of what breaks it.

    For a numbering ``id``, label[v] = min of id(u) over the vertices u
    that reach v (v included). Such labels are exactly those with:

    1. values that are integers in [0, n);
    2. no edge u->v with label[u] < label[v] (a fixpoint);
    3. in each class of equal labels, one strongly connected component
       of the class's own edges that reaches the whole class (it holds
       the vertex numbered with the label);
    4. numbers left for the other vertices: each needs one above its
       label that is no class's label (Hall's condition on the sorted
       labels).

    The count is the vertices that break 1 or 2, plus the classes'
    extra source components, plus the shortfall of numbers in 4.
    """
    from scipy.sparse import csgraph, csr_matrix
    n = g.n
    lab = np.asarray(labels, np.float64)[:n]
    if lab.shape != (n,):
        return n
    bad = ~np.isfinite(lab) | (lab != np.round(lab)) | (lab < 0) | (lab >= n)
    if bad.any():
        return int(bad.sum())
    lab = lab.astype(np.int64)
    ls, ld = lab[g.src], lab[g.dst]
    not_fixed = np.unique(g.dst[ls < ld]).size
    same = ls == ld
    s, d = g.src[same], g.dst[same]
    ncomp, comp = csgraph.connected_components(
        csr_matrix((np.ones(s.size, np.int8), (s, d)), shape=(n, n)),
        directed=True, connection="strong")
    fed = np.zeros(ncomp, bool)
    fed[comp[d][comp[s] != comp[d]]] = True
    comp_label = np.empty(ncomp, np.int64)
    comp_label[comp] = lab
    _, n_sources = np.unique(comp_label[~fed], return_counts=True)
    extra_sources = int(np.sum(n_sources - 1))
    values, sizes = np.unique(lab, return_counts=True)
    k = values.size
    need = np.cumsum((sizes - 1)[::-1])[::-1]           # labels >= values[j]
    free = (n - 1 - values) - (k - 1 - np.arange(k))   # free numbers > values[j]
    shortfall = int(max(0, np.max(need - free)))
    return not_fixed + extra_sources + shortfall


def reach_bits(g: Graph, sources):
    from scipy.sparse import csgraph
    a = g.matrix(False)
    bits = np.zeros(g.n, np.uint32)
    for b, s in enumerate(sources):
        reach = csgraph.breadth_first_order(a, int(s), directed=True,
                                            return_predecessors=False)
        bits[reach] |= np.uint32(1 << b)
    return bits.view(np.int32)


# --------------------------------------------------------------------------
# control: the same semantics in a lower precision (jax.numpy, on the
# default device)
# --------------------------------------------------------------------------

def control_pagerank(g: Graph, damping: float, iterations: int,
                     dtype="bfloat16"):
    """Pagerank's rank / out-degree computed in ``dtype`` for
    ``iterations``, as float64."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = g.n
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    outdeg = jnp.maximum(jnp.bincount(src, length=n), 1).astype(dt)

    @jax.jit
    def step(p):
        s = jax.ops.segment_sum(p[src], dst, num_segments=n)
        return ((1 - damping) / n + damping * s).astype(dt) / outdeg

    p = (jnp.full((n,), 1.0 / n, dt) / outdeg).astype(dt)
    for _ in range(iterations):
        p = step(p)
    return np.asarray(p.astype(jnp.float32), np.float64)


def control_fixpoint(g: Graph, root=None, *, weighted=False, level=False,
                     dtype="bfloat16"):
    """The min-gather fixpoint computed in ``dtype``: from ``root`` at 0
    (the others at infinity) or, without a root, from the vertex ids;
    each step takes the min over in-edges of the source's value, plus
    the edge's weight where ``weighted``; with ``level`` an unreached
    vertex takes that min plus 1 (bfs), else the min of both (sssp,
    wcc). Unreached vertices read ``INF``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)
    n = g.n
    src, dst = jnp.asarray(g.src), jnp.asarray(g.dst)
    w = jnp.asarray(g.w).astype(dt) if weighted else None
    inf = jnp.asarray(jnp.inf, dt)
    if root is None:
        p = jnp.arange(n).astype(dt)
    else:
        p = jnp.full((n,), inf, dt).at[root].set(0)

    @jax.jit
    def relax(p):
        x = p[src] if w is None else p[src] + w
        m = jax.ops.segment_min(x, dst, num_segments=n)
        if level:
            return jnp.where((p == inf) & (m < inf), m + 1, p)
        return jnp.minimum(p, m)

    while True:
        new = relax(p)
        if bool(jnp.all(new == p)):
            break
        p = new
    out = np.asarray(p.astype(jnp.float32))
    return np.where(np.isinf(out), INF, out).astype(np.float32)
