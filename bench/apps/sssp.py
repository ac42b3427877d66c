"""sssp: the weighted distance of every vertex from ``root``.

Compared number ``sssp_mismatches``: vertices whose distance differs
from scipy's Dijkstra (``reference.sssp``). Limit 0: dyadic weights
keep float32 path sums exact. The control relaxes the distances in
bfloat16 and misses on about a third of the vertices (PERF.md). Per
iteration: 12 B per edge (src and dst ids and the float32 weight), 4 B
per vertex read and 4 B written, 2 operations per edge.
"""
import numpy as np

from bench import reference

NUMBER = "sssp_mismatches"
LIMIT = 0
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 12, 8, 2


def answer(g, kwargs):
    return reference.sssp(g, kwargs["root"])


def measure(served, ref):
    return np.count_nonzero(served != ref)


def control(g, kwargs, dtype="bfloat16"):
    return reference.control_fixpoint(g, kwargs["root"], weighted=True,
                                      dtype=dtype)
