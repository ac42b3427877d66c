"""closeness: a 32-source bit-parallel BFS; bit b of a vertex is set iff
it is reachable from ``sources[b]``.

Compared number ``closeness_mismatches``: vertices whose bits differ
from scipy's reachability (``reference.reach_bits``). Limit 0: the
app has no floating point. For the same reason its control is the exact
reference and passes (PERF.md). Per iteration: 8 B per edge (src and
dst ids), 4 B per vertex read and 4 B written, 2 operations per edge.
"""
import numpy as np

from bench import reference

NUMBER = "closeness_mismatches"
LIMIT = 0
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 8, 8, 2


def answer(g, kwargs):
    return reference.reach_bits(g, kwargs["sources"])


def measure(served, ref):
    return np.count_nonzero(served != ref)


def control(g, kwargs, dtype="bfloat16"):
    return reference.reach_bits(g, kwargs["sources"])
