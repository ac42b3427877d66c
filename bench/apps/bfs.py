"""bfs: the level of every vertex from ``root`` (``INF`` unreached).

Compared number ``bfs_mismatches``: vertices whose level differs from
scipy's (``reference.bfs``). Limit 0: levels are small integers, exact
in float32. The control relaxes the levels in bfloat16, where they are
exact too, so it cannot fail this number (PERF.md). Per iteration: 8 B
per edge (src and dst ids), 4 B per vertex read and 4 B written, 2
operations per edge.
"""
import numpy as np

from bench import reference

NUMBER = "bfs_mismatches"
LIMIT = 0
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 8, 8, 2


def answer(g, kwargs):
    return reference.bfs(g, kwargs["root"])


def measure(served, ref):
    return np.count_nonzero(served != ref)


def control(g, kwargs, dtype="bfloat16"):
    return reference.control_fixpoint(g, kwargs["root"], level=True,
                                      dtype=dtype)
