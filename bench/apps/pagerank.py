"""pagerank: rank / out-degree of the app's pull power iteration.

Compared number ``pagerank_max_rel_err``: the largest |served - ref| /
ref over the vertices, against the float64 reference stopped by the
app's own rule (``reference.pagerank``). Limit 1e-4: sound float32 runs
read at most 2.5e-6 and the bfloat16 control at least 1.8e-2 on both
cells (PERF.md), so it sits 40x above the one and 180x below the other.
The control runs in bfloat16 for as many iterations as the reference.
Per iteration: 8 B per edge (src and dst ids), 4 B per vertex read and
4 B written, 2 operations per edge.
"""
import numpy as np

from bench import reference

NUMBER = "pagerank_max_rel_err"
LIMIT = 1e-4
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 8, 8, 2


def answer(g, kwargs):
    return reference.pagerank(g, kwargs.get("damping", 0.85))[0]


def measure(served, ref):
    ref = np.asarray(ref, np.float64)
    return np.max(np.abs(served.astype(np.float64) - ref) / ref)


def control(g, kwargs, dtype="bfloat16"):
    damping = kwargs.get("damping", 0.85)
    return reference.control_pagerank(
        g, damping, reference.pagerank(g, damping)[1], dtype)
