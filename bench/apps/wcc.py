"""wcc: min-label propagation from the store's own vertex numbering.

The labels depend on that numbering, which the benchmark does not know,
so there is no reference answer: the compared number
``wcc_violations`` is ``reference.wcc_violations`` of the served labels,
0 exactly when they are the min-label fixpoint under some numbering.
Limit 0: labels are integers, exact in float32. The control propagates
the vertex ids in bfloat16, which rounds ids above 256 (PERF.md). Per
iteration: 8 B per edge (src and dst ids), 4 B per vertex read and 4 B
written, 2 operations per edge.
"""
from bench import reference

NUMBER = "wcc_violations"
LIMIT = 0
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 8, 8, 2


def violations(g, served):
    return reference.wcc_violations(g, served)


def control(g, kwargs, dtype="bfloat16"):
    return reference.control_fixpoint(g, dtype=dtype)
