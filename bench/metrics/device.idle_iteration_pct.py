"""device.idle_iteration_pct: device idle in the host-driven loop.

Percent of the traced window in which the device runs nothing and the
innermost program span open on the host is ``executor.iteration``,
``executor.sync`` or ``executor.converged``: dispatch, the wait and the
eager convergence check of each iteration (``bench/spanreduce.py``).
Layer: device.
"""
from bench import spanreduce


def read(record):
    return spanreduce.idle_pct(getattr(record, "spans", None),
                               spanreduce.HOST_LOOP)
