"""planner.plan_s: seconds to plan the graph with ``PlanConfig()``.

The benchmark's host timer around ``store.plan(config)``: blocking,
classification and the lane schedule (not the upload). Layer: planner
(``core/planner.py``).
"""


def read(record):
    return record.setup.get("plan_s")
