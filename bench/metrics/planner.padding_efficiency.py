"""planner.padding_efficiency: real edges over padded edge slots.

A count from the packed payloads of the plan the window executed: the
real edges they hold over their blocks times ``E_BLK``. Layer: planner.
"""


def read(record):
    slots = record.plan.get("padded_edge_slots")
    return record.plan["num_edges"] / slots if slots else None
