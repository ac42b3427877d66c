"""executor.iterations_per_req: mean iterations per answered request.

A count: the mean of ``meta["iterations"]`` that the executor returns
with each answer of the window. Layer: executor (``core/executor.py``).
"""


def read(record):
    its = [r.iterations for r in record.requests if r.error is None]
    return sum(its) / len(its) if its else None
