"""executor.traces_per_req: iteration programs traced per request.

A count: the mean over the window's answered requests of the program's
``RequestMetrics.iteration_traces`` (0 where the service reused a cached
executor, 1 where it built one). Layer: executor (``core/executor.py``).
"""


def read(record):
    n = [getattr(r, "iteration_traces", None) for r in record.requests
         if r.error is None]
    n = [x for x in n if x is not None]
    return sum(n) / len(n) if n else None
