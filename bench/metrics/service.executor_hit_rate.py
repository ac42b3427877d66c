"""service.executor_hit_rate: share of requests run on a cached executor.

A count: of the window's answered requests whose ``RequestMetrics``
carry ``executor_hit``, the share for which the service found a compiled
executor of the app's program in its cache (1.0: no request built one).
Layer: service (``serve_graph/service.py``).
"""


def read(record):
    hits = [r.executor_hit for r in record.requests
            if r.error is None and getattr(r, "executor_hit", None) is not None]
    return sum(hits) / len(hits) if hits else None
