"""service.overhead_ms: the service's own share of a request's latency.

Median over the window's answered requests of ``t_total_ms -
t_execute_ms`` from the program's ``RequestMetrics`` (submit to answer,
less the executor's run): queueing, the store and plan cache look-ups and
handing the answer back. Layer: service (``serve_graph/service.py``).
"""
import statistics


def read(record):
    gaps = [r.t_total_ms - r.t_execute_ms for r in record.requests
            if r.error is None and r.t_total_ms is not None
            and r.t_execute_ms is not None]
    return statistics.median(gaps) if gaps else None
