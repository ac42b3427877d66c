"""The comparison that decides ``correct``, and its limits.

Per app one number, the worst over the answers compared:

* ``pagerank_max_rel_err``: the largest |served - ref| / ref over the
  vertices (float64 reference);
* ``<app>_mismatches`` for bfs, sssp and closeness: how many vertices
  differ from the exact reference. Levels, dyadic path sums and
  reachability bits are exact in float32 / int32, so the limit is 0;
* ``wcc_violations``: ``reference.wcc_violations`` of the labels, 0 for
  the min-label fixpoint under some numbering of the vertices; limit 0.

The pagerank limit sits between the largest reading of sound runs and
the smallest reading of the bfloat16 control; ``PERF.md`` gives both.
"""
from __future__ import annotations

import hashlib
import json
from typing import Dict, Iterable, Tuple

import numpy as np

from . import reference

# the reading of an answer that cannot be compared (wrong shape, NaN):
# finite, so that the result line stays plain JSON
UNCOMPARABLE = 1e30

LIMITS: Dict[str, float] = {
    "pagerank_max_rel_err": 1e-4,
    "bfs_mismatches": 0,
    "sssp_mismatches": 0,
    "wcc_violations": 0,
    "closeness_mismatches": 0,
}


def number_name(app: str) -> str:
    return {"pagerank": "pagerank_max_rel_err",
            "wcc": "wcc_violations"}.get(app, f"{app}_mismatches")


def measure(app: str, served, ref) -> float:
    """The compared number of one answer against its reference."""
    served = np.asarray(served)[:len(ref)]
    if served.shape != np.shape(ref):
        return UNCOMPARABLE
    if app == "pagerank":
        ref = np.asarray(ref, np.float64)
        return float(np.max(np.abs(served.astype(np.float64) - ref) / ref))
    return float(np.count_nonzero(served != ref))


def reading(g: reference.Graph, app: str, kwargs: dict, served,
            cache: dict) -> float:
    """The compared number of one served answer. Equal requests share
    one reference answer, equal wcc answers one count, in ``cache``."""
    if app == "wcc":
        digest = hashlib.blake2b(np.ascontiguousarray(served).tobytes())
        key = ("wcc", digest.hexdigest())
        if key not in cache:
            cache[key] = float(reference.wcc_violations(g, served))
        return cache[key]
    key = (app, json.dumps(kwargs, sort_keys=True))
    if key not in cache:
        cache[key] = reference.answer(g, app, kwargs)
    return measure(app, served, cache[key])


def worst(readings: Iterable[Tuple[str, float]]) -> Dict[str, float]:
    """Per number, the worst of ``(app, value)`` readings."""
    out: Dict[str, float] = {}
    for app, value in readings:
        name = number_name(app)
        v = UNCOMPARABLE if not np.isfinite(value) else value
        out[name] = max(out.get(name, v), v)
    return out


def verdict(values: Dict[str, float], failed: int) -> Tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    checks = {k: {"value": v, "limit": LIMITS[k]}
              for k, v in sorted(values.items())}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
