"""The comparison that decides ``correct``, and its limits.

Per app one number, the worst over the answers compared. Each app is a
file of its own, ``bench/apps/<app>.py`` (found by name, as the metric
readers are), which holds:

* ``NUMBER``, the name of its compared number, and ``LIMIT``, with the
  reason for the limit in its docstring;
* either ``answer(g, kwargs)``, the plain reference answer of a request
  (``bench/reference.py``), and ``measure(served, ref)``, the number of
  one served answer against it; or ``violations(g, served)``, a reading
  that needs no reference answer (wcc);
* ``control(g, kwargs, dtype="bfloat16")``: the reference computed in
  the precision below the configuration's (``bench/control.py``);
* ``EDGE_BYTES``, ``VERTEX_BYTES``, ``EDGE_OPS``: its roofline counts
  per iteration (``bench/roofline.py``).

Every function takes the root of the benchmark tree whose app files it
reads; it defaults to this checkout.
"""
from __future__ import annotations

import hashlib
import json
from pathlib import Path
from types import ModuleType
from typing import Dict, Iterable, Tuple

import numpy as np

from . import reference
from .loader import load_file

ROOT = Path(__file__).resolve().parents[1]

# the reading of an answer that cannot be compared (wrong shape, NaN):
# finite, so that the result line stays plain JSON
UNCOMPARABLE = 1e30


def app(name: str, root: Path = ROOT) -> ModuleType:
    """The app's file; an app without one is an error."""
    return load_file(root, "apps", name)


def limits(root: Path = ROOT) -> Dict[str, float]:
    """The limit of every app's number in the tree."""
    mods = (app(p.stem, root)
            for p in sorted((Path(root) / "bench" / "apps").glob("*.py")))
    return {m.NUMBER: m.LIMIT for m in mods}


LIMITS: Dict[str, float] = limits()


def number_name(name: str, root: Path = ROOT) -> str:
    return app(name, root).NUMBER


def measure(mod: ModuleType, served, ref) -> float:
    """The compared number of one answer against its reference."""
    served = np.asarray(served)[:len(ref)]
    if served.shape != np.shape(ref):
        return UNCOMPARABLE
    return float(mod.measure(served, ref))


def reading(g: reference.Graph, name: str, kwargs: dict, served,
            cache: dict, root: Path = ROOT) -> float:
    """The compared number of one served answer. Equal requests share
    one reference answer, equal answers of an app without one share one
    reading, in ``cache``."""
    mod = app(name, root)
    if not hasattr(mod, "answer"):
        digest = hashlib.blake2b(np.ascontiguousarray(served).tobytes())
        key = (name, digest.hexdigest())
        if key not in cache:
            cache[key] = float(mod.violations(g, served))
        return cache[key]
    key = (name, json.dumps(kwargs, sort_keys=True))
    if key not in cache:
        cache[key] = mod.answer(g, kwargs)
    return measure(mod, served, cache[key])


def worst(readings: Iterable[Tuple[str, float]],
          root: Path = ROOT) -> Dict[str, float]:
    """Per number, the worst of ``(app, value)`` readings."""
    out: Dict[str, float] = {}
    for name, value in readings:
        number = number_name(name, root)
        v = UNCOMPARABLE if not np.isfinite(value) else value
        out[number] = max(out.get(number, v), v)
    return out


def verdict(values: Dict[str, float], failed: int,
            root: Path = ROOT) -> Tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    lim = limits(root)
    checks = {k: {"value": v, "limit": lim[k]}
              for k, v in sorted(values.items())}
    checks["failed_requests"] = {"value": failed, "limit": 0}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
