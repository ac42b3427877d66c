"""The one traffic generator: turns a traffic file into requests.

A traffic file (``bench/traffic/<name>.json``) holds only parameters::

    {"source": "where the mix comes from",
     "loop": "closed", "clients": 1,
     "apps": [{"app": "bfs", "per_deck": 4, "params": {"root": "vertex"}},
              {"app": "closeness", "per_deck": 1, "params": {"sources": 32}},
              {"app": "pagerank", "per_deck": 1, "kwargs": {"damping": 0.85}}],
     "check_per_app": {"bfs": 4}}

``clients`` closed-loop clients share one request stream: each sends
its next request when its last is answered. Requests come in decks: a
deck holds ``per_deck`` requests of every app, spread evenly over the
deck from an offset drawn per app and deck from the seed. So every seed
sends the same mix of work in another order, and any run of consecutive
requests, such as a window that ends mid-deck, holds each app within two
requests of its share. ``kwargs`` pass through as given; each ``params``
entry is drawn per request:

* ``"vertex"``: one vertex with out-degree > 0 (the Graph500 root rule);
* an integer ``k``: a tuple of ``k`` distinct such vertices;
* ``{"pool": p, "zipf": s}``: one of ``p`` such vertices drawn once per
  run from the seed, the one of rank r (from 1) with weight r ** -s.

``check_per_app`` caps how many answers of an app a run compares.
"""
from __future__ import annotations

from typing import Iterator, List, Tuple

import numpy as np

from .loader import BenchError

Request = Tuple[str, dict]


def validate(traffic: dict) -> None:
    clients = traffic.get("clients")
    if traffic.get("loop") != "closed" or not isinstance(clients, int) \
            or clients < 1:
        raise BenchError("traffic needs loop 'closed' and clients >= 1")
    counts = [a.get("per_deck") for a in traffic["apps"]]
    if not all(isinstance(c, int) and c >= 1 for c in counts):
        raise BenchError(f"per_deck counts {counts} are not all >= 1")


def candidates(num_vertices: int, src: np.ndarray) -> np.ndarray:
    """Vertices with out-degree > 0."""
    return np.flatnonzero(np.bincount(src, minlength=num_vertices))


class _Draws:
    """Per-request parameter draws of one run; pools are drawn once."""

    def __init__(self, cand: np.ndarray, rng: np.random.Generator):
        self.cand, self.rng, self.pools = cand, rng, {}

    def request(self, app: dict) -> Request:
        kwargs = dict(app.get("kwargs", {}))
        for key, spec in app.get("params", {}).items():
            kwargs[key] = self._param(app["app"], key, spec)
        return app["app"], kwargs

    def _param(self, app: str, key: str, spec):
        cand, rng = self.cand, self.rng
        if spec == "vertex":
            return int(cand[rng.integers(cand.size)])
        if isinstance(spec, int) and 0 < spec <= cand.size:
            return tuple(int(v) for v in
                         rng.choice(cand, size=spec, replace=False))
        if isinstance(spec, dict) and set(spec) == {"pool", "zipf"} \
                and 0 < spec["pool"] <= cand.size:
            if (app, key) not in self.pools:
                pool = rng.choice(cand, size=spec["pool"], replace=False)
                w = np.arange(1, pool.size + 1, dtype=np.float64) \
                    ** -float(spec["zipf"])
                self.pools[app, key] = (pool, w / w.sum())
            pool, p = self.pools[app, key]
            return int(pool[rng.choice(pool.size, p=p)])
        raise BenchError(f"{app}: unknown parameter spec {spec!r}")


def deck_order(counts: List[int], rng: np.random.Generator) -> List[int]:
    """One deck: ``counts[i]`` entries of app ``i``, each app's entries at
    positions (k + u_i) / counts[i] of the deck, u_i uniform."""
    keys = [((k + u) / n, i) for i, (n, u) in
            enumerate(zip(counts, rng.random(len(counts))))
            for k in range(n)]
    return [i for _, i in sorted(keys)]


def stream(traffic: dict, cand: np.ndarray, seed: int) -> Iterator[Request]:
    """The endless request sequence of one run."""
    validate(traffic)
    rng = np.random.default_rng([int(seed), 1])
    draws = _Draws(cand, rng)
    apps = traffic["apps"]
    counts = [a["per_deck"] for a in apps]
    while True:
        for i in deck_order(counts, rng):
            yield draws.request(apps[i])


def warmup(traffic: dict, cand: np.ndarray, seed: int) -> List[Request]:
    """One request per app of the mix (its own draws, not the window's)."""
    validate(traffic)
    draws = _Draws(cand, np.random.default_rng([int(seed), 2]))
    return [draws.request(a) for a in traffic["apps"]]
