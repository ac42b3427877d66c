"""The control of the comparison: the reference in bfloat16, per seed.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        [--requests 20] [--apps pagerank,sssp,wcc]

For each seed it makes the cell's graph and the first ``--requests``
requests of the window's stream (at most ``check_per_app`` of an app, as
a run compares; only ``--apps`` where given), computes each answer in
bfloat16 (``reference.control_answer``) and reads it as a run reads a
served answer (``compare.reading``). It prints one JSON line per seed
with the numbers and ``compare.verdict`` of them, which has to read
``correct`` false, and a last line with the smallest reading of each
number over the seeds: the upper readings the limits are set below.
The benchmark's own runs never run this. Pagerank runs for the
iterations the float64 reference takes under the app's stopping rule;
wcc starts from the vertex ids.
"""
from __future__ import annotations

import argparse
import itertools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import compare, graphgen, reference, traffic  # noqa: E402
from bench.loader import Bench  # noqa: E402


def control_readings(bench: Bench, workload: str, seed: int, n_requests: int,
                     dtype: str = "bfloat16", apps=None) -> dict:
    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    mix = bench.traffic(wl["traffic"])
    src, dst, w = graphgen.edges(cfg["generator"], cfg["scale"],
                                 cfg["edge_factor"], cfg["structure_seed"],
                                 seed)
    g = reference.Graph(1 << cfg["scale"], src, dst, w)
    per_app = mix.get("check_per_app", {})
    seen: dict = {}
    cache: dict = {}
    readings = []
    stream = traffic.stream(mix, traffic.candidates(g.n, src), seed)
    for app, kwargs in itertools.islice(stream, n_requests):
        if (apps and app not in apps) \
                or seen.get(app, 0) >= per_app.get(app, n_requests):
            continue
        seen[app] = seen.get(app, 0) + 1
        iters = (reference.pagerank(g, kwargs.get("damping", 0.85))[1]
                 if app == "pagerank" else 0)
        low = reference.control_answer(g, app, kwargs, iters, dtype)
        readings.append((app, compare.reading(g, app, kwargs, low, cache)))
    return compare.worst(readings)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--apps", default="")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    bench = Bench(ROOT)
    apps = set(filter(None, args.apps.split(",")))
    lowest: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        values = control_readings(bench, args.workload, seed, args.requests,
                                  apps=apps)
        for k, v in values.items():
            lowest[k] = min(lowest.get(k, v), v)
        correct, checks = compare.verdict(values, 0)
        print(json.dumps({"seed": seed, "correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t0,
                          "device": dev.device_kind}), flush=True)
    print(json.dumps({"workload": args.workload, "control_lowest": lowest,
                      "limits": compare.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
