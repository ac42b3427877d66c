"""The control of the comparison: the reference in bfloat16, per seed.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 \
        [--requests 20] [--apps pagerank,sssp,wcc]

For each seed it makes the cell's graph and the first ``--requests``
requests of the window's stream (at most ``check_per_app`` of an app, as
a run compares; only ``--apps`` where given), computes each answer in
bfloat16 (the ``control`` of the app's file) and reads it as a run reads
a served answer (``compare.reading``). It prints one JSON line per seed
with the numbers and ``compare.verdict`` of them, which has to read
``correct`` false, and a last line with the smallest reading of each
number over the seeds: the upper readings the limits are set below.
The benchmark's own runs never run this.
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
    n, src, dst, w = graphgen.edges(cfg, seed, bench.root)
    g = reference.Graph(n, src, dst, w)
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
        low = compare.app(app, bench.root).control(g, kwargs, dtype)
        readings.append((app, compare.reading(g, app, kwargs, low, cache,
                                              bench.root)))
    return compare.worst(readings, bench.root)


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
        correct, checks = compare.verdict(values, 0, bench.root)
        print(json.dumps({"seed": seed, "correct": correct, "checks": checks,
                          "seconds": time.perf_counter() - t0,
                          "device": dev.device_kind}), flush=True)
    print(json.dumps({"workload": args.workload, "control_lowest": lowest,
                      "limits": compare.limits(bench.root)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
