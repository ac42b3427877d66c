"""Benchmark driver — one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines (see benchmarks/common.emit).

  Fig. 9   bench_pipelines      Big/Little measured vs modelled time
  Fig. 10  bench_heterogeneity  lane-combination sweep + model selection
  Fig. 12  bench_scalability    speedup vs number of lanes
  Tab. IV  bench_preprocessing  DBG / partition+schedule cost
  Tab. V   bench_sota           vs monolithic (ThunderGP-like) baseline
  Fig. 13  bench_roofline       resource-centric roofline analogue
  —        bench_serving        GraphService throughput/latency/caching
  —        bench_fused          fused vs per-entry execution (+ JSON)
  —        bench_streaming      delta apply vs full rebuild (+ JSON)
  —        bench_sharding       sharded vs single-device fused (+ JSON)
  —        bench_control_plane  p99 update latency, threads vs pool (+ JSON)
  —        bench_obs            tracing-off vs tracing-on overhead (+ JSON)
  —        bench_autotune       calibrate-and-replan gates (+ JSON)
  —        bench_profile        utilization profiler gates (+ JSON)

The chip benchmark of record is ``bench/run.py`` (``BENCHMARK.json``);
these suites time CPU or interpret-mode runs.
"""
from __future__ import annotations

import argparse
import os
import time

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list: pipelines,heterogeneity,scalability,"
                         "preprocessing,amortization,sota,roofline,serving,"
                         "fused,streaming,sharding,control_plane,obs,"
                         "autotune,profile")
    ap.add_argument("--quick", action="store_true",
                    help="smaller graph set (CI-speed)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiniest graphs (implies --quick; CI smoke tier)")
    args = ap.parse_args()

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        # one fixed in-checkout path (the path is part of the cache key)
        import jax
        jax.config.update("jax_compilation_cache_dir", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            ".jax_cache"))
    if args.smoke:
        args.quick = True
    want = (None if args.only == "all"
            else set(args.only.split(",")))

    from . import (bench_autotune, bench_control_plane, bench_fused,
                   bench_heterogeneity, bench_obs, bench_pipelines,
                   bench_preprocessing, bench_profile, bench_roofline,
                   bench_scalability, bench_serving, bench_sharding,
                   bench_sota, bench_streaming)

    suites = [
        ("pipelines", lambda: bench_pipelines.run(
            graphs=["ggs", "hws"] if args.quick else None)),
        ("heterogeneity", lambda: bench_heterogeneity.run(
            graphs=["r16s", "unif16"] if args.quick else None,
            n_lanes=4 if args.quick else 8)),
        ("scalability", lambda: bench_scalability.run(
            graphs=("ggs",) if args.quick else ("r16s", "g17s", "ggs"),
            lane_counts=(1, 2, 4) if args.quick else (1, 2, 4, 8, 16))),
        ("preprocessing", lambda: bench_preprocessing.run(
            graphs=("ggs", "ams") if args.quick
            else ("r16s", "g17s", "ggs", "ams", "hds", "tcs", "pks",
                  "ljs"))),
        ("amortization", lambda: bench_preprocessing.run_amortization(
            graphs=("ggs",) if args.quick else ("ggs", "g17s"),
            n_lanes=4 if args.quick else 8)),
        ("sota", lambda: bench_sota.run(
            graphs=("r16s",) if args.quick
            else ("r16s", "g17s", "tcs", "pks", "hws"),
            n_lanes=4 if args.quick else 8)),
        ("roofline", lambda: bench_roofline.run(
            graphs=("r16s",) if args.quick else ("r16s", "tcs"),
            n_lanes=4 if args.quick else 8)),
        # --quick has no mid tier for serving; it gets the smoke sizes
        ("serving", lambda: bench_serving.run(smoke=args.quick)),
        # acceptance target: >= 8 lanes even on the quick graph set (the
        # dispatch wall only shows at high entry counts)
        ("fused", lambda: bench_fused.run(
            graphs=["ggs"] if args.quick else ["ggs", "hws", "r16s"],
            lane_counts=(8,) if args.quick else (8, 16),
            repeats=3 if args.quick else 5)),
        # the >=5x acceptance gate runs at every tier (the quick tier
        # IS the acceptance graph; --smoke shrinks it further for CI
        # and loosens the gate — see bench_streaming). Always 5 repeats:
        # the gate is a median ratio and 3 samples is too noisy to gate.
        ("streaming", lambda: bench_streaming.run(smoke=args.smoke,
                                                  repeats=5)),
        # forced 8-device CPU subprocess (device count is fixed at jax
        # import, so the parent process can't host it); gates parity,
        # per-device dispatch counts, the single cross-device merge,
        # and streaming shard reuse at every tier
        ("sharding", lambda: bench_sharding.run(smoke=args.smoke)),
        # gates p99 update latency with a process pool <= threads-only
        # at every tier, and dumps the full ServiceMetrics snapshot
        # (JSON + Prometheus text) as artifacts
        ("control_plane", lambda: bench_control_plane.run(
            smoke=args.quick)),
        # gates the unconditional obs instrumentation: tracing-on
        # (coarse) p50 within 5% of tracing-off at every tier
        ("obs", lambda: bench_obs.run(
            graphs=["ggs"] if args.quick else ["ggs", "hws"],
            rounds=9 if args.smoke else 15)),
        # gates the model-guided loop: post-retune drift ratio_p50 in
        # [0.5, 2.0], retuned-vs-analytic measured makespan (interleaved
        # A/B), bit-identical results across the plan swap
        ("autotune", lambda: bench_autotune.run(
            graphs=["ggs"] if args.quick else ["ggs", "hws"],
            n_lanes=4 if args.quick else 8,
            rounds=3 if args.smoke else 5)),
        # gates the utilization profiler: analytic lane bytes within
        # ±10% of the jaxpr-derived count, profile-on p50 within 5%,
        # gauges on /metrics, dashboard/readyz up
        ("profile", lambda: bench_profile.run(
            graphs=["ggs"] if args.quick else ["ggs", "hws"],
            rounds=5 if args.smoke else 9)),
    ]
    print("name,us_per_call,derived")
    for name, fn in suites:
        if want and name not in want:
            continue
        t0 = time.time()
        fn()
        print(f"suite.{name},{(time.time() - t0) * 1e6:.0f},done",
              flush=True)


if __name__ == "__main__":
    main()
