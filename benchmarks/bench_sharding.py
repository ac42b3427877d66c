"""Sharded (per-device lane ownership) vs single-device fused execution.

Runs in a SUBPROCESS with ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` and ``JAX_PLATFORMS=cpu`` (the parent process has already
imported jax with one device; device count is fixed at import). The
child never starts an accelerator backend: a chip belongs to one
process, and the parent may hold it. The inner run builds one store, plans
once, and compares the fused single-device executor against the sharded
one on the same cached plan:

  * parity gate: results bit-identical for pagerank (the acceptance
    criterion's 'sum' app — the mode where program-shape drift shows);
  * dispatch gates: per-device kernel dispatch counts must match the
    placement's per-device payload queues, their total must equal the
    fused path's dispatch count (sharding never adds launches), and the
    cross-device merge count must be exactly 1;
  * placement gate: the LPT balance bound (max load <= total/n + max);
  * streaming gate: after a 1% skewed-churn delta, at least half of the
    resident sharded lane payloads are reused without re-transfer
    (``shards_moved`` accounting);
  * timing (recorded, not gated — on forced-CPU devices the per-
    iteration broadcast/collect transfers dominate; the regime the
    placement targets is real multi-chip HBM): interleaved A/B
    per-iteration wall time.

Results go to stdout as usual AND to a ``BENCH_sharding.json`` artifact.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

N_DEVICES = 8
OUT_JSON = "BENCH_sharding.json"


def run(smoke: bool = False, out_json: str = OUT_JSON):
    """Spawn the forced-8-device CPU inner run and pass its output
    through."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": (os.environ.get("XLA_FLAGS", "") +
                         " --xla_force_host_platform_device_count="
                         f"{N_DEVICES}").strip()}
    cmd = [sys.executable, "-m", "benchmarks.bench_sharding", "--inner",
           "--out-json", out_json]
    if smoke:
        cmd.append("--smoke")
    r = subprocess.run(cmd, env=env, capture_output=True, text=True,
                       timeout=1200)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        sys.stderr.write(r.stderr)
        raise RuntimeError(
            f"bench_sharding inner run failed ({r.returncode})")
    with open(out_json) as f:
        return json.load(f)["records"]


def _inner(smoke: bool, out_json: str) -> None:
    import time

    import jax
    import numpy as np

    from repro import api
    from repro.core import gas
    from repro.core.types import Geometry
    from repro.graphs import datasets
    from repro.streaming import apply_delta, random_delta

    from .common import emit

    assert jax.device_count() == N_DEVICES, \
        f"inner run expected {N_DEVICES} devices, got {jax.device_count()}"

    # finer partitioning than the shared GEOM so there are enough lanes
    # to spread (same regime bench_fused measures dispatch scaling in)
    geom = Geometry(U=256, W=256, T=256, E_BLK=256, big_batch=4)
    graphs = ["ggs"] if smoke else ["ggs", "hws"]
    repeats = 3 if smoke else 5
    iters = 2
    records = []
    for name in graphs:
        g = datasets.load(name)
        app = gas.make_pagerank(max_iters=iters)
        store = api.GraphStore(g, geom=geom)
        cfg = api.PlanConfig(n_lanes=N_DEVICES)
        fused = store.executor(app, cfg, path="ref")
        sharded = store.executor(app, cfg, path="ref", shard=N_DEVICES)

        # -- parity gate (bit-identical) --------------------------------
        pf, mf = fused.run(max_iters=iters)
        ps, ms = sharded.run(max_iters=iters)
        assert mf["iterations"] == ms["iterations"]
        np.testing.assert_array_equal(pf, ps)

        # -- dispatch gates ---------------------------------------------
        df, ds = fused.dispatch_stats(), sharded.dispatch_stats()
        sh = sharded.sharded
        per_dev = ds["kernel_dispatches_per_device"]
        assert per_dev == [len(sh.payloads_of(d))
                           for d in range(N_DEVICES)], \
            "per-device dispatches do not match the placement queues"
        assert ds["kernel_dispatches"] == df["kernel_dispatches"], \
            "sharding changed the total kernel dispatch count"
        assert ds["cross_device_merges"] == 1, \
            "expected exactly one cross-device merge per iteration"
        # program-derived (not static-accounting) merge gate: the traced
        # merge+apply program must contain exactly ONE scatter op
        mt = sharded.merge_trace_stats()
        assert mt["merge_scatter_ops"] == 1, \
            (f"merge program contains {mt['merge_scatter_ops']} scatter "
             f"ops; the cross-device merge must be a single scatter-set")

        # -- placement gate ---------------------------------------------
        pl = sh.placement
        assert max(pl.loads) <= pl.lpt_bound() + 1e-12, \
            "placement exceeded the LPT balance bound"

        # -- timing (interleaved A/B; recorded, not gated) ---------------
        vf, vs = fused.init_props(), sharded.init_props()
        fused._iter_fn = fused._build_iteration()
        fused._iter_fn(vf, fused.aux, 0).block_until_ready()
        sharded._iterate(vs, 0).block_until_ready()
        ts_f, ts_s = [], []
        for _ in range(repeats):
            t0 = time.perf_counter()
            fused._iter_fn(vf, fused.aux, 0).block_until_ready()
            ts_f.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            sharded._iterate(vs, 0).block_until_ready()
            ts_s.append(time.perf_counter() - t0)
        t_f, t_s = float(np.median(ts_f)), float(np.median(ts_s))

        # -- streaming reuse gate ----------------------------------------
        # same degree-skew the streaming acceptance gate uses: hot 1% of
        # dsts absorb the churn, which DBG co-locates into few partitions
        delta = random_delta(g, churn=0.01, hot_frac=0.01,
                             base_fp=store.fingerprint())
        res = apply_delta(store, delta)
        st = res.stats
        assert st["shards_reused"] >= st["shards_moved"], \
            (f"expected >= half resident shard reuse at 1% churn, got "
             f"{st['shards_reused']} reused / {st['shards_moved']} moved")

        rec = {
            "graph": name, "V": g.num_vertices, "E": g.num_edges,
            "n_devices": N_DEVICES, "n_lanes": cfg.n_lanes,
            "t_iteration_fused_s": t_f,
            "t_iteration_sharded_s": t_s,
            "kernel_dispatches_per_device": per_dev,
            "cross_device_merges": ds["cross_device_merges"],
            "placement": sh.stats(),
            "delta": {k: st[k] for k in
                      ("dirty_partitions", "shards_moved",
                       "shard_bytes_moved", "shards_reused",
                       "shard_bytes_reused")},
        }
        records.append(rec)
        emit(f"sharding.{name}.iter", t_s * 1e6,
             f"fused={t_f * 1e6:.0f}us devices={N_DEVICES} "
             f"imbalance={pl.imbalance:.2f}")
        emit(f"sharding.{name}.dispatch", 0.0,
             f"per_device={per_dev} xmerges={ds['cross_device_merges']}")
        emit(f"sharding.{name}.reuse", 0.0,
             f"reused={st['shards_reused']} moved={st['shards_moved']} "
             f"bytes_reused={st['shard_bytes_reused']}")
        store.clear_plans()
    with open(out_json, "w") as f:
        json.dump({"benchmark": "sharded_vs_fused", "records": records},
                  f, indent=2)
    emit("sharding.artifact", 0.0, out_json)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--inner", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--out-json", default=OUT_JSON)
    args = ap.parse_args()
    if args.inner:
        _inner(args.smoke, args.out_json)
    else:
        run(smoke=args.smoke, out_json=args.out_json)
