"""Shared benchmark utilities: CPU-calibrated perf model + CSV output."""
from __future__ import annotations

import sys
import time

import numpy as np

from repro import api
from repro.core import gas, perf_model
from repro.core.types import Geometry
from repro.graphs import datasets

GEOM = Geometry(U=4096, W=512, T=512, E_BLK=256, big_batch=8)

# Datasets per benchmark tier (CPU wall-time budget)
SMALL = ["ggs", "ams", "g17s", "hws"]
MEDIUM = ["r16s", "tcs", "pks", "unif16"]
LARGE = ["r18s", "hds", "bbs", "ljs"]


def emit(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")
    sys.stdout.flush()


def store_for(graph, geom=GEOM) -> api.GraphStore:
    """Construct a fresh GraphStore (NOT memoized — run_amortization's
    rebuild baseline relies on that). Benchmarks hold onto the returned
    store and share it across every plan mode / lane count they sweep —
    the amortization the layered API exists for."""
    return api.GraphStore(graph, geom=geom)


def cpu_calibrated_hw(graph_or_store, app=None, geom=GEOM, n_samples=12,
                      use_cache=True):
    """Calibrate the perf model's coefficients on this host by timing a
    few partitions on both pipeline types (the paper benchmarks memory
    latency to fit Eq. 4's a and b; we least-squares all four terms).

    Results are cached as a device spec per (host, geometry) in the
    autotune SpecRegistry (REGRAPH_SPEC_DIR, default .regraph_specs/),
    so a multi-benchmark run calibrates once; the cached path returns
    ``(hw, [])``. ``use_cache=False`` forces a fresh calibration (and
    refreshes the spec)."""
    from repro.autotune import DeviceSpec, SpecRegistry, \
        default_device_kind, geometry_key
    registry = SpecRegistry()
    kind = "bench-" + default_device_kind()
    if use_cache:
        spec = registry.get(kind, geom)
        if spec is not None and spec.source == "bench":
            return spec.hw, []
    app = app or gas.make_pagerank(max_iters=2)
    store = (graph_or_store if isinstance(graph_or_store, api.GraphStore)
             else store_for(graph_or_store, geom))
    from repro.core.executor import init_props
    from repro.kernels import ops
    import jax
    vprops = init_props(store, app)
    samples = []
    infos = sorted([i for i in store.infos if i.num_edges > 0],
                   key=lambda i: -i.num_edges)
    for i in infos[:n_samples]:
        for kind, work in (("little", store.little_work(i.pid)),
                           ("big", store.big_work((i.pid,)))):
            entry = ops.materialize_entry(work, 0, work.n_blocks)
            if entry is None:
                continue
            f = jax.jit(lambda vp: ops.run_entry(
                entry, vp, app.scatter, app.gather, "ref")[0])
            f(vprops).block_until_ready()
            f(vprops).block_until_ready()
            ts = []
            for _ in range(5):
                t0 = time.perf_counter()
                f(vprops).block_until_ready()
                ts.append(time.perf_counter() - t0)
            samples.append((i, store.geom, kind, float(np.median(ts))))
    hw, diag = perf_model.calibrate_full(samples, perf_model.TPU_V5E)
    try:
        registry.put(DeviceSpec(
            device_kind=kind, geom_key=geometry_key(geom), hw=hw,
            version=1, created_at=time.time(), source="bench", fit=diag))
    except OSError:
        pass   # read-only checkout: caching is best-effort
    return hw, samples


def mteps(graph, seconds_per_iter: float) -> float:
    return graph.num_edges / seconds_per_iter / 1e6
