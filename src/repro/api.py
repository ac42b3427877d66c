"""Layered public API for the heterogeneous graph engine.

Three composable layers (paper §IV–§V: the push-button flow separates
app-independent graph preparation from model-guided scheduling):

    GraphStore  — app-independent; DBG relabeling, dst-range
                  partitioning, Little/Big brick blockings. Built once
                  per (graph, Geometry), memoizes blockings and plans.
    Planner     — per PlanConfig (typed: mode/forced split/n_lanes/hw);
                  classifies partitions with the perf model and builds
                  the lane schedule. Cheap; cached on the store.
    Executor    — per (plan, app); device-resident lane entries and the
                  jit'd iteration loop (run / time_iteration /
                  time_lanes).

Quickstart::

    from repro import api
    from repro.graphs.rmat import rmat

    compiled = api.compile(rmat(12, 16, seed=7), "pagerank", n_lanes=8)
    props, meta = compiled.run()

Amortized multi-app use (build the store once, plan each app)::

    store = api.GraphStore(graph, geom=geom)
    for name in ("pagerank", "bfs", "wcc"):
        props, meta = store.plan_and_run(api.BUILTIN_APPS[name]())

Serving (multi-tenant: LRU of stores + request queue + coalescing —
see repro/serve_graph/)::

    with api.GraphService(byte_budget=512 << 20, workers=2) as svc:
        handles = [svc.submit(g, name) for name in api.BUILTIN_APPS]
        results = [h.result(timeout=120) for h in handles]

Streaming updates flow through :class:`GraphDelta` / :func:`apply_delta`
(see repro/streaming/); multi-device execution through
``compile(shard=...)`` / ``GraphStore.shard()`` (see repro/sharding/).

Serving at scale layers the control plane on top (see repro/control/):
``GraphService(pool=N)`` moves store builds and delta splices into
worker processes (:class:`WorkerPool`), submits carry ``priority`` /
``deadline`` / ``tenant`` through the model-guided scheduler with
:class:`TenantQuota` admission (typed :class:`QueueFull` /
:class:`QuotaExceeded` / :class:`DeadlineExpired` rejections), and
:class:`ControlPlane` + :func:`serve_jobs` expose persistent job
records over an HTTP JSON API::

    plane = api.ControlPlane(svc, job_store=api.JobStore("jobs.jsonl"))
    server, url = api.serve_jobs(plane)        # POST {url}/jobs, ...

Every job carries an end-to-end trace (:class:`Tracer`): spans cross
the scheduler queue and the worker-process boundary, per-lane spans
record measured-vs-estimated drift against the perf model, and
``GET {url}/jobs/{id}/trace`` returns Chrome-trace JSON for Perfetto
(see docs/OBSERVABILITY.md).

docs/ARCHITECTURE.md maps the whole system.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Union

from .autotune import (AutoTuner, Calibrator, DeviceSpec, RetunePolicy,
                       SpecRegistry)
from .control import (ControlPlane, DeadlineExpired, JobRecord,
                      JobScheduler, JobStore, QueueFull, QuotaExceeded,
                      RejectedJob, TenantQuota, WorkerCrashed, WorkerPool,
                      serve_jobs)
from .core.executor import Executor
from .core.gas import (BUILTIN_APPS, GASApp, make_bfs, make_closeness,
                       make_pagerank, make_sssp, make_wcc)
from .core.perf_model import HW, TPU_V5E, TPU_V5E_SCALED
from .core.planner import PlanBundle, PlanConfig, Planner
from .core.store import GraphStore
from .core.types import Geometry, SchedulePlan
from .graphs.formats import Graph, fingerprint as graph_fingerprint
from .obs import (DriftAccumulator, LaneFootprint, Span, SpanContext,
                  Tracer, UtilizationAccumulator)
from .serve_graph import (GraphService, GraphStoreCache, RequestHandle,
                          ServiceMetrics, UpdateResult)
from .sharding import (LanePlacement, ShardedExecutor, ShardedLanes,
                       place_lanes)
from .streaming import (GraphDelta, RegroupPolicy, apply_delta,
                        apply_delta_to_graph, chain_fingerprint,
                        compact_deltas, compose_deltas, grouping_drift,
                        grown_num_vertices, make_delta, random_delta,
                        rebuild_plans, reregister, splice_delta)

__all__ = [
    "AutoTuner", "BUILTIN_APPS", "Calibrator", "CompiledApp",
    "ControlPlane", "DeadlineExpired", "DeviceSpec",
    "DriftAccumulator", "Executor", "GASApp", "Geometry", "GraphDelta",
    "GraphService", "GraphStore", "GraphStoreCache", "HW", "JobRecord",
    "JobScheduler", "JobStore", "LaneFootprint", "LanePlacement",
    "PlanBundle",
    "PlanConfig", "Planner", "QueueFull", "QuotaExceeded",
    "RegroupPolicy", "RejectedJob",
    "RequestHandle", "RetunePolicy", "SchedulePlan", "ServiceMetrics",
    "ShardedExecutor", "SpecRegistry",
    "ShardedLanes", "Span", "SpanContext", "TPU_V5E", "TPU_V5E_SCALED",
    "TenantQuota", "Tracer", "UpdateResult",
    "UtilizationAccumulator", "WorkerCrashed",
    "WorkerPool", "apply_delta", "apply_delta_to_graph",
    "chain_fingerprint", "compact_deltas", "compile", "compose_deltas",
    "graph_fingerprint", "grouping_drift", "grown_num_vertices",
    "make_bfs", "make_closeness", "make_delta", "make_pagerank",
    "make_sssp", "make_wcc", "place_lanes", "random_delta",
    "rebuild_plans", "reregister", "serve_jobs", "splice_delta",
]


@dataclasses.dataclass
class CompiledApp:
    """The result of :func:`compile`: one app bound to a (possibly
    shared) GraphStore and a cached plan, ready to run. ``executor``
    is an :class:`Executor` or — under ``compile(shard=...)`` — a
    :class:`ShardedExecutor` (same run/time_iteration/stats surface;
    ``time_lanes`` exists only on the single-device form)."""

    store: GraphStore
    executor: Union[Executor, ShardedExecutor]

    @property
    def app(self) -> GASApp:
        return self.executor.app

    @property
    def config(self) -> PlanConfig:
        return self.executor.bundle.config

    @property
    def plan(self) -> SchedulePlan:
        return self.executor.plan

    def run(self, max_iters: Optional[int] = None, collect_history=False):
        return self.executor.run(max_iters=max_iters,
                                 collect_history=collect_history)

    def time_iteration(self, repeats: int = 5) -> float:
        return self.executor.time_iteration(repeats=repeats)

    def time_lanes(self, repeats: int = 3):
        return self.executor.time_lanes(repeats=repeats)

    def stats(self) -> dict:
        return self.executor.stats()


def compile(
    graph: Optional[Graph],
    app: Union[GASApp, str],
    *,
    geom: Optional[Geometry] = None,
    config: Optional[PlanConfig] = None,
    store: Optional[GraphStore] = None,
    path: Optional[str] = None,
    use_dbg: Optional[bool] = None,
    fuse_lanes: bool = True,
    shard=None,
    **cfg,
) -> CompiledApp:
    """Push-button entry point: prepare (or reuse) a GraphStore, plan,
    and materialize an executor for one app.

    ``app`` may be a :class:`GASApp` or a builtin name ("pagerank",
    "bfs", "sssp", "wcc", "closeness"). Extra keyword arguments become
    :class:`PlanConfig` fields (``n_lanes``, ``mode``, ``hw``,
    ``forced_little``, ``forced_big``). Pass ``store=`` to amortize
    preprocessing across apps; ``graph`` may then be None.
    ``fuse_lanes=False`` disables the packed-lane execution path (one
    kernel launch per plan entry instead of one per lane; bit-identical
    results — see README §Performance). ``shard`` switches to
    multi-device execution with per-device lane ownership (``True`` =
    every local device, int = first n, or an explicit device sequence;
    bit-identical to the single-device fused path — see README
    §Sharding); the returned :class:`CompiledApp` then wraps a
    :class:`ShardedExecutor`.

    Returns a :class:`CompiledApp` (run / time_iteration / stats).
    """
    if isinstance(app, str):
        if app not in BUILTIN_APPS:
            raise ValueError(f"unknown builtin app {app!r}; available: "
                             f"{sorted(BUILTIN_APPS)}")
        app = BUILTIN_APPS[app]()
    if config is not None and cfg:
        raise ValueError("pass either config= or PlanConfig kwargs, not both")
    if config is None:
        config = PlanConfig(**cfg)
    if store is None:
        if graph is None:
            raise ValueError("compile() needs a graph when no store= given")
        store = GraphStore(graph, geom=geom or Geometry(),
                           use_dbg=use_dbg if use_dbg is not None else True)
    else:
        # a shared store fixes graph/geometry/DBG — reject contradictions
        store.validate_compatible(graph=graph, geom=geom, use_dbg=use_dbg)
    return CompiledApp(store=store,
                       executor=store.executor(app, config, path=path,
                                               fuse_lanes=fuse_lanes,
                                               shard=shard))
