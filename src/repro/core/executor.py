"""Executor — per-(plan, app) materialization and the jit'd run loop.

The Executor is the only layer that touches the device: it turns the
plan's lane queues into device-resident payloads, builds the jit'd
iteration (Scatter+Gather kernels → merge → Apply), and owns ``run`` /
``time_iteration`` / ``time_lanes``. The store's aux (out-degrees etc.)
is shared across every Executor on the same store, so running five apps
re-uploads nothing app-independent.

Execution is FUSED by default: each lane is one packed payload run as a
single ``pallas_call`` (``kernels.ops.run_lane``) and the per-iteration
merge is one tile-indexed scatter-set over all lanes' output tiles —
kernel dispatches and trace size scale with the number of lanes, not
the number of materialized plan entries. ``fuse_lanes=False`` restores
the one-launch-per-entry path (bit-identical results; useful for A/B
benchmarks and for debugging a single entry).
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..kernels import ops
from . import perf_model
from .gas import GASApp, GATHER_IDENTITY
from .planner import PlanBundle


def init_props(store, app: GASApp):
    """Initial padded property vector for one app on a store (in DBG
    ids). Needs only store-level state — callers that never execute a
    plan (e.g. perf-model calibration) use this directly instead of
    building an Executor."""
    aux = store.aux
    p = app.init(aux | {
        "outdeg": np.asarray(aux["outdeg"]),
        "perm": store.perm,
    })
    full = np.full(store.V_pad, GATHER_IDENTITY[app.gather],
                   np.int32 if app.gather == "or" else np.float32)
    full[:p.shape[0]] = p[:store.V_pad]
    if app.name == "pagerank":
        full[store.graph.num_vertices:] = 0.0
    return jnp.asarray(full)


def _sub_jaxprs(v):
    """Yield every jaxpr held by one eqn param value: raw Jaxpr,
    ClosedJaxpr, or tuples/lists of either (lax.cond's ``branches``)."""
    if hasattr(v, "eqns"):                        # raw Jaxpr
        yield v
    elif hasattr(getattr(v, "jaxpr", None), "eqns"):
        yield v.jaxpr                             # ClosedJaxpr
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def _count_jaxpr_eqns(jaxpr) -> int:
    """Total equations including nested (pjit / pallas / cond branch)
    sub-jaxprs — the trace-size measure the fused path collapses."""
    n = len(jaxpr.eqns)
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                n += _count_jaxpr_eqns(sub)
    return n


# the name of the fused iteration function in ``_iteration_fn``, which
# JAX traces (``obs.JitCounts.traced``) and compiles (``jit_iteration``)
# under
ITERATION_PROGRAM = "iteration"


class Executor:
    """Per-(plan, app) single-device executor.

    Parameters
    ----------
    store:   the :class:`~.store.GraphStore` the plan was built on
             (supplies aux, V_pad, perm; shared across executors).
    bundle:  the (cached) :class:`~.planner.PlanBundle` to execute;
             its materialized payloads are memoized on the bundle, so
             every app on the same plan shares device memory.
    app:     the :class:`~.gas.GASApp` whose scatter/gather/apply UDFs
             bind at run time.
    path:    kernel path — "pallas" (Mosaic-compiled on a TPU, Pallas
             interpret mode on hosts without one) or "ref" (pure-jnp
             oracle; the default off-TPU).
    fuse_lanes: True (default) runs each lane as ONE packed kernel
             launch; False launches per plan entry. Both paths are
             bit-identical (they share the single-merge program
             structure) — see the module docstring.

    Invariants: ``run`` returns properties in ORIGINAL vertex ids;
    one iteration dispatches exactly one merge (``dispatch_stats``);
    the multi-device counterpart is
    :class:`repro.sharding.executor.ShardedExecutor` (same surface,
    minus ``time_lanes``/``trace_stats``).
    """

    def __init__(self, store, bundle: PlanBundle, app: GASApp,
                 path: Optional[str] = None, fuse_lanes: bool = True,
                 drift_parent: Optional[obs.DriftAccumulator] = None,
                 calibrator=None,
                 util_parent: Optional[obs.UtilizationAccumulator] = None,
                 profile: bool = True):
        self.store = store
        self.bundle = bundle
        self.app = app
        self.geom = store.geom
        self.path = path or ops.default_path()
        self.V_pad = store.V_pad
        self.fuse_lanes = bool(fuse_lanes)
        # measured-vs-model drift; chains to the service-level
        # accumulator when this executor runs under a GraphService
        self.drift = obs.DriftAccumulator(parent=drift_parent)
        # pipeline utilization profiler (repro.obs.profile): analytic
        # lane footprints × measured lane times → achieved GB/s and
        # %-of-peak; chains to the service-level accumulator like drift.
        # profile=False skips footprint derivation and sampling entirely
        # (the A/B knob bench_profile's overhead gate exercises).
        self.profile = bool(profile)
        self.util = obs.UtilizationAccumulator(parent=util_parent)
        # %-of-peak denominator: the published HBM peak of the device
        # this executor runs on; None (no utilization) for a device kind
        # the table does not know
        self._peak_bps = perf_model.device_peak_bandwidth_bps(
            jax.devices()[0].device_kind)
        self._footprints = None  # lazy obs.lane_footprints
        self._lane_est = perf_model.lane_estimates(bundle.plan)
        # the estimate a measured iteration is compared against for the
        # "makespan" drift kind: plan.est_makespan assumes lanes run in
        # parallel (the device model); under a serial-host calibration
        # (combine == "sum") this executor runs lanes back-to-back, so
        # the like-for-like estimate is the SUM of lane estimates —
        # otherwise a perfectly-fitted model on a well-balanced plan
        # would show ~n_lanes of phantom drift and thrash the retuner
        if bundle.config.hw.combine == "sum":
            self._est_iteration = sum(e for e, _ in self._lane_est)
        else:
            self._est_iteration = bundle.plan.est_makespan
        # optional autotune sink: measured lane timings land here as
        # (feature row, kind, seconds) calibration samples — both from
        # traced runs and from time_lanes sweeps (repro.autotune)
        self._calibrator = calibrator
        self._lane_rows = None   # lazy perf_model.lane_feature_rows

        t0 = time.perf_counter()
        # shared across every app on this plan (memoized on the bundle);
        # only the form this executor runs is materialized
        if self.fuse_lanes:
            self.packed_lanes: List[List[dict]] = bundle.packed_lanes()
            self._payloads = [p for lane in self.packed_lanes for p in lane]
        else:
            self.packed_lanes = None
            self._payloads = [p for lane in bundle.lane_entries()
                              for p in lane]
        self.t_materialize = time.perf_counter() - t0
        self._arrays = [ops.device_arrays(p) for p in self._payloads]

        self.aux = store.aux
        self._iter_fn = None
        self._build_lock = threading.Lock()   # one trace of _iter_fn
        self._lane_fns = None   # cached per-lane jits for time_lanes
        self._traced_fns = None  # cached (lane fns, merge_apply) pair

    @property
    def plan(self):
        return self.bundle.plan

    @property
    def lane_entries(self) -> List[List[dict]]:
        """Per-entry payloads (legacy surface; the fused executor only
        materializes these on first access)."""
        return self.bundle.lane_entries()

    # ------------------------------------------------------------------
    @property
    def accum_dtype(self):
        return jnp.int32 if self.app.gather == "or" else jnp.float32

    def footprints(self):
        """Per-lane analytic :class:`~repro.obs.profile.LaneFootprint`
        (None for snapped-away lanes), derived once from the payload
        structure this executor actually runs — the byte model the
        utilization samples and ``jaxpr_lane_bytes`` validation share."""
        if self._footprints is None:
            lanes = (self.packed_lanes if self.fuse_lanes
                     else self.bundle.lane_entries())
            self._footprints = obs.lane_footprints(lanes, self.V_pad)
        return self._footprints

    def _util_add(self, lane_idx: int, kind: str, measured_s: float,
                  span=None):
        """Fold one measured lane execution into the utilization
        accumulator (and onto the live ``executor.lane`` span when one
        is open). No-op with ``profile=False``."""
        if not self.profile:
            return None
        fps = self.footprints()
        fp = fps[lane_idx] if lane_idx < len(fps) else None
        if fp is None:
            return None
        gbps = (fp.hbm_bytes / measured_s / 1e9 if measured_s > 0
                else 0.0)
        if span is not None:
            span.set(hbm_bytes=fp.hbm_bytes, flops=fp.flops,
                     gbps=round(gbps, 3))
        self.util.add(fp.kind, fp.hbm_bytes, fp.flops, measured_s,
                      peak_bps=self._peak_bps or 0.0, lane=lane_idx)
        return gbps

    def _run_payload(self, payload, vprops):
        """Dispatch one device payload (packed lane or single entry)."""
        run = ops.run_lane if self.fuse_lanes else ops.run_entry
        return run(payload, vprops, self.app.scatter, self.app.gather,
                   self.path)

    def _iteration_fn(self):
        """The raw (un-jitted) one-iteration function — separate from
        :meth:`_build_iteration` so trace-size reporting can inspect the
        jaxpr without a compiled-call wrapper in the way.

        Both paths share the SAME single ``merge_all`` (one tile-indexed
        scatter-set over every payload's output tiles) and differ only
        in kernel-launch granularity — one launch per packed lane vs one
        per entry. Keeping the merge+apply region structurally identical
        is what makes the two paths bit-identical: XLA re-fuses
        value-equal scatter chains differently per program shape, which
        shows up as 1-ULP drift in 'sum' apps.

        The payload arrays are ARGUMENTS (``arrays``, one dict per
        payload, as :func:`~repro.kernels.ops.device_arrays` gives them),
        not closure constants, so a multi-GB edge stream is never folded
        into the lowered module."""
        app, geom = self.app, self.geom
        payloads = self._payloads
        ident = GATHER_IDENTITY[app.gather]
        dt = self.accum_dtype

        def iteration(vprops, aux, it, arrays):
            accum = jnp.full((self.V_pad,), ident, dt)
            outs = [self._run_payload({**p, **a}, vprops)
                    for p, a in zip(payloads, arrays)]
            accum = ops.merge_all(accum, outs, geom.T)
            return app.apply(accum, vprops, aux, it)

        return iteration

    def _build_iteration(self):
        """``fn(vprops, aux, it)``: the jitted iteration with this
        executor's payload arrays bound as arguments."""
        fn = jax.jit(self._iteration_fn())
        arrays = self._arrays
        return lambda vprops, aux, it: fn(vprops, aux, it, arrays)

    def lower_iteration(self):
        """Lower the fused iteration exactly as :meth:`run` compiles it;
        ``.as_text()`` of the result shows which kernels it launches
        (``tpu_custom_call`` for Mosaic-compiled Pallas)."""
        return jax.jit(self._iteration_fn()).lower(
            self.init_props(), self.aux, 0, self._arrays)

    def init_props(self):
        return init_props(self.store, self.app)

    def _build_traced_fns(self):
        """Per-lane jitted fns returning the RAW (tiles, tile_idx)
        outputs — no merge — plus ONE jitted merge+apply. Together they
        run an iteration with per-lane timing visibility while keeping
        the single-merge+apply program region of :meth:`_iteration_fn`
        (the structure bit-identity depends on); only kernel-launch
        granularity differs."""
        lanes = (self.packed_lanes if self.fuse_lanes
                 else self.bundle.lane_entries())
        lane_fns = []
        for lane in lanes:
            if not lane:
                lane_fns.append(None)
                continue

            def lane_fn(vp, lane=lane):
                return [self._run_payload(p, vp) for p in lane]

            lane_fns.append(jax.jit(lane_fn))

        app, geom = self.app, self.geom
        ident = GATHER_IDENTITY[app.gather]
        dt = self.accum_dtype

        def merge_apply(vprops, outs, aux, it):
            accum = jnp.full((self.V_pad,), ident, dt)
            accum = ops.merge_all(accum, outs, geom.T)
            return app.apply(accum, vprops, aux, it)

        return lane_fns, jax.jit(merge_apply)

    def _run_iteration_traced(self, vprops, it):
        """One iteration under an active tracer with lane detail: a span
        per lane (carrying the model estimate, so every trace doubles as
        a calibration sample), one for merge+apply, drift samples for
        both levels (``run`` opens the ``executor.iteration`` around
        them)."""
        lane_fns, merge_apply = self._traced_fns
        est = self._lane_est
        outs = []
        for li, f in enumerate(lane_fns):
            if f is None:
                continue
            e_i, kind_i = est[li] if li < len(est) else (0.0, "mixed")
            t0 = time.perf_counter()
            n_entries = (len(self.plan.lanes[li])
                         if li < len(self.plan.lanes) else 0)
            with obs.span("executor.lane", "executor", lane=li,
                          kind=kind_i, est_time=e_i,
                          n_entries=n_entries) as lane_sp:
                lane_out = f(vprops)
                jax.block_until_ready(lane_out)
                measured = time.perf_counter() - t0
                # achieved-bandwidth counters ride on the span the
                # trace already carries (bytes are analytic, so the
                # only run-path cost is the divide + dict update)
                self._util_add(li, kind_i, measured, span=lane_sp)
            self.drift.add(kind_i, e_i, measured)
            self._calib_add(li, kind_i, measured)
            outs.extend(lane_out)
        with obs.span("executor.merge_apply", "executor", it=it):
            new = merge_apply(vprops, outs, self.aux, it)
            new.block_until_ready()
        return new

    def run(self, max_iters: Optional[int] = None, collect_history=False,
            start: Optional[GASApp] = None):
        """Run to convergence; returns props in ORIGINAL vertex ids.

        ``start`` supplies the initial properties (its ``init``) and
        ``self.app`` the iteration: it is an app of the same program,
        differing only in :data:`~.gas.START_KWARGS` (a new bfs root), so
        one executor serves every start state without a re-trace.
        Default: ``self.app``.

        When a tracer with ``lane_detail`` is active on this thread, the
        iteration switches to the traced per-lane path (extra dispatches
        per iteration, bit-identical results — see
        :meth:`_build_traced_fns`); otherwise the single fused jit runs
        and only the per-iteration makespan drift sample is taken.

        Spans (each also a profiler annotation, so a profiler trace
        shows them with no tracer installed): ``executor.iteration``
        per iteration, holding on the fused path ``executor.compile``
        (the first call of a freshly built iteration jit),
        ``executor.sync`` (the host waits for the device) and, on both
        paths, ``executor.converged``; then ``executor.readback``."""
        tracer = obs.current_tracer()
        lane_detail = (tracer is not None and tracer.lane_detail
                       and obs.current_ctx() is not None)
        if lane_detail and self._traced_fns is None:
            self._traced_fns = self._build_traced_fns()
        vprops = init_props(self.store, start or self.app)
        iters = max_iters or self.app.max_iters
        est_makespan = self._est_iteration
        history = []
        it_done = 0
        for it in range(iters):
            with obs.span("executor.iteration", "executor", it=it):
                t_it = time.perf_counter()
                if lane_detail:
                    new = self._run_iteration_traced(vprops, it)
                else:
                    new = self._call_iteration(vprops, it)
                    with obs.span("executor.sync", "executor"):
                        new.block_until_ready()
                self.drift.add("makespan", est_makespan,
                               time.perf_counter() - t_it)
                if collect_history:
                    history.append(np.asarray(new))
                with obs.span("executor.converged", "executor"):
                    done = self.app.converged(vprops, new, it)
            it_done = it + 1
            vprops = new
            if done:
                break
        with obs.span("executor.readback", "executor"):
            out = np.asarray(vprops)[self.store.perm]  # back to original ids
        return out, {"iterations": it_done, "history": history}

    def _call_iteration(self, vprops, it):
        """One call of the fused iteration jit. The first call builds it
        and runs under ``_build_lock``, and the jit is published only
        after it, so threads sharing this executor trace it once."""
        fn = self._iter_fn
        if fn is None:
            with self._build_lock:
                fn = self._iter_fn
                if fn is None:
                    fn = self._build_iteration()
                    new = self._first_call(fn, vprops, it)
                    self._iter_fn = fn
                    return new
        return fn(vprops, self.aux, it)

    def _first_call(self, fn, vprops, it):
        """The first call of a freshly built iteration jit, under an
        ``executor.compile`` span: jaxpr trace, lowering and the compile
        or persistent-cache fetch, up to the dispatch's return. The span
        carries what this thread traced and compiled meanwhile."""
        c0 = obs.jitcount.thread_counts()
        with obs.span("executor.compile", "executor") as sp:
            new = fn(vprops, self.aux, it)
            d = obs.jitcount.thread_counts() - c0
            sp.set(traces=d.traces, compiles=d.compiles,
                   cache_hits=d.cache_hits, cache_misses=d.cache_misses)
        return new

    # ------------------------------------------------------------------
    def time_iteration(self, repeats: int = 5) -> float:
        """Median wall time of one full iteration (all lanes, serialised —
        single host device). Used by benchmarks."""
        vprops = self.init_props()
        self._call_iteration(vprops, 0).block_until_ready()  # warmup
        ts = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            self._iter_fn(vprops, self.aux, 0).block_until_ready()
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    def _build_lane_fns(self):
        """One jitted fn per lane, built once and cached for the life of
        the executor (same lifetime as ``_iter_fn``) — repeated
        ``time_lanes`` sweeps must not pay a re-trace per call."""
        ident = GATHER_IDENTITY[self.app.gather]
        dt = self.accum_dtype
        lanes = (self.packed_lanes if self.fuse_lanes
                 else self.bundle.lane_entries())
        fns = []
        for lane in lanes:
            if not lane:
                fns.append(None)
                continue

            def lane_fn(vp, lane=lane):
                accum = jnp.full((self.V_pad,), ident, dt)
                outs = [self._run_payload(p, vp) for p in lane]
                return ops.merge_all(accum, outs, self.geom.T)

            fns.append(jax.jit(lane_fn))
        return fns

    def time_lanes(self, repeats: int = 3):
        """Per-lane wall times — the quantity the scheduler balances.
        On real hardware lanes run concurrently; on the host we time them
        one by one and report max() as the modelled makespan analogue."""
        if self._lane_fns is None:
            self._lane_fns = self._build_lane_fns()
        vprops = self.init_props()
        out = []
        for i, f in enumerate(self._lane_fns):
            if f is None:
                out.append(0.0)
                continue
            f(vprops).block_until_ready()
            ts = []
            for _ in range(repeats):
                t0 = time.perf_counter()
                f(vprops).block_until_ready()
                ts.append(time.perf_counter() - t0)
            med = float(np.median(ts))
            out.append(med)
            # every calibration sweep is also a drift + utilization sample
            if i < len(self._lane_est):
                e_i, kind_i = self._lane_est[i]
                self.drift.add(kind_i, e_i, med)
                self._calib_add(i, kind_i, med)
                self._util_add(i, kind_i, med)
        return out

    def _calib_add(self, lane_idx: int, kind: str, measured_s: float):
        """Forward one measured lane time to the attached Calibrator as a
        (feature row, kind, seconds) sample. Rows are per-lane sums of
        unit-coefficient model terms (perf_model.lane_feature_rows) and
        depend only on the plan + base HW constants, so they are computed
        once per executor."""
        if self._calibrator is None:
            return
        if self._lane_rows is None:
            self._lane_rows = perf_model.lane_feature_rows(self.bundle)
        if lane_idx < len(self._lane_rows):
            self._calibrator.add_lane(self._lane_rows[lane_idx], kind,
                                      measured_s)

    # ------------------------------------------------------------------
    def memory_footprint(self) -> int:
        """Device bytes pinned by this executor's payloads. NOTE:
        payloads are memoized on the bundle, so executors sharing a plan
        share these bytes — treat this as an attribution for cache
        budgeting, not an exclusive-ownership measure."""
        return sum(ops.payload_nbytes(p) for p in self._payloads)

    def dispatch_stats(self) -> dict:
        """Static launch accounting: what one iteration dispatches. The
        fused path turns O(entries) kernel launches + merges into
        O(lanes) launches + ONE merge — the per-entry numbers are
        reported alongside so callers can see the delta."""
        num_entries = sum(p["n_entries"] for p in self._payloads)
        return {
            "fuse_lanes": self.fuse_lanes,
            "num_entries": num_entries,
            "kernel_dispatches": len(self._payloads),
            "merge_dispatches": 1 if self._payloads else 0,
            "payload_bytes": self.memory_footprint(),
        }

    def trace_stats(self) -> dict:
        """Abstractly trace one iteration and measure the jaxpr — the
        trace/compile-size cost the fused path collapses. Traces fresh
        on every call (no caching) so fused/per-entry A/Bs are honest;
        don't call it on a hot path."""
        fn = self._iteration_fn()
        vprops = self.init_props()
        t0 = time.perf_counter()
        jaxpr = jax.make_jaxpr(fn)(vprops, self.aux, 0, self._arrays)
        t_trace = time.perf_counter() - t0
        return {
            "jaxpr_eqns": _count_jaxpr_eqns(jaxpr.jaxpr),
            "t_trace_ms": t_trace * 1e3,
        }

    def utilization(self) -> dict:
        """The pipeline-utilization report: the accumulator's per-kind
        achieved GB/s / %-of-peak / intensity plus this executor's
        static per-lane footprints and bandwidth ceiling. Empty
        ``kinds``/``lanes`` until a traced run or ``time_lanes`` sweep
        has produced measured samples."""
        rep = self.util.report()
        rep["peak_bandwidth_gbps"] = (self._peak_bps / 1e9
                                      if self._peak_bps else None)
        rep["profile"] = self.profile
        rep["footprints"] = [fp.as_dict() if fp is not None else None
                             for fp in (self.footprints()
                                        if self.profile else [])]
        return rep

    def stats(self) -> dict:
        b, store = self.bundle, self.store
        padded_edges = sum(p["n_blocks"] for p in self._payloads) \
            * self.geom.E_BLK
        real_edges = sum(p["num_real_edges"] for p in self._payloads)
        return {
            "V": store.graph.num_vertices, "E": store.graph.num_edges,
            "partitions": len(b.infos),
            "dense": len(b.dense), "sparse": len(b.sparse),
            "little_lanes": b.plan.num_little_lanes,
            "big_lanes": b.plan.num_big_lanes,
            "est_makespan": b.plan.est_makespan,
            "t_dbg_ms": store.t_dbg * 1e3,
            # plan-local: partitioning + blocking THIS plan paid for
            # (cache-hit blockings cost 0) + scheduling
            "t_partition_schedule_ms":
                (store.t_partition + b.t_block + b.t_plan) * 1e3,
            "t_plan_ms": b.t_plan * 1e3,
            # padding efficiency of the brick layout actually executed
            "num_real_edges": real_edges,
            "num_padded_edges": padded_edges,
            "padding_efficiency": (real_edges / padded_edges
                                   if padded_edges else 1.0),
            "drift": self.drift.report(),
            "utilization": self.utilization(),
            **self.dispatch_stats(),
        }
