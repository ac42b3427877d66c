"""Observability tests: tracer/span mechanics, carriers across the
queue and process-pool boundaries, per-lane perf-model drift, the
Chrome-trace export through the HTTP job API, spans in a JAX profiler
trace, and the per-thread compile counts.

Tracer unit tests are pure Python. The integration tests run tiny RMAT
graphs on the ref path (control-plane suite geometry); the pool test
pays one spawn startup and is the slowest item here.
"""
import glob
import json
import os
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro import obs
from repro.control import ControlPlane
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.core.executor import ITERATION_PROGRAM
from repro.obs import DriftAccumulator, SpanContext, Tracer, jitcount
from repro.serve_graph import GraphService

GEOM = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)
WAIT = 300.0


@pytest.fixture(scope="module")
def g1():
    return rmat(8, 6, seed=1, weighted=True)


# ---------------------------------------------------------------------------
# tracer / span mechanics (no jax)
# ---------------------------------------------------------------------------

class TestTracer:
    def test_span_off_is_noop(self):
        # library code calls obs.span unconditionally; with no tracer
        # bound to the thread nothing is recorded into any Tracer
        tr = Tracer()
        with obs.span("anything") as sp:
            sp.set(x=1).end()           # all inert
            assert sp.context is None and obs.current_ctx() is None
        assert tr.stats()["spans_recorded"] == 0 and tr.trace_ids() == []

    def test_nesting_follows_thread_local_context(self):
        tr = Tracer()
        root = tr.start_trace("root", "test")
        with tr.activate(root.context):
            with obs.span("outer") as outer:
                with obs.span("inner") as inner:
                    assert inner.parent_id == outer.span_id
                assert obs.current_ctx().span_id == outer.span_id
            assert obs.current_ctx() == root.context
        root.end()
        spans = {d["name"]: d for d in tr.export(root.trace_id)}
        assert spans["outer"]["parent_id"] == root.span_id
        assert spans["inner"]["parent_id"] == spans["outer"]["span_id"]
        assert all(d["trace_id"] == root.trace_id for d in spans.values())

    def test_end_is_idempotent_and_records_once(self):
        tr = Tracer()
        root = tr.start_trace("r")
        root.end(outcome="first")
        dur = root.dur
        root.end(outcome="second")      # no re-record, no new duration
        assert root.dur == dur
        spans = tr.export(root.trace_id)
        assert len(spans) == 1
        # the recorded dict is the FIRST end()'s snapshot
        assert spans[0]["attrs"]["outcome"] == "first"

    def test_exception_marks_error_attr(self):
        tr = Tracer()
        root = tr.start_trace("r")
        with pytest.raises(ValueError):
            with tr.activate(root.context):
                with obs.span("doomed"):
                    raise ValueError("boom")
        d = tr.export(root.trace_id)[0]
        assert d["name"] == "doomed"
        assert "ValueError: boom" in d["attrs"]["error"]

    def test_backdated_start(self):
        tr = Tracer()
        t0 = time.time() - 5.0
        sp = tr.start_trace("r", t_start=t0)
        sp.end(t_end=t0 + 2.0)
        d = tr.export(sp.trace_id)[0]
        assert d["t_start"] == t0 and d["dur"] == pytest.approx(2.0)

    def test_adopt_reparents_foreign_spans(self):
        # simulate the pool worker: its own tracer, own trace id
        worker = Tracer()
        wroot = worker.start_trace("pool.worker.build", "pool-worker")
        with worker.activate(wroot.context):
            with obs.span("store.dbg"):
                pass
        wroot.end()
        shipped = worker.export(wroot.trace_id)

        parent = Tracer()
        proot = parent.start_trace("job")
        dispatch = parent.start_span("pool.build_store",
                                     parent=proot.context)
        n = parent.adopt(shipped, dispatch.context)
        dispatch.end()
        proot.end()
        assert n == len(shipped) == 2
        spans = {d["name"]: d for d in parent.export(proot.trace_id)}
        # every adopted span now belongs to the parent's trace, and the
        # worker's ROOT hangs off the dispatch span; the child keeps its
        # worker-side parent link
        assert all(d["trace_id"] == proot.trace_id
                   for d in spans.values())
        assert (spans["pool.worker.build"]["parent_id"]
                == spans["pool.build_store"]["span_id"])
        assert (spans["store.dbg"]["parent_id"]
                == spans["pool.worker.build"]["span_id"])

    def test_bounded_spans_and_traces(self):
        tr = Tracer(max_traces=2, max_spans_per_trace=3)
        roots = [tr.start_trace(f"t{i}") for i in range(4)]
        for r in roots:
            r.end()
        assert len(tr.trace_ids()) == 2     # LRU kept the newest two
        keep = tr.start_trace("keep")
        with tr.activate(keep.context):
            for i in range(10):
                with obs.span(f"s{i}"):
                    pass
        keep.end()
        assert len(tr.export(keep.trace_id)) == 3
        assert tr.stats()["spans_dropped"] >= 8

    def test_chrome_trace_format(self, tmp_path):
        tr = Tracer()
        root = tr.start_trace("job", "service", app="pagerank")
        with tr.activate(root.context):
            with obs.span("work", "executor", lane=0):
                pass
        root.end()
        path = tmp_path / "trace.json"
        doc = tr.to_chrome_trace(path=str(path), trace_id=root.trace_id)
        loaded = json.loads(path.read_text())
        assert loaded == json.loads(json.dumps(doc))    # serializable
        assert loaded["displayTimeUnit"] == "ms"
        events = loaded["traceEvents"]
        assert len(events) == 2
        for ev in events:
            assert ev["ph"] == "X"
            assert ev["ts"] > 0 and ev["dur"] >= 0      # microseconds
            assert {"name", "cat", "pid", "tid", "args"} <= ev.keys()
        by_name = {e["name"]: e for e in events}
        assert by_name["work"]["args"]["lane"] == 0
        assert (by_name["work"]["args"]["parent_id"]
                == by_name["job"]["args"]["span_id"])

    def test_span_context_is_picklable_tuple(self):
        import pickle
        ctx = SpanContext("tid", "sid")
        assert pickle.loads(pickle.dumps(ctx)) == ("tid", "sid")
        assert ctx.trace_id == "tid" and ctx.span_id == "sid"

    def test_cross_thread_carrier(self):
        tr = Tracer()
        root = tr.start_trace("root")
        done = threading.Event()

        def worker(ctx):
            with tr.activate(ctx):
                with obs.span("in-worker"):
                    pass
            done.set()

        t = threading.Thread(target=worker, args=(root.context,))
        t.start()
        t.join(10)
        assert done.is_set()
        root.end()
        d = {s["name"]: s for s in tr.export(root.trace_id)}
        assert d["in-worker"]["parent_id"] == root.span_id


class TestDrift:
    def test_ratios(self):
        d = DriftAccumulator()
        d.add("little", 1.0, 2.0)
        d.add("little", 1.0, 4.0)
        rep = d.report()["little"]
        assert rep["n"] == 2
        assert rep["ratio"] == pytest.approx(3.0)       # 6.0 / 2.0
        assert rep["ratio_min"] == pytest.approx(2.0)
        assert rep["ratio_max"] == pytest.approx(4.0)

    def test_nonpositive_estimate_excluded_from_ratio(self):
        d = DriftAccumulator()
        d.add("idle", 0.0, 1.0)
        rep = d.report()["idle"]
        assert rep["n"] == 1 and rep["ratio"] is None

    def test_parent_chaining(self):
        parent = DriftAccumulator()
        child = DriftAccumulator(parent=parent)
        child.add("big", 2.0, 3.0)
        assert parent.report()["big"]["n"] == 1
        child.clear()
        assert parent.report()["big"]["n"] == 1     # parent unaffected


# ---------------------------------------------------------------------------
# executor: traced per-lane path
# ---------------------------------------------------------------------------

class TestExecutorTracing:
    @pytest.fixture(scope="class")
    def compiled(self, g1):
        return api.compile(g1, "pagerank", geom=GEOM, path="ref",
                           n_lanes=2)

    def test_traced_path_bit_identical_to_fused(self, g1, compiled):
        ref, _ = compiled.run(max_iters=4)
        other = api.compile(g1, "pagerank", geom=GEOM, path="ref",
                            n_lanes=2)
        tr = Tracer()
        root = tr.start_trace("run")
        with tr.activate(root.context):
            traced, meta = other.run(max_iters=4)
        root.end()
        # same single merge+apply program region -> bit identity
        np.testing.assert_array_equal(np.asarray(traced), np.asarray(ref))
        names = [d["name"] for d in tr.export(root.trace_id)]
        assert names.count("executor.iteration") == meta["iterations"]
        assert "executor.lane" in names and "executor.merge_apply" in names

    def test_lane_spans_carry_model_estimates(self, g1):
        c = api.compile(g1, "pagerank", geom=GEOM, path="ref", n_lanes=2)
        tr = Tracer()
        root = tr.start_trace("run")
        with tr.activate(root.context):
            c.run(max_iters=2)
        root.end()
        lanes = [d for d in tr.export(root.trace_id)
                 if d["name"] == "executor.lane"]
        assert lanes
        for d in lanes:
            assert d["attrs"]["kind"] in ("little", "big", "mixed", "idle")
            assert d["attrs"]["est_time"] >= 0.0
            assert d["attrs"]["n_entries"] >= 1
        # measured-vs-estimated drift was fed from the same runs
        drift = c.executor.stats()["drift"]
        assert "makespan" in drift and drift["makespan"]["n"] >= 2
        lane_kinds = {d["attrs"]["kind"] for d in lanes}
        assert lane_kinds <= set(drift)

    def test_lane_detail_off_keeps_fused_path(self, g1):
        c = api.compile(g1, "pagerank", geom=GEOM, path="ref", n_lanes=2)
        tr = Tracer(lane_detail=False)
        root = tr.start_trace("run")
        with tr.activate(root.context):
            c.run(max_iters=2)
        root.end()
        names = [d["name"] for d in tr.export(root.trace_id)]
        assert "executor.lane" not in names
        # coarse drift still sampled
        assert c.executor.stats()["drift"]["makespan"]["n"] >= 2


# ---------------------------------------------------------------------------
# end-to-end: HTTP job API -> Chrome trace, across queue + pool
# ---------------------------------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _post(url, body=None):
    req = urllib.request.Request(
        url, data=json.dumps(body or {}).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class TestEndToEndTrace:
    @pytest.fixture(scope="class")
    def plane(self, g1):
        # pool=1: the store build crosses the process boundary, so the
        # trace must carry across the pickled envelope. prepare=False so
        # the build happens INSIDE the traced job, not at register time.
        with ControlPlane(workers=1, pool=1, default_geom=GEOM,
                          default_path="ref") as cp:
            cp.register(g1, prepare=False)
            cp.serve_http()
            yield cp

    @pytest.fixture(scope="class")
    def base(self, plane):
        return f"http://127.0.0.1:{plane._http_server.server_address[1]}"

    def test_trace_covers_queue_pool_plan_execute(self, plane, base, g1):
        st, rec = _post(base + "/jobs", {
            "fingerprint": g1.fingerprint(), "app": "pagerank",
            "max_iters": 3})
        assert st == 201
        jid = rec["id"]
        st, _ = _get(base + f"/jobs/{jid}/result?timeout={WAIT}")
        assert st == 200
        st, doc = _get(base + f"/jobs/{jid}/trace")
        assert st == 200
        events = doc["traceEvents"]
        names = [e["name"] for e in events]
        # end-to-end span coverage: submit -> queue -> pool worker ->
        # store/plan -> per-lane execute -> merge/apply
        for needle in ("control.submit", "job:pagerank", "queue.wait",
                       "pool.build_store", "pool.worker.build",
                       "store.dbg", "store.partition", "service.plan",
                       "plan.build", "plan.pack", "service.execute",
                       "executor.iteration", "executor.lane",
                       "executor.merge_apply"):
            assert needle in names, (needle, sorted(set(names)))
        # the job record carries the trace id, and every event —
        # including the ones recorded in the worker PROCESS — was
        # re-parented into that one trace
        st, full = _get(base + f"/jobs/{jid}")
        by_name = {e["name"]: e for e in events}
        ids = {e["args"]["span_id"] for e in events}
        for e in events:
            parent = e["args"].get("parent_id")
            assert parent is None or parent in ids, e["name"]
        wroot = by_name["pool.worker.build"]
        assert (wroot["args"]["parent_id"]
                == by_name["pool.build_store"]["args"]["span_id"])
        assert wroot["args"]["pid"] != by_name["queue.wait"]["args"].get(
            "pid")  # really another process (worker stamps its os.getpid)
        # lane spans expose the perf-model estimate next to measured dur
        lane = by_name["executor.lane"]
        assert "est_time" in lane["args"] and lane["dur"] >= 0
        # drift aggregated into service stats and the prometheus gauges
        snap = plane.metrics_snapshot()
        assert snap["drift"]["makespan"]["n"] >= 1
        with urllib.request.urlopen(base + "/metrics") as r:
            prom = r.read().decode()
        assert 'regraph_perf_model_drift{kind="makespan"}' in prom
        # valid, self-consistent Chrome JSON: ph/ts/dur on every event
        assert doc["displayTimeUnit"] == "ms"
        assert all(e["ph"] == "X" and e["ts"] > 0 for e in events)
        assert full["trace_id"]

    def test_trace_404s(self, plane, base):
        st, err = _get(base + "/jobs/job-99999999/trace")
        assert st == 404 and err["error"] == "no_trace"

    def test_update_job_gets_its_own_trace(self, plane, g1):
        from repro.streaming import random_delta
        d = random_delta(g1, churn=0.02, seed=11)
        rec = plane.update_job(g1.fingerprint(), d)
        doc = plane.trace(rec.id)
        assert doc is not None
        names = [e["name"] for e in doc["traceEvents"]]
        assert "service.update" in names
        # the splice ran in the apply-lane worker process
        assert "pool.apply" in names and "pool.worker.apply" in names
        assert "plan.rebuild" in names

    def test_tracer_stats_exposed(self, plane):
        snap = plane.metrics_snapshot()
        assert snap["tracer"]["spans_recorded"] > 0
        assert snap["tracer"]["traces"] >= 1


# ---------------------------------------------------------------------------
# spans on the profiler's clock; compile counts per thread
# ---------------------------------------------------------------------------

SERVED = ("service.execute", "service.executor", "executor.compile",
          "executor.iteration", "executor.sync", "executor.converged",
          "executor.readback")


def _host_events(tdir, names):
    """(start_ns, end_ns, name) of the host events named in ``names``
    in the one profiler trace under ``tdir``."""
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for plane in pd.planes if not plane.name.startswith(
                      "/device:")
                  for line in plane.lines for ev in line.events
                  if ev.name in names)


def _inside(inner, outer):
    return outer[0] <= inner[0] and inner[1] <= outer[1]


class TestProfilerSpans:
    def test_span_is_a_profiler_annotation(self, tmp_path):
        tr = Tracer()
        root = tr.start_trace("root")
        with jax.profiler.trace(str(tmp_path)):
            with obs.span("service.bare"):
                pass
            with tr.activate(root.context):
                with obs.span("service.recorded") as sp:
                    sp.set(k=1)
        root.end()
        names = [e[2] for e in _host_events(
            str(tmp_path), {"service.bare", "service.recorded"})]
        assert names == ["service.bare", "service.recorded"]
        recorded = [d["name"] for d in tr.export(root.trace_id)]
        assert recorded == ["root", "service.recorded"]

    def test_served_request_spans_without_a_tracer(self, g1, tmp_path):
        with GraphService(default_geom=GEOM, default_path="ref") as svc:
            fp = svc.register(g1)
            with jax.profiler.trace(str(tmp_path)):
                h = svc.submit(fingerprint=fp, app="bfs",
                               app_kwargs={"root": 5})
                _, meta = h.result(WAIT)
            (ex, _), = svc._executors.values()
        ev = _host_events(str(tmp_path), set(SERVED))
        by = {}
        for e in ev:
            by.setdefault(e[2], []).append(e)
        assert set(by) == set(SERVED)
        (execute,), (build,) = by["service.execute"], by["service.executor"]
        (comp,), (back,) = by["executor.compile"], by["executor.readback"]
        its = by["executor.iteration"]
        assert len(its) == meta["iterations"]
        assert build[1] <= execute[0]
        assert all(_inside(e, execute) for e in its + [back])
        assert _inside(comp, its[0]) and back[0] >= its[-1][1]
        for name in ("executor.sync", "executor.converged"):
            assert len(by[name]) == len(its)
            assert all(_inside(e, it) for e, it in zip(by[name], its))
        assert comp[1] <= by["executor.sync"][0][0]
        # the annotations alone do not switch to the per-lane program
        assert ex._traced_fns is None and ex._iter_fn is not None

    def test_tracer_records_the_same_spans_and_stays_fused(self, g1):
        tr = Tracer(lane_detail=False)
        with GraphService(default_geom=GEOM, default_path="ref",
                          tracer=tr) as svc:
            h = svc.submit(g1, "bfs", app_kwargs={"root": 6})
            _, meta = h.result(WAIT)
            (ex, _), = svc._executors.values()
        spans = tr.export(h.trace_ctx.trace_id)
        names = [d["name"] for d in spans]
        assert set(SERVED) <= set(names)
        assert names.count("executor.iteration") == meta["iterations"]
        assert "executor.lane" not in names
        assert ex._traced_fns is None
        (comp,) = [d for d in spans if d["name"] == "executor.compile"]
        assert comp["attrs"]["traces"] >= 1
        assert comp["attrs"]["compiles"] >= 1
        assert {"cache_hits", "cache_misses"} <= set(comp["attrs"])


class TestJitCounts:
    def test_counts_are_per_thread(self):
        def fresh(x):
            return x * 3 + 1

        out = {}

        def worker():
            c0 = jitcount.thread_counts()
            jax.jit(fresh)(jnp.ones(3)).block_until_ready()
            out["d"] = jitcount.thread_counts() - c0

        mine0, tot0 = jitcount.thread_counts(), jitcount.totals()
        t = threading.Thread(target=worker)
        t.start()
        t.join(60)
        assert out["d"].traced["fresh"] == 1 and out["d"].compiles >= 1
        assert out["d"].trace_s > 0
        assert (jitcount.thread_counts() - mine0).traced["fresh"] == 0
        assert (jitcount.totals() - tot0).traced["fresh"] == 1

    def test_counts_survive_many_threads(self):
        """16 threads, each tracing 4 fresh functions with a short
        switch interval: no count is lost from a thread or the total."""
        import sys
        n_threads, per = 16, 4
        deltas = [None] * n_threads

        def worker(i):
            c0 = jitcount.thread_counts()
            for k in range(per):
                def f(x, k=k):
                    return x + k
                f.__name__ = f"stress_{i}"
                jax.jit(f)(jnp.ones(2)).block_until_ready()
            deltas[i] = jitcount.thread_counts() - c0

        tot0 = jitcount.totals()
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            ts = [threading.Thread(target=worker, args=(i,))
                  for i in range(n_threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join(120)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in ts)
        names = {f"stress_{i}" for i in range(n_threads)}
        for i, d in enumerate(deltas):
            assert {k: v for k, v in d.traced.items() if k in names} == \
                {f"stress_{i}": per}
        tot = jitcount.totals() - tot0
        assert {k: tot.traced[k] for k in names} == dict.fromkeys(names, per)

    def test_iteration_traces_new_root_then_repeat(self, g1):
        with GraphService(default_geom=GEOM, default_path="ref") as svc:
            fp = svc.register(g1)
            got = []
            for root in (7, 7, 8):
                h = svc.submit(fingerprint=fp, app="sssp",
                               app_kwargs={"root": root})
                h.result(WAIT)
                got.append(h.metrics.iteration_traces)
        # a new root is a new start state of the same program: only the
        # first request traces the iteration
        assert got == [1, 0, 0]
        assert h.metrics.as_dict()["iteration_traces"] == 0

    def test_iteration_traces_exact_with_two_workers(self, g1):
        """Four roots of one program on two workers: the first round
        traces once per executor built (both workers may miss the cache
        at once, and each executor traces once however many threads
        share it), the second round not at all."""
        roots = (11, 12, 13, 14)
        with GraphService(default_geom=GEOM, default_path="ref",
                          workers=2) as svc:
            fp = svc.register(g1)
            rounds = []
            for _ in range(2):
                hs = [svc.submit(fingerprint=fp, app="bfs",
                                 app_kwargs={"root": r}) for r in roots]
                for h in hs:
                    h.result(WAIT)
                rounds.append([h.metrics.iteration_traces for h in hs])
            built = svc.metrics.executor_misses
        first, second = rounds
        assert set(first) <= {0, 1} and sum(first) == built
        assert 1 <= built <= 2
        assert second == [0] * len(roots)

    def test_traced_under_the_iteration_program_name(self, g1):
        c = api.compile(g1, "wcc", geom=GEOM, path="ref", n_lanes=2)
        c0 = jitcount.thread_counts()
        c.run(max_iters=2)
        c.run(max_iters=2)
        assert (jitcount.thread_counts() - c0).traced[ITERATION_PROGRAM] == 1

    def test_metrics_render_jit_families(self, g1):
        from repro.serve_graph.metrics import merge_expositions
        with GraphService(default_geom=GEOM, default_path="ref") as svc:
            svc.submit(g1, "pagerank").result(WAIT)
            text = svc.metrics.render_prometheus()
            snap = svc.metrics.snapshot()
        vals = {}
        for line in merge_expositions(text).splitlines():
            if line.startswith("regraph_jit_"):
                name, v = line.split()
                vals[name] = float(v)
        assert set(vals) == {f"regraph_jit_{n}_total" for n in (
            "traces", "compiles", "cache_hits", "cache_misses",
            "trace_seconds")}
        assert vals["regraph_jit_traces_total"] >= 1
        assert vals["regraph_jit_compiles_total"] >= 1
        assert vals["regraph_jit_trace_seconds_total"] > 0
        assert snap["jit"]["traces"] >= vals["regraph_jit_traces_total"]
        for fam in vals:
            assert f"# TYPE {fam} counter" in text
