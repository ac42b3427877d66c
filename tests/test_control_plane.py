"""Control-plane tests: scheduler semantics, process-pool worker tier,
service-level scheduling (priority / deadline / admission / cancel),
job records, and the HTTP job API end-to-end.

Scheduler and job-store tests are pure Python (fake clocks, no jax
work). Service tests run tiny RMAT graphs on the ref path, reusing the
serving-suite geometry.
"""
import concurrent.futures
import json
import pickle
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.control import (ControlPlane, DeadlineExpired, JobScheduler,
                           JobStore, QueueFull, QuotaExceeded, RejectedJob,
                           TenantQuota, WorkerCrashed, WorkerPool)
from repro.control.jobs import JobState
from repro.serve_graph.metrics import (ServiceMetrics, _escape_label,
                                       merge_expositions)
from repro.core.planner import PlanConfig
from repro.core.store import GraphStore
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.serve_graph import GraphService
from repro.streaming import apply_delta, random_delta, rebuild_plans

GEOM = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)
WAIT = 300.0


@pytest.fixture(scope="module")
def g1():
    return rmat(8, 6, seed=1, weighted=True)


@pytest.fixture(scope="module")
def g2():
    return rmat(8, 6, seed=2, weighted=True)


@pytest.fixture(scope="module")
def pool():
    """One warm single-worker pool shared by the pool tests (spawn
    startup is the expensive part)."""
    with WorkerPool(workers=1, warm=True) as p:
        yield p


def _service(**kw):
    kw.setdefault("default_geom", GEOM)
    kw.setdefault("default_path", "ref")
    kw.setdefault("workers", 1)
    return GraphService(**kw)


# ---------------------------------------------------------------------------
# scheduler unit tests (fake clock, no service)
# ---------------------------------------------------------------------------

class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


class TestJobScheduler:
    def test_priority_then_fifo(self):
        s = JobScheduler()
        s.push("a", priority=0)
        s.push("b", priority=5)
        s.push("c", priority=5)
        s.push("d", priority=1)
        assert [s.pop(0) for _ in range(4)] == ["b", "c", "d", "a"]

    def test_deadline_breaks_priority_ties(self):
        clk = FakeClock()
        s = JobScheduler(clock=clk)
        s.push("late", deadline=clk.t + 50.0)
        s.push("soon", deadline=clk.t + 10.0)
        s.push("none")                      # no deadline sorts last
        assert [s.pop(0) for _ in range(3)] == ["soon", "late", "none"]

    def test_cost_breaks_remaining_ties(self):
        s = JobScheduler()
        s.push("slow", cost=9.0)
        s.push("fast", cost=0.1)
        assert s.pop(0) == "fast"

    def test_queue_full_typed(self):
        s = JobScheduler(max_depth=1)
        s.push("a")
        with pytest.raises(QueueFull) as ei:
            s.push("b")
        assert isinstance(ei.value, RejectedJob)
        assert s.stats()["rejected_queue_full"] == 1
        assert s.qsize() == 1               # nothing half-enqueued

    def test_quota_bucket_refills(self):
        clk = FakeClock()
        s = JobScheduler(default_quota=TenantQuota(rate=1.0, burst=2.0),
                         clock=clk)
        s.push("a", tenant="t")
        s.push("b", tenant="t")             # burst of 2 spent
        with pytest.raises(QuotaExceeded) as ei:
            s.push("c", tenant="t")
        assert "retry in" in str(ei.value)
        clk.t += 1.0                        # 1 token back at rate=1/s
        s.push("c", tenant="t")
        assert s.stats()["rejected_quota"] == 1

    def test_per_tenant_quota_isolation(self):
        clk = FakeClock()
        s = JobScheduler(quotas={"stingy": TenantQuota(rate=0.001)},
                         clock=clk)
        s.push("a", tenant="stingy")
        with pytest.raises(QuotaExceeded):
            s.push("b", tenant="stingy")
        for i in range(5):                  # others are unlimited
            s.push(f"x{i}", tenant="rich")
        assert s.stats()["depth_by_tenant"]["rich"] == 5

    def test_deadline_shed_on_pop(self):
        clk = FakeClock()
        shed = []
        s = JobScheduler(clock=clk, on_shed=shed.append)
        s.push("doomed", deadline=clk.t + 1.0)
        s.push("fine")
        clk.t += 2.0
        assert s.pop(0) == "fine"           # expired job never surfaces
        assert shed == ["doomed"]
        assert s.stats()["shed"] == 1

    def test_remove_and_reprioritize(self):
        s = JobScheduler()
        s.push("a", priority=0)
        s.push("b", priority=0)
        assert s.remove("a")
        assert not s.remove("a")            # second remove is a no-op
        s.push("c", priority=0)
        s.reprioritize("c", 9)              # lazy invalidation re-keys
        assert [s.pop(0) for _ in range(2)] == ["c", "b"]

    def test_sentinel_drains_last(self):
        s = JobScheduler(max_depth=1)       # sentinel bypasses admission
        s.push("work")
        s.push_sentinel("stop")
        assert s.pop(0) == "work"
        assert s.pop(0) == "stop"

    def test_pop_timeout(self):
        s = JobScheduler()
        t0 = time.perf_counter()
        assert s.pop(0.05) is None
        assert time.perf_counter() - t0 < 5.0


# ---------------------------------------------------------------------------
# process-pool worker tier
# ---------------------------------------------------------------------------

class TestWorkerPool:
    def test_store_pickle_roundtrip(self, g1):
        st = GraphStore(g1, geom=GEOM, use_dbg=True)
        st.plan(PlanConfig())               # plan cache must NOT travel
        clone = pickle.loads(pickle.dumps(st))
        assert clone.fingerprint() == st.fingerprint()
        for k in ("src", "dst", "weights"):
            assert np.array_equal(clone.edges[k], st.edges[k])
        assert clone.plan(PlanConfig()).plan is not None    # rebuildable

    def test_worker_never_starts_an_accelerator_backend(self, monkeypatch):
        """Workers inherit the parent's environment; even one that asks
        for a TPU must come up on the CPU (the chip is the parent's)."""
        import jax
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        with WorkerPool(workers=1) as p:
            assert p._run(0, jax.default_backend) == "cpu"

    def test_build_store_matches_local(self, pool, g1):
        st = pool.build_store(g1, geom=GEOM, use_dbg=True,
                              fp=g1.fingerprint())
        ref = GraphStore(g1, geom=GEOM, use_dbg=True,
                         fingerprint=g1.fingerprint())
        assert st.fingerprint() == ref.fingerprint()
        for k in ("src", "dst", "weights"):
            assert np.array_equal(st.edges[k], ref.edges[k])

    def test_apply_cached_and_need_state(self, pool, g1):
        ref = GraphStore(g1, geom=GEOM, use_dbg=True,
                         fingerprint=g1.fingerprint())
        d = random_delta(g1, churn=0.02, seed=5)
        local = apply_delta(ref, d)
        # this pool built g1's store in the previous test -> cached base
        st = pool.build_store(g1, geom=GEOM, use_dbg=True,
                              fp=g1.fingerprint())
        res = pool.apply(st, d)
        assert res.fingerprint == local.fingerprint
        for k in ("src", "dst", "weights"):
            assert np.array_equal(res.store.edges[k], local.store.edges[k])
        # a cold pool has to be shipped the base once, then succeeds
        with WorkerPool(workers=1, warm=True) as cold:
            res2 = cold.apply(ref, d)
            assert cold.stats()["need_state_retries"] == 1
            assert res2.fingerprint == local.fingerprint

    def test_parent_side_plan_rebuild(self, pool, g1):
        ref = GraphStore(g1, geom=GEOM, use_dbg=True,
                         fingerprint=g1.fingerprint())
        ref.plan(PlanConfig())
        d = random_delta(g1, churn=0.02, seed=6)
        res = pool.apply(ref, d)
        s = rebuild_plans(ref, res.store, res.dirty_pids)
        assert s["plans_rebuilt"] == 1

    def test_crash_respawn(self, pool, g1):
        with pytest.raises(WorkerCrashed):
            pool.build_store(g1, geom=GEOM, use_dbg=True, _crash=True)
        # the pool respawned: same call now works
        st = pool.build_store(g1, geom=GEOM, use_dbg=True,
                              fp=g1.fingerprint())
        assert st.fingerprint() == g1.fingerprint()
        assert pool.stats()["crashes"] >= 1


# ---------------------------------------------------------------------------
# service-level scheduling semantics
# ---------------------------------------------------------------------------

class TestServiceScheduling:
    def test_priority_ordering(self, g1, g2):
        """A high-priority submit drains before an earlier low-priority
        one when both are queued behind a held worker."""
        with _service() as svc:
            fp1, fp2 = svc.register(g1), svc.register(g2)
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)           # warm the store
            order = []
            gate = threading.Event()
            hold = svc.submit(
                fingerprint=fp1, app="pagerank", max_iters=5,
                observer=lambda e, i: gate.wait(60)
                if e == "running" else None)
            time.sleep(0.2)                 # hold reaches the worker
            lo = svc.submit(fingerprint=fp2, app="bfs",
                            app_kwargs={"root": 0}, priority=0,
                            observer=lambda e, i: order.append(("lo", e)))
            hi = svc.submit(fingerprint=fp2, app="pagerank", max_iters=3,
                            priority=5,
                            observer=lambda e, i: order.append(("hi", e)))
            gate.set()
            for h in (hi, lo, hold):
                h.result(timeout=WAIT)
            ran = [t for t, e in order if e == "running"]
            assert ran == ["hi", "lo"], ran

    def test_deadline_shed(self, g1, g2):
        with _service() as svc:
            fp1, fp2 = svc.register(g1), svc.register(g2)
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)
            gate = threading.Event()
            hold = svc.submit(
                fingerprint=fp1, app="pagerank", max_iters=5,
                observer=lambda e, i: gate.wait(60)
                if e == "running" else None)
            time.sleep(0.1)
            doomed = svc.submit(fingerprint=fp2, app="sssp",
                                app_kwargs={"root": 0}, deadline=0.05)
            time.sleep(0.3)                 # deadline passes in queue
            gate.set()
            with pytest.raises(DeadlineExpired):
                doomed.result(timeout=WAIT)
            hold.result(timeout=WAIT)
            assert svc.metrics.snapshot()["shed_deadline"] == 1

    def test_queue_full_and_coalesce_bypass(self, g1):
        """Admission rejects at max depth — but a coalescing twin rides
        the in-flight job, so identical work is never refused (no
        priority inversion through the quota/depth gate)."""
        with _service(max_queue_depth=1) as svc:
            fp1 = svc.register(g1)
            gate = threading.Event()
            hold = svc.submit(
                fingerprint=fp1, app="pagerank", max_iters=5,
                observer=lambda e, i: gate.wait(60)
                if e == "running" else None)
            time.sleep(0.2)
            q1 = svc.submit(fingerprint=fp1, app="bfs",
                            app_kwargs={"root": 0})
            with pytest.raises(QueueFull):
                svc.submit(fingerprint=fp1, app="sssp",
                           app_kwargs={"root": 0})
            # identical submit coalesces: bypasses the full queue
            twin = svc.submit(fingerprint=fp1, app="bfs",
                              app_kwargs={"root": 0})
            gate.set()
            for h in (hold, q1, twin):
                h.result(timeout=WAIT)
            assert svc.stats()["service"]["rejected_queue_full"] >= 1

    def test_coalesce_boosts_priority(self, g1, g2):
        """A high-priority duplicate of a queued low-priority job boosts
        that job instead of waiting behind admission."""
        with _service() as svc:
            fp1, fp2 = svc.register(g1), svc.register(g2)
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)
            order = []
            gate = threading.Event()
            hold = svc.submit(
                fingerprint=fp1, app="pagerank", max_iters=5,
                observer=lambda e, i: gate.wait(60)
                if e == "running" else None)
            time.sleep(0.2)
            lo = svc.submit(fingerprint=fp2, app="bfs",
                            app_kwargs={"root": 0}, priority=0,
                            observer=lambda e, i: order.append(("lo", e)))
            mid = svc.submit(fingerprint=fp2, app="wcc", priority=3,
                             observer=lambda e, i: order.append(("mid", e)))
            # duplicate of lo at priority 9 -> boosts the queued job
            boost = svc.submit(fingerprint=fp2, app="bfs",
                               app_kwargs={"root": 0}, priority=9)
            gate.set()
            for h in (lo, mid, boost, hold):
                h.result(timeout=WAIT)
            ran = [t for t, e in order if e == "running"]
            assert ran == ["lo", "mid"], ran
            assert boost.result(timeout=WAIT)[1] is lo.result(
                timeout=WAIT)[1]            # coalesced: same meta object

    def test_quota_rejection_per_tenant(self, g1):
        with _service(quotas={"stingy": TenantQuota(rate=0.001,
                                                    burst=1)}) as svc:
            fp1 = svc.register(g1)
            ok = svc.submit(fingerprint=fp1, app="wcc", tenant="stingy")
            with pytest.raises(QuotaExceeded):
                svc.submit(fingerprint=fp1, app="closeness",
                           app_kwargs={"sources": [0]}, tenant="stingy")
            ok.result(timeout=WAIT)
            # other tenants unaffected
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)
            t = svc.stats()["service"]["tenants"]["stingy"]
            assert t["rejected"] == 1 and t["completed"] == 1

    def test_cancel_queued_job(self, g1, g2):
        with _service() as svc:
            fp1, fp2 = svc.register(g1), svc.register(g2)
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)
            gate = threading.Event()
            hold = svc.submit(
                fingerprint=fp1, app="pagerank", max_iters=5,
                observer=lambda e, i: gate.wait(60)
                if e == "running" else None)
            time.sleep(0.1)
            victim = svc.submit(fingerprint=fp2, app="bfs",
                                app_kwargs={"root": 0})
            assert svc.cancel(victim)
            assert not svc.cancel(victim)   # already detached
            gate.set()
            with pytest.raises(concurrent.futures.CancelledError):
                victim.result(timeout=WAIT)
            hold.result(timeout=WAIT)

    def test_pool_backed_service(self, g1):
        with _service(pool=1) as svc:
            fp1 = svc.register(g1)
            props, _ = svc.run(fingerprint=fp1, app="pagerank",
                               max_iters=3, timeout=WAIT)
            # reference: threads-only service, same graph/config
            with _service() as ref_svc:
                ref_svc.register(g1)
                ref_props, _ = ref_svc.run(fingerprint=fp1, app="pagerank",
                                           max_iters=3, timeout=WAIT)
            np.testing.assert_array_equal(np.asarray(props),
                                          np.asarray(ref_props))
            d = random_delta(g1, churn=0.02, seed=7)
            up = svc.update(fp1, d)
            assert up.mode == "incremental" and "path" in up.stats
            svc.run(fingerprint=up.fingerprint, app="pagerank",
                    max_iters=3, timeout=WAIT)
            assert svc.stats()["pool"]["jobs"] >= 2

    def test_worker_crash_releases_lease(self, g1):
        """Regression: a worker-process crash mid-update must not leak
        the base store's cache lease — the entry stays usable, pins
        return to zero, and the respawned pool serves the retry."""
        with _service(pool=1) as svc:
            fp1 = svc.register(g1)
            svc.run(fingerprint=fp1, app="pagerank", max_iters=2,
                    timeout=WAIT)
            old_key = next(iter(svc.cache.keys()))
            d = random_delta(g1, churn=0.02, seed=8)
            real_apply = svc._pool.apply
            svc._pool.apply = lambda store, delta, **kw: real_apply(
                store, delta, _crash=True)
            try:
                with pytest.raises(WorkerCrashed):
                    svc.update(fp1, d)
            finally:
                svc._pool.apply = real_apply
            # lease audit: no pins leaked, entry still cached
            assert svc.cache.pin_count(old_key) == 0
            assert old_key in svc.cache
            assert svc.metrics.snapshot()["update_failures"] == 1
            # the job was NOT silently retried; an explicit retry works
            up = svc.update(fp1, d)
            assert up.mode == "incremental"
            assert svc._pool.stats()["crashes"] == 1


# ---------------------------------------------------------------------------
# job records
# ---------------------------------------------------------------------------

class TestJobStore:
    def test_lifecycle_and_timestamps(self):
        js = JobStore()
        rec = js.create(kind="run", app="pagerank", tenant="t")
        assert rec.state == JobState.SUBMITTED
        js.transition(rec.id, JobState.QUEUED)
        js.transition(rec.id, JobState.RUNNING)
        js.transition(rec.id, JobState.DONE, metrics={"x": 1})
        r = js.get(rec.id)
        assert r.state == JobState.DONE and r.metrics == {"x": 1}
        assert r.timestamps.keys() >= {"submitted", "queued", "running",
                                       "done"}
        assert r.to_dict()["terminal"] is True

    def test_transitions_never_go_backwards(self):
        js = JobStore()
        rec = js.create(kind="run", app="bfs")
        js.transition(rec.id, JobState.RUNNING)
        js.transition(rec.id, JobState.QUEUED)      # late observer race
        assert js.get(rec.id).state == JobState.RUNNING
        js.transition(rec.id, JobState.CANCELLED)
        js.transition(rec.id, JobState.DONE)        # cannot resurrect
        assert js.get(rec.id).state == JobState.CANCELLED

    def test_retention_evicts_only_terminal(self):
        js = JobStore(max_records=3)
        live = js.create(kind="run", app="a")       # stays non-terminal
        done = [js.create(kind="run", app=f"d{i}") for i in range(3)]
        for r in done:
            js.transition(r.id, JobState.DONE)
        js.create(kind="run", app="new")            # forces eviction
        assert js.get(live.id) is not None          # live never evicted
        assert js.get(done[0].id) is None           # oldest terminal gone
        assert js.stats()["records"] <= 4

    def test_log_ring_and_chunked_reads(self):
        js = JobStore(log_lines=8)
        rec = js.create(kind="run", app="a")        # 1 creation line
        for i in range(20):
            js.append_log(rec.id, f"line {i}")
        lines, off, done = js.read_logs(rec.id, offset=0, limit=5)
        assert len(lines) == 5 and not done
        # offset 0 is older than the ring: skipped forward, so the
        # first line returned is the oldest RETAINED one
        assert "line 12" in lines[0]
        lines2, off2, done2 = js.read_logs(rec.id, offset=off, limit=100)
        assert "line 19" in lines2[-1] and not done2    # not terminal yet
        js.transition(rec.id, JobState.DONE)
        lines3, _, done3 = js.read_logs(rec.id, offset=off2, limit=100)
        assert done3 and any("done" in ln for ln in lines3)

    def test_jsonl_persistence(self, tmp_path):
        path = tmp_path / "jobs.jsonl"
        js = JobStore(persist_path=str(path))
        a = js.create(kind="run", app="pagerank")
        b = js.create(kind="run", app="bfs")
        js.transition(a.id, JobState.DONE)
        js.transition(b.id, JobState.FAILED, error="boom")
        js.transition(a.id, JobState.FAILED)        # no double-persist
        rows = [json.loads(ln) for ln in path.read_text().splitlines()]
        assert [r["id"] for r in rows] == [a.id, b.id]
        assert rows[1]["error"] == "boom" and rows[1]["logs"]


# ---------------------------------------------------------------------------
# control plane + HTTP API end-to-end
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


class TestControlPlaneHTTP:
    @pytest.fixture(scope="class")
    def plane(self, g1):
        with ControlPlane(workers=2, default_geom=GEOM,
                          default_path="ref") as cp:
            cp.register(g1)
            cp.serve_http()
            yield cp

    @pytest.fixture(scope="class")
    def base(self, plane):
        return f"http://127.0.0.1:{plane._http_server.server_address[1]}"

    def test_submit_to_done_over_http(self, plane, base, g1):
        st, rec = _post(base + "/jobs", {
            "fingerprint": g1.fingerprint(), "app": "pagerank",
            "max_iters": 5, "tenant": "alice", "priority": 2})
        assert st == 201 and rec["state"] in ("submitted", "queued",
                                              "running")
        jid = rec["id"]
        st, res = _get(base + f"/jobs/{jid}/result?timeout={WAIT}")
        assert st == 200 and res["num_properties"] == g1.num_vertices
        deadline = time.time() + 10         # observer fires async
        while time.time() < deadline:
            st, rec = _get(base + f"/jobs/{jid}")
            if rec["terminal"]:
                break
            time.sleep(0.05)
        assert rec["state"] == JobState.DONE
        assert "t_execute_ms" in rec["metrics"]
        assert rec["timestamps"].keys() >= {"submitted", "queued",
                                            "running", "done"}
        # chunked log stream (urllib decodes chunked transfer)
        st, logs = _get(base + f"/jobs/{jid}/logs")
        assert st == 200 and logs["done"]
        assert any("running" in ln for ln in logs["lines"])
        # listing + filters
        st, lst = _get(base + "/jobs?tenant=alice")
        assert st == 200 and any(j["id"] == jid for j in lst["jobs"])
        st, lst = _get(base + "/jobs?tenant=nobody")
        assert lst["jobs"] == []

    def test_typed_http_errors(self, base):
        st, err = _post(base + "/jobs", {})
        assert (st, err["error"]) == (400, "bad_request")
        st, err = _post(base + "/jobs", {"fingerprint": "nope"})
        assert (st, err["error"]) == (404, "unknown_fingerprint")
        st, _ = _get(base + "/jobs/job-99999999")
        assert st == 404
        st, err = _post(base + "/jobs/job-99999999/cancel")
        assert st == 409 and err["cancelled"] is False

    def test_update_job_then_serve_new_fp(self, plane, base, g1):
        d = random_delta(g1, churn=0.02, seed=9)
        rec = plane.update_job(g1.fingerprint(), d)
        assert rec.state == JobState.DONE and rec.kind == "update"
        new_fp = rec.metrics["fingerprint"]
        assert rec.metrics["stats"]["path"] in ("splice", "bulk_sort")
        st, r2 = _post(base + "/jobs", {"fingerprint": new_fp,
                                        "app": "pagerank", "max_iters": 3})
        assert st == 201
        st, _ = _get(base + f"/jobs/{r2['id']}/result?timeout={WAIT}")
        assert st == 200

    def test_metrics_endpoints(self, base):
        st, snap = _get(base + "/metrics.json")
        assert st == 200
        assert {"service", "scheduler", "jobs"} <= snap.keys()
        with urllib.request.urlopen(base + "/metrics") as r:
            prom = r.read().decode()
        for needle in ("regraph_requests_total", "regraph_scheduler_depth",
                       'regraph_jobs{state="done"}',
                       'regraph_tenant_requests_total{tenant="alice"'):
            assert needle in prom, needle

    def test_rejected_jobs_are_recorded(self, g1):
        """An admission refusal raises AND leaves a queryable record."""
        with ControlPlane(workers=1, default_geom=GEOM, default_path="ref",
                          quotas={"s": TenantQuota(rate=0.001)}) as cp:
            fp = cp.register(g1)
            cp.submit_job(fingerprint=fp, app="wcc", tenant="s")
            with pytest.raises(QuotaExceeded):
                cp.submit_job(fingerprint=fp, app="pagerank", tenant="s",
                              max_iters=2)
            rejected = cp.jobs.list(state=JobState.REJECTED)
            assert len(rejected) == 1
            assert "quota" in rejected[0]["error"]


# ---------------------------------------------------------------------------
# prometheus exposition: merged families, escaping
# ---------------------------------------------------------------------------

def _parse_exposition(text):
    """Strict promtext round-trip parse: returns {family: (help, type,
    [sample lines])} and fails on malformed lines, duplicate metadata,
    or samples appearing before their family's headers."""
    import re as _re
    sample_re = _re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
        r'(\{(?:[a-zA-Z_][a-zA-Z0-9_]*="(?:\\.|[^"\\\n])*",?)*\})?'
        r' (NaN|[-+0-9.eE]+)$')
    fams = {}
    for line in text.splitlines():
        if not line.strip():
            continue
        if line.startswith("# HELP ") or line.startswith("# TYPE "):
            _, kw, name, rest = line.split(None, 3)
            fam = fams.setdefault(name, [None, None, []])
            idx = 0 if kw == "HELP" else 1
            assert fam[idx] is None, f"duplicate # {kw} for {name}"
            fam[idx] = rest
        else:
            m = sample_re.match(line)
            assert m, f"malformed sample line: {line!r}"
            name = m.group(1)
            assert name in fams, f"sample before headers: {name}"
            float(m.group(3)) if m.group(3) != "NaN" else None
            fams[name][2].append(line)
    return fams


class TestPrometheusExposition:
    def test_merge_expositions_dedupes_headers(self):
        a = ("# HELP x_total Things.\n# TYPE x_total counter\n"
             'x_total{k="a"} 1\n')
        b = ("# HELP x_total Things again (dropped).\n"
             "# TYPE x_total counter\n"
             'x_total{k="b"} 2\n'
             "# HELP y_depth Depth.\n# TYPE y_depth gauge\ny_depth 3\n")
        merged = merge_expositions(a, b)
        fams = _parse_exposition(merged)
        assert fams["x_total"][0] == "Things."        # first header wins
        assert len(fams["x_total"][2]) == 2           # both samples kept
        assert fams["y_depth"][2] == ["y_depth 3"]
        # family order is first appearance
        assert list(fams) == ["x_total", "y_depth"]

    def test_control_plane_prometheus_roundtrips(self, g1):
        """The merged /metrics document must parse cleanly: one HELP +
        one TYPE per family, every sample under its family (regression:
        the old concatenation repeated nothing only by luck — a family
        emitted by both the service and the plane would have carried
        duplicate metadata)."""
        with ControlPlane(workers=1, default_geom=GEOM,
                          default_path="ref") as cp:
            fp = cp.register(g1)
            rec = cp.submit_job(fingerprint=fp, app="pagerank",
                                max_iters=2)
            cp.result(rec.id, timeout=WAIT)
            fams = _parse_exposition(cp.prometheus())
        for fam in ("regraph_requests_total", "regraph_latency_ms",
                    "regraph_scheduler_depth", "regraph_jobs",
                    "regraph_perf_model_drift"):
            help_, type_, samples = fams[fam]
            assert help_ and type_ and samples, fam

    def test_label_escaping_deterministic(self):
        m = ServiceMetrics()
        nasty = 'ten"ant\\with\nnewline'
        m.record_submit(False, tenant=nasty)
        text = m.render_prometheus()
        fams = _parse_exposition(text)      # no raw newline broke a line
        line = [ln for ln in fams["regraph_tenant_requests_total"][2]
                if "ten" in ln][0]
        assert '\\"' in line and "\\\\" in line and "\\n" in line

    def test_label_escaping_property(self):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings
        from hypothesis import strategies as st

        def unescape(s):
            out, i = [], 0
            while i < len(s):
                c = s[i]
                if c == "\\":
                    assert i + 1 < len(s), "dangling backslash"
                    n = s[i + 1]
                    assert n in ('\\', 'n', '"'), f"bad escape \\{n}"
                    out.append({'\\': '\\', 'n': '\n', '"': '"'}[n])
                    i += 2
                else:
                    assert c not in ('\n', '"'), f"unescaped {c!r}"
                    out.append(c)
                    i += 1
            return "".join(out)

        @settings(max_examples=200, deadline=None)
        @given(st.text(min_size=0, max_size=64))
        def check(value):
            esc = _escape_label(value)
            assert "\n" not in esc          # never breaks the line
            assert unescape(esc) == value   # lossless round-trip

        check()


# ---------------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------------

def test_cost_estimates_learn_from_measurements(g1):
    """After one measured run the scheduler's cost for that (store, app)
    comes from the EWMA, not the plan model."""
    with _service() as svc:
        fp = svc.register(g1)
        svc.run(fingerprint=fp, app="pagerank", max_iters=3, timeout=WAIT)
        with svc._cost_lock:
            assert svc._cost_n >= 1 and len(svc._cost_ewma) == 1
            ewma = next(iter(svc._cost_ewma.values()))
        assert ewma > 0.0
        skey = next(iter(svc.cache.keys()))
        cost, model_est = svc._estimate_cost(skey, "pagerank", PlanConfig())
        assert cost == pytest.approx(ewma)
        # an app never run on this store falls back to the plan model,
        # scaled by the observed model/actual ratio
        cost2, est2 = svc._estimate_cost(skey, "wcc", PlanConfig())
        assert cost2 > 0.0
