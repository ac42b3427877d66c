"""Utilization profiler + readiness/dashboard tests.

Pure-Python pieces (accumulator, reservoir percentiles, log stamps) run with no jax work; the footprint-vs-jaxpr parity and the
export path run one tiny RMAT graph on the ref path like the other
control-plane tests.
"""
import json
import re
import time
import urllib.request

import jax
import pytest

from repro import api, obs
from repro.control import ControlPlane, JobStore, WorkerPool
from repro.control.dashboard import DASHBOARD_HTML
from repro.control.jobs import JobState
from repro.core import perf_model
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.obs.profile import UtilizationAccumulator
from repro.serve_graph import GraphService
from repro.serve_graph.metrics import ServiceMetrics, _Reservoir

from test_control_plane import _parse_exposition

GEOM = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)
WAIT = 300.0

# a job-log line: "[<epoch seconds>] <LEVEL> <message>"
LOG_LINE = re.compile(r"^\[\d+\.\d{3}\] (INFO|ERROR) .+")


@pytest.fixture(scope="module")
def g1():
    return rmat(8, 6, seed=1, weighted=True)


# ---------------------------------------------------------------------------
# lane footprints vs jaxpr ground truth
# ---------------------------------------------------------------------------

class TestLaneFootprints:
    @pytest.fixture(scope="class")
    def ex(self, g1):
        return api.compile(g1, "pagerank", geom=GEOM, n_lanes=2,
                           path="ref").executor

    def test_analytic_matches_jaxpr(self, ex):
        checked = 0
        for li, fp in enumerate(ex.footprints()):
            truth = obs.jaxpr_lane_bytes(ex, li)
            if fp is None or truth is None:
                continue
            checked += 1
            assert fp.total_bytes == pytest.approx(truth, rel=0.10)
        assert checked > 0

    def test_footprint_invariants(self, ex):
        for fp in ex.footprints():
            if fp is None:
                continue
            assert fp.hbm_bytes > 0 and fp.flops > 0
            assert fp.total_bytes >= fp.edge_bytes
            assert fp.intensity == fp.flops / fp.hbm_bytes
            d = fp.as_dict()
            assert d["hbm_bytes"] == fp.hbm_bytes
            assert d["kind"] in ("little", "big", "mixed")

    def test_traced_run_accumulates_utilization(self, ex):
        tr = obs.Tracer(lane_detail=True)
        root = tr.start_trace("t")
        with tr.activate(root.context):
            ex.run(max_iters=2)
        root.end()
        util = ex.stats()["utilization"]
        assert util["profile"] is True
        assert util["kinds"], "traced run must record samples"
        for rep in util["kinds"].values():
            assert rep["gbps"] > 0 and rep["n"] > 0
        # the denominator is the running device's published peak; a
        # device kind without one (the CPU) reports no utilization
        peak = perf_model.device_peak_bandwidth_bps(
            jax.devices()[0].device_kind)
        if peak is None:
            assert util["peak_bandwidth_gbps"] is None
            assert all(rep["utilization"] is None
                       for rep in util["kinds"].values())
        else:
            assert util["peak_bandwidth_gbps"] == pytest.approx(peak / 1e9)
        # exec.lane spans carry the footprint counters
        spans = [s for s in tr.export(root.trace_id)
                 if s["name"] == "executor.lane"]
        assert spans and all("hbm_bytes" in s["attrs"]
                             and "gbps" in s["attrs"] for s in spans)

    def test_profile_off_records_nothing(self, g1):
        from repro.core import gas
        from repro.core.executor import Executor
        store = api.GraphStore(g1, geom=GEOM)
        bundle = store.plan(api.PlanConfig(n_lanes=2))
        ex = Executor(store, bundle, gas.make_pagerank(max_iters=2),
                      path="ref", profile=False)
        tr = obs.Tracer(lane_detail=True)
        root = tr.start_trace("t")
        with tr.activate(root.context):
            ex.run(max_iters=2)
        root.end()
        util = ex.stats()["utilization"]
        assert util["profile"] is False
        assert util["kinds"] == {} and util["footprints"] == []


# ---------------------------------------------------------------------------
# UtilizationAccumulator (pure python)
# ---------------------------------------------------------------------------

class TestUtilizationAccumulator:
    def test_report_shape_and_math(self):
        acc = UtilizationAccumulator()
        acc.add("little", nbytes=2e9, flops=4e9, measured_s=1.0,
                peak_bps=4e9, lane=0)
        rep = acc.report()
        little = rep["kinds"]["little"]
        assert little["gbps"] == pytest.approx(2.0)
        assert little["utilization"] == pytest.approx(0.5)
        assert little["intensity"] == pytest.approx(2.0)
        assert rep["peak_bandwidth_gbps"] == pytest.approx(4.0)
        assert rep["lanes"][0]["kind"] == "little"

    def test_device_peak_table(self):
        assert perf_model.device_peak_bandwidth_bps("TPU v5 lite") == 819e9
        assert perf_model.device_peak_bandwidth_bps("cpu") is None
        assert perf_model.device_peak_bandwidth_bps("TPU v9 made up") is None

    def test_no_peak_means_none_utilization(self):
        acc = UtilizationAccumulator()
        acc.add("big", 1e9, 1e9, 0.5)
        rep = acc.report()
        assert rep["kinds"]["big"]["utilization"] is None
        assert rep["peak_bandwidth_gbps"] is None

    def test_parent_chaining(self):
        parent = UtilizationAccumulator()
        child = UtilizationAccumulator(parent=parent)
        child.add("little", 1e9, 1e9, 1.0, peak_bps=2e9, lane=3)
        assert parent.report()["kinds"]["little"]["n"] == 1
        assert parent.report()["lanes"][3]["gbps"] == pytest.approx(1.0)
        with pytest.raises(ValueError):
            parent.set_parent(parent)

    def test_clear(self):
        acc = UtilizationAccumulator()
        acc.add("little", 1e9, 1e9, 1.0, lane=0)
        acc.clear()
        rep = acc.report()
        assert rep["kinds"] == {} and rep["lanes"] == {}

    def test_lane_retention_bound(self):
        acc = UtilizationAccumulator()
        for lane in range(UtilizationAccumulator._MAX_LANES + 10):
            acc.add("little", 1.0, 1.0, 1.0, lane=lane)
        assert len(acc.report()["lanes"]) \
            == UtilizationAccumulator._MAX_LANES


# ---------------------------------------------------------------------------
# reservoir percentile edge cases (satellite d)
# ---------------------------------------------------------------------------

class TestReservoir:
    def test_empty_is_none(self):
        r = _Reservoir()
        assert r.percentile(50) is None
        assert r.mean() is None
        assert len(r) == 0

    def test_single_sample_every_percentile(self):
        r = _Reservoir()
        r.add(7.5)
        for p in (0, 1, 50, 99, 100):
            assert r.percentile(p) == 7.5
        assert r.mean() == 7.5

    def test_p0_and_p100_are_extremes(self):
        r = _Reservoir()
        for x in (5.0, 1.0, 9.0, 3.0):
            r.add(x)
        assert r.percentile(0) == 1.0
        assert r.percentile(100) == 9.0
        assert r.percentile(50) == 5.0      # nearest-rank of sorted

    def test_bounded_keeps_most_recent(self):
        r = _Reservoir(maxlen=4)
        for x in range(10):
            r.add(float(x))
        assert r.percentile(0) == 6.0 and r.percentile(100) == 9.0


# ---------------------------------------------------------------------------
# prometheus exposition of the new gauges (satellite d)
# ---------------------------------------------------------------------------

class TestUtilizationExposition:
    def test_gauges_rendered_and_parse(self):
        m = ServiceMetrics()
        m.utilization.add("little", 2e9, 4e9, 1.0, peak_bps=4e9, lane=0)
        m.utilization.add("big", 8e9, 8e9, 2.0, peak_bps=4e9, lane=1)
        fams = _parse_exposition(m.render_prometheus())
        bw = fams["regraph_lane_bandwidth_gbps"]
        ut = fams["regraph_pipeline_utilization"]
        assert bw[1] == "gauge" and ut[1] == "gauge"
        assert any('kind="little"' in ln and ln.endswith(" 2.0")
                   for ln in bw[2])
        assert any('kind="big"' in ln for ln in ut[2])
        snap = m.snapshot()
        assert snap["utilization"]["kinds"]["big"]["utilization"] \
            == pytest.approx(1.0)

    def test_empty_accumulator_keeps_families_valid(self):
        fams = _parse_exposition(ServiceMetrics().render_prometheus())
        assert fams["regraph_lane_bandwidth_gbps"][2] == []
        assert fams["regraph_pipeline_utilization"][2] == []


# ---------------------------------------------------------------------------
# job-log stamps (satellite c)
# ---------------------------------------------------------------------------

class TestJobLogStamps:
    def test_epoch_and_level_on_every_line(self):
        js = JobStore()
        rec = js.create(kind="run", app="pagerank")
        js.transition(rec.id, JobState.QUEUED)
        js.append_log(rec.id, "custom note")
        js.transition(rec.id, JobState.FAILED, error="boom")
        lines = list(js.get(rec.id).logs)
        assert lines and all(LOG_LINE.match(ln) for ln in lines)
        assert any(" ERROR " in ln for ln in lines)       # failure line
        stamp = float(lines[0].split("]")[0][1:])
        assert abs(stamp - time.time()) < 60              # epoch seconds
        assert all(isinstance(ln, str) for ln in lines)

    def test_explicit_level(self):
        js = JobStore()
        rec = js.create(kind="run", app="wcc")
        js.append_log(rec.id, "scary", level="error")
        assert " ERROR scary" in list(js.get(rec.id).logs)[-1]


# ---------------------------------------------------------------------------
# readiness probes + dashboard (satellite b, tentpole 3)
# ---------------------------------------------------------------------------

def _get(url):
    import urllib.error
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


class TestReadinessAndDashboard:
    def test_pool_alive_flips_on_close(self):
        pool = WorkerPool(workers=1)
        assert pool.alive() is True
        pool.close()
        assert pool.alive() is False

    def test_readyz_healthz_dashboard(self, g1):
        svc = GraphService(workers=1, default_geom=GEOM,
                           default_path="ref")
        cp = ControlPlane(service=svc)
        try:
            _, base = cp.serve_http()
            st, body = _get(base + "/healthz")
            assert st == 200 and json.loads(body)["status"] == "ok"
            st, body = _get(base + "/readyz")
            info = json.loads(body)
            assert st == 200 and info["ready"] is True
            assert info["scheduler_accepting"] is True
            assert "queue_depth" in info
            st, html = _get(base + "/dashboard")
            assert st == 200
            assert "/metrics.json" in html
            assert "Pipeline utilization" in html
            # closing the service must flip readiness while the HTTP
            # face stays up (liveness unchanged)
            svc.close()
            st, body = _get(base + "/readyz")
            info = json.loads(body)
            assert st == 503 and info["ready"] is False
            assert info["scheduler_accepting"] is False
            st, _ = _get(base + "/healthz")
            assert st == 200
        finally:
            cp.close()
            svc.close()

    def test_dashboard_html_self_contained(self):
        assert "<script src" not in DASHBOARD_HTML
        assert 'href="http' not in DASHBOARD_HTML
        for needle in ("util-kinds", "util-lanes", "latency", "drift",
                       "prefers-color-scheme: dark"):
            assert needle in DASHBOARD_HTML, needle


# ---------------------------------------------------------------------------
# service-level chaining: a traced job feeds the /metrics gauges
# ---------------------------------------------------------------------------

class TestServiceUtilizationChaining:
    def test_traced_job_surfaces_gauges(self, g1):
        with ControlPlane(workers=1, default_geom=GEOM,
                          default_path="ref",
                          tracer=obs.Tracer(lane_detail=True)) as cp:
            fp = cp.register(g1)
            rec = cp.submit_job(fingerprint=fp, app="pagerank",
                                max_iters=2)
            cp.result(rec.id, timeout=WAIT)
            fams = _parse_exposition(cp.prometheus())
            assert fams["regraph_lane_bandwidth_gbps"][2], \
                "no bandwidth samples after a lane-traced job"
            assert fams["regraph_pipeline_utilization"][2]
            snap = cp.metrics_snapshot()
            assert snap["service"]["utilization"]["kinds"]
