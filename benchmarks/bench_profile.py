"""Utilization-profiler gates — footprint truth, overhead, export.

The profiler (repro.obs.profile) is only worth shipping if its numbers
are *trustworthy* and its cost is *invisible*, so this suite gates:

  1. **byte parity** — every non-empty lane's analytic
     ``LaneFootprint.total_bytes`` within ±10% of the independent
     jaxpr-derived operand/result byte count of the same lane fn
     (they are exact today; the tolerance absorbs future traced
     constants).
  2. **overhead** — profile-on vs profile-off executors over the SAME
     cached plan, run interleaved (A/B per round) under per-lane
     tracing; profile-on p50 within 5%.
  3. **export** — a traced job through a ControlPlane surfaces
     ``regraph_lane_bandwidth_gbps`` / ``regraph_pipeline_utilization``
     samples on ``GET /metrics``, the ``/dashboard`` page serves, and
     ``/readyz`` reports ready.

Results go to stdout as CSV AND to ``BENCH_profile.json``.
"""
from __future__ import annotations

import json
import time
import urllib.request

import numpy as np

from repro import api, obs
from repro.core import gas
from repro.core.executor import Executor
from repro.graphs import datasets

from .common import GEOM, cpu_calibrated_hw, emit, store_for

GATE_BYTES = 0.10        # |analytic/jaxpr - 1| per non-empty lane
GATE_OVERHEAD = 1.05     # profile-on p50 / profile-off p50


def _traced_run(compiled_or_ex, tracer, iters):
    run = getattr(compiled_or_ex, "run")
    root = tracer.start_trace("bench")
    with tracer.activate(root.context):
        t0 = time.perf_counter()
        run(max_iters=iters)
        dt = time.perf_counter() - t0
    root.end()
    return dt


def _gate_bytes(ex) -> list:
    rows = []
    for li, fp in enumerate(ex.footprints()):
        truth = obs.jaxpr_lane_bytes(ex, li)
        if fp is None or truth is None:
            continue
        ratio = fp.total_bytes / truth
        rows.append({"lane": li, "kind": fp.kind,
                     "analytic_bytes": fp.total_bytes,
                     "jaxpr_bytes": truth, "ratio": ratio,
                     "hbm_bytes": fp.hbm_bytes,
                     "intensity": fp.intensity})
        assert abs(ratio - 1.0) <= GATE_BYTES, (
            f"lane {li} analytic bytes {fp.total_bytes} vs jaxpr "
            f"{truth} (ratio {ratio:.4f}) outside the "
            f"±{GATE_BYTES:.0%} gate")
    assert rows, "no non-empty lanes to validate"
    return rows


def _gate_overhead(store, hw, rounds, iters):
    c_on = api.compile(None, "pagerank", store=store, n_lanes=4, hw=hw)
    ex_on = c_on.executor
    ex_off = Executor(store, ex_on.bundle, gas.make_pagerank(),
                      profile=False)
    tr_on = obs.Tracer(lane_detail=True)
    tr_off = obs.Tracer(lane_detail=True)
    _traced_run(c_on, tr_on, iters)          # warm both jit paths
    _traced_run(ex_off, tr_off, iters)
    ts = {"on": [], "off": []}
    for _ in range(rounds):                  # interleaved: drift cancels
        ts["on"].append(_traced_run(c_on, tr_on, iters))
        ts["off"].append(_traced_run(ex_off, tr_off, iters))
    p50 = {k: float(np.median(v)) for k, v in ts.items()}
    ratio = p50["on"] / max(p50["off"], 1e-12)
    assert ratio <= GATE_OVERHEAD, (
        f"profiler-on p50 regression {100 * (ratio - 1):.1f}% exceeds "
        f"the {100 * (GATE_OVERHEAD - 1):.0f}% gate")
    util = ex_on.utilization()
    assert util["kinds"], "profile-on run recorded no utilization samples"
    assert not ex_off.utilization()["kinds"], \
        "profile=False executor must not accumulate samples"
    return {"p50_on_s": p50["on"], "p50_off_s": p50["off"],
            "overhead": ratio, "rounds": rounds,
            "kinds": {k: {"n": r["n"], "gbps": r["gbps"],
                          "utilization": r["utilization"]}
                      for k, r in util["kinds"].items()}}


def _http(url: str):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.status, r.read().decode()


def _gate_export(g) -> dict:
    from repro.control import ControlPlane
    with ControlPlane(workers=1, default_geom=GEOM, default_path="ref",
                      tracer=obs.Tracer(lane_detail=True)) as cp:
        fp = cp.register(g)
        rec = cp.submit_job(fingerprint=fp, app="pagerank", max_iters=2)
        cp.result(rec.id, timeout=120)
        server, base = cp.serve_http()
        code, text = _http(base + "/metrics")
        assert code == 200
        bw = [ln for ln in text.splitlines()
              if ln.startswith("regraph_lane_bandwidth_gbps{")]
        ut = [ln for ln in text.splitlines()
              if ln.startswith("regraph_pipeline_utilization{")]
        assert bw and ut, (
            "utilization gauges missing from /metrics after a traced "
            f"job: bw={bw} util={ut}")
        dcode, dhtml = _http(base + "/dashboard")
        assert dcode == 200 and "Pipeline utilization" in dhtml
        rcode, rbody = _http(base + "/readyz")
        ready = json.loads(rbody)
        assert rcode == 200 and ready["ready"], ready
        return {"bandwidth_samples": len(bw),
                "utilization_samples": len(ut),
                "dashboard_bytes": len(dhtml), "readyz": ready}


def run(graphs=None, rounds=9, iters=2, out_json="BENCH_profile.json"):
    graphs = graphs or ["ggs"]
    records = []
    for name in graphs:
        g = datasets.load(name)
        store = store_for(g)
        hw, _ = cpu_calibrated_hw(store)
        c = api.compile(None, "pagerank", store=store, n_lanes=4, hw=hw)
        byte_rows = _gate_bytes(c.executor)
        worst = max(abs(r["ratio"] - 1.0) for r in byte_rows)
        emit(f"profile.{name}.bytes", 0.0,
             f"{len(byte_rows)} lanes, worst |ratio-1|={worst:.4f} "
             f"(gate <= {GATE_BYTES:.2f})")
        ov = _gate_overhead(store, hw, rounds, iters)
        emit(f"profile.{name}.overhead", ov["p50_on_s"] * 1e6,
             f"overhead={100 * (ov['overhead'] - 1):+.1f}% "
             f"(gate <= {100 * (GATE_OVERHEAD - 1):.0f}%)")
        records.append({"graph": name, "V": g.num_vertices,
                        "E": g.num_edges, "lanes": byte_rows,
                        "worst_byte_ratio_err": worst, **ov})
    export = _gate_export(datasets.load(graphs[0]))
    emit("profile.export", 0.0,
         f"{export['bandwidth_samples']} bandwidth samples on /metrics; "
         f"dashboard+readyz ok")
    if out_json:
        with open(out_json, "w") as f:
            json.dump({"benchmark": "utilization_profiler",
                       "gate_bytes": GATE_BYTES,
                       "gate_overhead": GATE_OVERHEAD,
                       "records": records, "export": export}, f,
                      indent=2)
        emit("profile.artifact", 0.0, out_json)
    emit("profile.gate", 0.0, "pass")
    return records


if __name__ == "__main__":
    run()
