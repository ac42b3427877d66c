"""Device idle pinned on the program's spans, and the Little/Big kernel
split (``bench/spanreduce.py`` and its six readers).

A synthetic trace checks the interval arithmetic; the trace recorded on
a TPU v5e before the program had spans reads nothing; and the accepted
trace reduction and its nine readers read on that trace exactly what
they read before spans existed (``v5e_small.readings.json``).
"""
import json
from pathlib import Path

import pytest

from bench import spanreduce as sr
from bench import tracereduce as tr
from bench.loader import Bench
from bench.run import Request, RunRecord

from .test_bench_trace import _Ev, _Line, _Plane, _Profile

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).with_name("fixtures")
SMALL = FIXTURES / "v5e_small.xplane.pb"
ACCEPTED = ["service.overhead_ms", "store.build_s", "planner.plan_s",
            "planner.padding_efficiency", "executor.iterations_per_req",
            "executor.merge_apply_pct", "kernel.share_pct",
            "gas_kernel_roofline", "device.idle_pct"]
NEW = ["executor.compile_ms", "executor.traces_per_req",
       "device.idle_compile_pct", "device.idle_iteration_pct",
       "kernel.little_share_pct", "kernel.big_share_pct"]


def _read(name, record):
    return Bench(ROOT).reader(name)(record)


def _record(summary, spans=None, traces=(1, 0)):
    reqs = [Request("bfs", {"root": 3}, 0.5, 6, 500.0, 499.2),
            Request("wcc", {}, 0.4, 5, 400.0, 399.5)]
    for r, n in zip(reqs, traces):
        r.iteration_traces = n
    rec = RunRecord("tiny", "TPU v5 lite", {}, {}, reqs, 2.0, summary)
    rec.spans = spans
    return rec


def _kernel(i, kind=None):
    meta = "{}" if kind is None else '{\n"pipeline":"%s"\n}' % kind
    return (f"%gas_pallas_call.{i} = f32[8,1,512]{{2,1,0}} custom-call("
            f"s32[3] %a), custom_call_target=\"tpu_custom_call\", "
            f"frontend_attributes={{kernel_metadata={meta}}}")


def _synthetic():
    """Two requests. Host: a request annotation and, on the worker's
    line, service.execute > executor.iteration > executor.compile /
    executor.sync / executor.converged, then executor.readback."""
    client = _Line("python", [
        _Ev("request:bfs", 100, 900), _Ev("between requests", 1000, 100),
        _Ev("request:wcc", 1100, 400)])
    worker = _Line("python", [
        _Ev("service.executor", 110, 40),
        _Ev("service.execute", 150, 840),
        _Ev("executor.iteration", 160, 500),
        _Ev("executor.compile", 170, 300),
        _Ev("executor.sync", 480, 100),
        _Ev("executor.converged", 600, 50),
        _Ev("executor.readback", 900, 60),
        _Ev("service.execute", 1150, 300),
        _Ev("executor.iteration", 1160, 200),
        _Ev("executor.compile", 1170, 20),
        _Ev("PjitFunction(iteration)", 1170, 20)])
    dev = _Plane("/device:TPU:0", [_Line("XLA Ops", [
        _Ev(_kernel(1, "little"), 470, 60),          # 470-530
        _Ev(_kernel(2, "big"), 530, 40),             # 530-570
        _Ev("%fusion.1 = f32[8] fusion(f32[8] %x)", 560, 30),   # to 590
        _Ev(_kernel(3, "big"), 1200, 100),
        _Ev(_kernel(4), 1400, 50)])])
    return _Profile([_Plane("/host:CPU", [client, worker]), dev])


def test_innermost_takes_the_latest_open_span():
    spans = [(0, 100, "a"), (10, 50, "b"), (20, 30, "c"), (60, 70, "d"),
             (200, 210, "e")]
    assert sr.innermost(spans) == [(0, 10, "a"), (10, 20, "b"),
                                   (20, 30, "c"), (30, 50, "b"),
                                   (50, 60, "a"), (60, 70, "d"),
                                   (70, 100, "a"), (200, 210, "e")]


def test_idle_partitioned_by_innermost_span():
    pd = _synthetic()
    out = sr.reduce(pd)
    base = tr.reduce(pd)
    idle = {k: v * 1e9 for k, v in out["idle_s"].items()}
    # window 100-1500; busy 470-590, 1200-1300, 1400-1450
    assert idle == pytest.approx({
        sr.OUTSIDE: 10 + 160 + 50,          # 100-110, 990-1150, 1450-1500
        "service.executor": 40,
        "service.execute": 10 + 240 + 30 + 10 + 40,
        "executor.iteration": 10 + 10 + 10 + 10 + 10 + 60,
        "executor.compile": 300 + 20,
        "executor.converged": 50,
        "executor.readback": 60})
    assert sum(out["idle_s"].values()) == pytest.approx(
        base["window_s"] - base["busy_s"])
    assert out["kernel_kind_s"] == pytest.approx({"little": 60e-9,
                                                  "big": 140e-9})
    assert out["compile_ms_per_request"] == pytest.approx([300e-6, 20e-6])
    assert out["program_spans"] == 10


def test_readers_on_the_synthetic_trace():
    pd = _synthetic()
    rec = _record(tr.reduce(pd), sr.reduce(pd))
    got = {n: _read(n, rec) for n in NEW}
    assert got == pytest.approx({
        "executor.compile_ms": 160e-6,
        "executor.traces_per_req": 0.5,
        "device.idle_compile_pct": 100 * 320 / 1400,
        "device.idle_iteration_pct": 100 * (110 + 0 + 50) / 1400,
        "kernel.little_share_pct": 100 * 60 / 1400,
        "kernel.big_share_pct": 100 * 140 / 1400})
    # the unkinded launch (1400-1450) is kernel time of neither kind
    assert got["kernel.little_share_pct"] + got["kernel.big_share_pct"] \
        == pytest.approx(_read("kernel.share_pct", rec) - 100 * 50 / 1400)


def test_executor_hit_rate_reads_the_requests_that_carry_a_hit():
    rec = _record(None)
    assert _read("service.executor_hit_rate", rec) is None
    rec.requests[0].executor_hit = False
    assert _read("service.executor_hit_rate", rec) == 0.0
    rec.requests[1].executor_hit = True
    assert _read("service.executor_hit_rate", rec) == 0.5
    rec.requests[1].error = "TimeoutError: "
    assert _read("service.executor_hit_rate", rec) == 0.0


def test_a_trace_without_program_spans_reads_nothing():
    """The trace of a program from before the spans and kernel kinds:
    every new reader reads nothing, and none raises."""
    pd = tr.load(str(SMALL))
    spans = sr.reduce(pd)
    assert spans["program_spans"] == 0 and spans["kernel_kind_s"] == {}
    assert spans["idle_s"] == {sr.OUTSIDE: pytest.approx(
        sum(spans["idle_s"].values()))}
    rec = _record(tr.reduce(pd), spans, traces=(None, None))
    assert {n: _read(n, rec) for n in NEW} == dict.fromkeys(NEW)
    bare = _record(tr.reduce(pd))
    del bare.spans
    for r in bare.requests:
        del r.iteration_traces
    assert {n: _read(n, bare) for n in NEW} == dict.fromkeys(NEW)


def test_accepted_readings_unchanged_on_the_recorded_trace():
    """``tracereduce.reduce`` and the nine accepted readers, on the
    trace recorded before spans existed, read what the accepted
    benchmark read (``v5e_small.readings.json``, written by the
    accepted checkout), to the byte."""
    summary = tr.reduce(tr.load(str(SMALL)))
    reqs = [Request("pagerank", {}, 0.9, 16, 900.0, 899.1),
            Request("bfs", {"root": 3}, 0.5, 6, 500.0, 499.2),
            Request("wcc", {}, 0.4, 5, 400.0, 399.5)]
    rec = RunRecord("tiny", "TPU v5 lite",
                    {"store_build_s": 1.25, "plan_s": 0.5},
                    {"num_vertices": 4096, "num_edges": 31000,
                     "padded_edge_slots": 65536, "blocks": 256,
                     "little_lanes": 8, "big_lanes": 0}, reqs, 2.0, summary)
    now = {"reduce": summary,
           "readers": {n: _read(n, rec) for n in ACCEPTED}}
    want = (FIXTURES / "v5e_small.readings.json").read_text()
    assert json.dumps(now, indent=1, sort_keys=True) + "\n" == want
