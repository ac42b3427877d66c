"""The trace reduction: interval arithmetic, and a small trace recorded
on a TPU v5e (three requests of a scale-12 R-MAT graph through the
served path, host annotations as the benchmark writes them)."""
from pathlib import Path

import pytest

from bench import tracereduce as tr

FIXTURE = Path(__file__).with_name("fixtures") / "v5e_small.xplane.pb"


def test_union_and_gaps():
    cover = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert cover == [(0, 3), (5, 9), (12, 13)]
    assert tr.total(cover) == 8
    assert tr.gaps(cover, 0, 15) == [(3, 5), (9, 12), (13, 15)]
    assert tr.gaps(cover, 6, 12.5) == [(9, 12)]
    assert tr.gaps([], 1, 2) == [(1, 2)]


class _Ev:
    def __init__(self, name, start, dur, stats=()):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduce_on_a_synthetic_trace():
    k = "%gas_pallas_call.{} = f32[8,1,512]{{2,1,0}} custom-call(s32[3] %a)"
    host = _Plane("/host:CPU", [_Line("python", [
        _Ev("request:bfs", 100, 600), _Ev("between requests", 700, 100),
        _Ev("request:wcc", 800, 200)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit_iteration", 0, 2000)]),
        _Line("XLA Ops", [
            _Ev(k.format(3), 50, 250),          # clipped to start at 100
            _Ev("%fusion.1 = f32[8] fusion(f32[8] %x)", 300, 100),
            _Ev(k.format(4), 500, 100),
            _Ev("%fusion.2 = f32[8] fusion(f32[8] %y)", 550, 100),
            _Ev(k.format(3), 850, 100),
            _Ev("%fusion.3 = f32[8] fusion(f32[8] %z)", 1100, 50)])])
    out = tr.reduce(_Profile([host, dev]))
    assert out["window_s"] == pytest.approx(900e-9)
    assert out["busy_s"] == pytest.approx((300 + 150 + 100) * 1e-9)
    assert out["kernel_s"] == pytest.approx((200 + 100 + 100) * 1e-9)
    assert out["other_s"] == pytest.approx(150e-9)
    gaps = out["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["between requests", "request:bfs",
                                    "request:wcc"]
    assert gaps[0][1] == pytest.approx(200e-9)
    assert out["breakdown"]["device_ops"] == [
        ["gas_pallas_call", pytest.approx(400e-9)],
        ["fusion", pytest.approx(200e-9)]]


@pytest.mark.parametrize("name, kind, kernel", [
    ("%gas_pallas_call.14 = s32[189,1,512]{2,1,0:T(1,128)S(1)} "
     "custom-call(s32[29878]{0:T(1024)S(1)} %copy-done.25)",
     "gas_pallas_call", True),
    ("%copy-start.2 = (s32[3]{0:T(128)S(1)}) copy-start(s32[3] %a)",
     "copy-start", False),
    ("%broadcast_in_dim.9.clone = f32[2,1,512] broadcast(f32[] %c)",
     "broadcast_in_dim", False),
    ("%gas_pallas_call.3 = f32[2] fusion(f32[2] %x)", "gas_pallas_call",
     False)])
def test_op_kind_and_kernel(name, kind, kernel):
    assert tr.op_kind(name) == kind
    assert tr.is_kernel(name) is kernel


def test_reduce_refuses_a_trace_without_device_ops():
    host = _Plane("/host:CPU", [_Line("python", [_Ev("request:bfs", 0, 10)])])
    with pytest.raises(ValueError):
        tr.reduce(_Profile([host]))


def test_reduce_on_a_trace_recorded_on_the_chip():
    out = tr.reduce(tr.load(str(FIXTURE)))
    assert out["devices_traced"] == 1
    assert 0 < out["kernel_s"] < out["busy_s"] < out["window_s"]
    assert out["other_s"] == pytest.approx(out["busy_s"] - out["kernel_s"])
    names = [n for n, _ in out["breakdown"]["device_ops"]]
    assert len(names) == 10
    assert all(s > 0 for _, s in out["breakdown"]["idle_gaps"])
    labels = {n for n, _ in out["breakdown"]["idle_gaps"]}
    assert labels <= {"request", "between requests", "outside annotations"}
