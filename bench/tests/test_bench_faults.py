"""A run with the timed path broken underneath comes out not correct.

Each test drives ``run_cell`` on the CPU at a tiny size, skipping the
look for a chip and the Pallas check, with one fault planted in the
program: an iteration that returns its state unchanged, half of every
lane's output tiles left out of the merge, or an answer altered where the
executor produces it. One chip has no exchange between chips to leave
out.
"""
import json
import time

import numpy as np
import pytest

from bench import run
from bench.loader import Bench

from .conftest import TINY_CELL


def _run(root, seed=2**33 + 3, cell=TINY_CELL):
    import jax
    return run.run_cell(Bench(root), cell, seed, 1.0, False,
                        find=lambda chips: jax.devices()[:chips],
                        require_pallas=False, t_start=time.perf_counter())


def test_sound_run_is_correct(tiny_root):
    res = _run(tiny_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"requests_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_clients_share_the_stream_and_every_answer_is_checked(tiny_root):
    (tiny_root / "bench/traffic/concurrent.json").write_text(json.dumps(
        {"loop": "closed", "clients": 4, "apps": [
            {"app": "bfs", "per_deck": 3,
             "params": {"root": {"pool": 4, "zipf": 1.0}}},
            {"app": "pagerank", "per_deck": 1}]}))
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "tiny.concurrent", "config": "tiny",
                              "traffic": "concurrent", "chips": 1,
                              "why": "test"})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    res = _run(tiny_root, cell="tiny.concurrent")
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 4 and res["failed"] == 0
    assert set(res["checks"]) == {"bfs_mismatches", "pagerank_max_rel_err",
                                  "failed_requests"}


def _unchanged(monkeypatch):
    from repro.core.executor import Executor
    monkeypatch.setattr(Executor, "_build_iteration",
                        lambda self: lambda vprops, aux, it: vprops)


def _half_left_out(monkeypatch):
    from repro.kernels import ops
    merge = ops.merge_all
    monkeypatch.setattr(ops, "merge_all", lambda acc, outs, t: merge(
        acc, [(x[:x.shape[0] // 2], i[:i.shape[0] // 2]) for x, i in outs],
        t))


def _answer_altered(monkeypatch):
    from repro.core.executor import Executor
    run_ = Executor.run

    def altered(self, *a, **kw):
        props, meta = run_(self, *a, **kw)
        props = np.array(props)
        reached = np.flatnonzero(np.abs(props) < 1e30)   # not INF
        props[reached[len(reached) // 2]] += 1
        return props, meta

    monkeypatch.setattr(Executor, "run", altered)


@pytest.mark.parametrize("fault", [_unchanged, _half_left_out,
                                   _answer_altered])
def test_fault_makes_the_run_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tiny_root)
    assert res["correct"] is False, res["checks"]
