"""Serving subsystem: fingerprints, byte-budgeted store LRU, bounded
per-store plan LRU, GraphService queue/coalescing, and cold/warm parity
with the direct api.compile path.

Every blocking wait uses an explicit timeout so a queue/worker bug
fails loudly instead of hanging the suite (CI adds pytest-timeout on
top as a backstop).
"""
import inspect
import sys
import threading

import numpy as np
import pytest

from repro import api
from repro.core import gas
from repro.core.perf_model import TPU_V5E
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.serve_graph import (GraphService, GraphStoreCache, ServiceClosed,
                               graph_fingerprint, store_key)
from repro.serve_graph.fingerprint import resolve_fingerprint

GEOM = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)
WAIT = 300.0   # generous per-request wait; failures surface as TimeoutError

FIVE_APPS = [
    ("pagerank", {}),
    ("bfs", {"root": 0}),
    ("sssp", {"root": 0}),
    ("wcc", {}),
    ("closeness", {"sources": np.arange(4)}),
]


@pytest.fixture(scope="module")
def graphs():
    return [rmat(8, 6, seed=s, weighted=True) for s in (1, 2, 3)]


def make_service(**kw):
    kw.setdefault("default_geom", GEOM)
    kw.setdefault("default_path", "ref")
    return GraphService(**kw)


# ---------------------------------------------------------------- identity
def test_fingerprint_content_identity(graphs):
    g = graphs[0]
    # name is cosmetic: same content, different name -> same fingerprint
    twin = rmat(8, 6, seed=1, weighted=True, name="other-name")
    assert g.fingerprint() == twin.fingerprint()
    assert g.fingerprint() == graph_fingerprint(g)   # method == function
    assert g.fingerprint() != graphs[1].fingerprint()
    # weights participate in identity
    unweighted = rmat(8, 6, seed=1, weighted=False)
    assert unweighted.fingerprint() != g.fingerprint()
    # rebinding an array attribute invalidates the instance cache
    fp0 = unweighted.fingerprint()
    unweighted.weights = np.ones(unweighted.num_edges, np.float32)
    assert unweighted.fingerprint() != fp0


def test_resolve_fingerprint_and_store_key(graphs):
    g = graphs[0]
    fp = g.fingerprint()
    assert resolve_fingerprint(g) == fp
    assert resolve_fingerprint(fp) == fp
    assert resolve_fingerprint(g, fp) == fp
    with pytest.raises(ValueError):
        resolve_fingerprint(None, None)
    with pytest.raises(ValueError):
        resolve_fingerprint(g, "deadbeef")      # mismatched pair
    with pytest.raises(ValueError):
        store_key("", GEOM, True)
    assert store_key(fp, GEOM, True) != store_key(fp, GEOM, False)


# ------------------------------------------------------------- plan LRU
def test_store_plan_lru_bound_and_order(graphs):
    store = api.GraphStore(graphs[0], geom=GEOM, max_plans=2)
    b1 = store.plan(api.PlanConfig(n_lanes=1))
    b2 = store.plan(api.PlanConfig(n_lanes=2))
    store.plan(api.PlanConfig(n_lanes=1))            # touch: b1 now MRU
    b3 = store.plan(api.PlanConfig(n_lanes=3))       # evicts b2 (LRU)
    assert store.stats()["cached_plans"] == 2
    assert store.plan_evictions == 1
    assert store.plan(api.PlanConfig(n_lanes=1)) is b1
    assert store.plan(api.PlanConfig(n_lanes=3)) is b3
    assert store.plan(api.PlanConfig(n_lanes=2)) is not b2   # rebuilt
    assert store.has_plan(api.PlanConfig(n_lanes=2))
    with pytest.raises(ValueError):
        api.GraphStore(graphs[0], geom=GEOM, max_plans=0)


def test_plan_eviction_does_not_break_running_executor(graphs):
    """An Executor holds its own bundle reference; plan-LRU eviction
    must not invalidate it."""
    store = api.GraphStore(graphs[0], geom=GEOM, max_plans=1)
    ex = store.executor(gas.make_pagerank(max_iters=2),
                        api.PlanConfig(n_lanes=2), path="ref")
    store.plan(api.PlanConfig(n_lanes=1))    # evicts ex's cached bundle
    assert not store.has_plan(api.PlanConfig(n_lanes=2))
    props, meta = ex.run(max_iters=2)        # still runs fine
    assert meta["iterations"] >= 1


def test_quantized_hw_cache_keys_share_plans(graphs):
    """Two near-identical calibrations (differences past the 3rd
    significant digit, as successive host calibrations produce) must
    share one cached plan; a genuinely different calibration must not."""
    noisy_a = TPU_V5E.clone(c_edges=1.0001234, c_store=0.5000321,
                            gather_b=2.0004e-6)
    noisy_b = TPU_V5E.clone(c_edges=1.0002999, c_store=0.5001987,
                            gather_b=2.0009e-6)
    assert (api.PlanConfig(hw=noisy_a).cache_key()
            == api.PlanConfig(hw=noisy_b).cache_key())
    store = api.GraphStore(graphs[0], geom=GEOM)
    assert store.plan(api.PlanConfig(hw=noisy_a)) is \
        store.plan(api.PlanConfig(hw=noisy_b))
    distinct = TPU_V5E.clone(c_edges=1.27)
    assert (api.PlanConfig(hw=distinct).cache_key()
            != api.PlanConfig(hw=noisy_a).cache_key())
    assert store.plan(api.PlanConfig(hw=distinct)) is not \
        store.plan(api.PlanConfig(hw=noisy_a))


# -------------------------------------------------------- memory footprint
def test_memory_footprint_accounting(graphs):
    store = api.GraphStore(graphs[0], geom=GEOM)
    fp0 = store.memory_footprint()
    parts = ("graph_bytes", "edge_bytes", "blocking_bytes", "plan_bytes",
             "aux_bytes")
    assert all(fp0[k] >= 0 for k in parts)
    assert fp0["total_bytes"] == sum(fp0[k] for k in parts)
    assert fp0["graph_bytes"] > 0 and fp0["edge_bytes"] > 0
    assert fp0["plan_bytes"] == 0                 # nothing planned yet

    bundle = store.plan(api.PlanConfig(n_lanes=2))
    fp1 = store.memory_footprint()
    assert fp1["blocking_bytes"] > fp0["blocking_bytes"]
    bundle.lane_entries()                         # materialize on device
    fp2 = store.memory_footprint()
    assert fp2["plan_bytes"] > 0
    assert fp2["total_bytes"] > fp1["total_bytes"]
    assert store.stats()["total_bytes"] == fp2["total_bytes"]


# ------------------------------------------------------------- store cache
def _stores(graphs):
    return [(store_key(g.fingerprint(), GEOM, True),
             api.GraphStore(g, geom=GEOM)) for g in graphs]


def test_store_cache_lru_eviction_order(graphs):
    entries = _stores(graphs)
    cache = GraphStoreCache(max_stores=2)
    for k, s in entries:
        cache.put(k, s)
    assert len(cache) == 2 and cache.evictions == 1
    assert cache.keys() == [k for k, _ in entries[1:]]   # oldest gone
    # touching the LRU entry protects it from the next eviction
    assert cache.get(entries[1][0]) is entries[1][1]
    cache.put(*entries[0])
    assert entries[1][0] in cache and entries[2][0] not in cache


def test_store_cache_byte_budget(graphs):
    entries = _stores(graphs)
    one = entries[0][1].memory_footprint()["total_bytes"]
    cache = GraphStoreCache(byte_budget=int(one * 2.5))
    for k, s in entries:
        cache.put(k, s)
    assert len(cache) == 2
    assert cache.current_bytes <= int(one * 2.5)
    assert cache.evictions == 1
    # a budget smaller than one store still admits it (soft cap), then
    # evicts it as soon as the next store arrives
    tiny = GraphStoreCache(byte_budget=one // 2)
    tiny.put(*entries[0])
    assert len(tiny) == 1
    tiny.put(*entries[1])
    assert len(tiny) == 1 and entries[1][0] in tiny
    with pytest.raises(ValueError):
        GraphStoreCache(byte_budget=0)
    with pytest.raises(ValueError):
        GraphStoreCache(max_stores=0)


def test_store_cache_eviction_releases_plans(graphs):
    k, s = _stores(graphs[:1])[0]
    s.plan(api.PlanConfig(n_lanes=2))
    assert s.stats()["cached_plans"] == 1
    cache = GraphStoreCache()
    cache.put(k, s)
    assert cache.evict(k)
    assert s.stats()["cached_plans"] == 0    # device entries released


def test_store_cache_pinning_blocks_eviction(graphs):
    entries = _stores(graphs)
    cache = GraphStoreCache(max_stores=1)
    cache.put(*entries[0])
    with cache.lease(entries[0][0]) as (store, hit):
        assert hit and store is entries[0][1]
        assert not cache.evict(entries[0][0])          # pinned
        cache.put(*entries[1])                          # over budget...
        assert entries[0][0] in cache                   # ...but pinned stays
        assert cache.stats()["pinned"] == 1
    # lease released -> budget enforced again
    assert len(cache) == 1 and entries[0][0] not in cache
    with pytest.raises(KeyError):
        with cache.lease(entries[2][0]):                # no builder
            pass
    built = []
    with cache.lease(entries[2][0],
                     builder=lambda: built.append(1) or entries[2][1]) \
            as (store, hit):
        assert not hit and built == [1]


def test_store_cache_failed_build_recovers(graphs):
    """A builder that raises must not wedge the key: the placeholder is
    removed and the next lease builds normally."""
    entries = _stores(graphs[:1])
    cache = GraphStoreCache()
    k = entries[0][0]
    with pytest.raises(RuntimeError, match="bad build"):
        with cache.lease(k, builder=lambda: (_ for _ in ()).throw(
                RuntimeError("bad build"))):
            pass
    assert k not in cache and cache.pin_count(k) == 0
    with cache.lease(k, builder=lambda: entries[0][1]) as (store, hit):
        assert store is entries[0][1] and not hit


def test_store_cache_concurrent_builds_dedupe(graphs):
    """Concurrent leases: same key builds once (waiters latch on the
    first build), different keys build concurrently off-lock."""
    entries = _stores(graphs[:2])
    cache = GraphStoreCache()
    calls = []
    barrier = threading.Barrier(4, timeout=WAIT)
    results, errs = [], []

    def worker(i):
        k, s = entries[i % 2]

        def build():
            calls.append(i % 2)
            return s

        try:
            barrier.wait()
            with cache.lease(k, builder=build) as (store, _hit):
                results.append(store is s)
        except BaseException as e:
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=WAIT)
    assert not errs and results == [True] * 4
    assert sorted(calls) == [0, 1]      # exactly one build per key


def test_store_cache_get_or_build_and_stats(graphs):
    entries = _stores(graphs[:1])
    cache = GraphStoreCache()
    calls = []
    k = entries[0][0]
    s1, hit1 = cache.get_or_build(k, lambda: calls.append(1)
                                  or entries[0][1])
    s2, hit2 = cache.get_or_build(k, lambda: calls.append(1)
                                  or entries[0][1])
    assert (hit1, hit2) == (False, True) and s1 is s2 and calls == [1]
    st = cache.stats()
    assert st["hits"] == 1 and st["misses"] == 1 and st["hit_rate"] == 0.5
    assert cache.clear() == 1 and len(cache) == 0


# ---------------------------------------------------------------- service
def test_coalescing_n_submits_one_execution(graphs):
    with make_service(workers=2) as svc:
        hs = [svc.submit(graphs[0], "pagerank", n_lanes=2, max_iters=4)
              for _ in range(8)]
        results = [h.result(timeout=WAIT) for h in hs]
        assert svc.metrics.executions == 1
        assert svc.metrics.submitted == 8
        assert svc.metrics.coalesced >= 1
        # fan-out shares the one result object
        for props, meta in results[1:]:
            assert props is results[0][0]
            assert meta is results[0][1]
        assert sum(1 for h in hs if h.metrics.coalesced) \
            == svc.metrics.coalesced
        # the stage breakdown belongs to the executing request only;
        # twins get their own end-to-end time + the shared hit flags
        leader = [h for h in hs if not h.metrics.coalesced]
        assert len(leader) == 1
        assert leader[0].metrics.t_execute_ms is not None
        for h in hs:
            if h.metrics.coalesced:
                assert h.metrics.t_execute_ms is None
                assert h.metrics.t_queue_ms is None
            assert h.metrics.t_total_ms is not None
            assert h.metrics.store_hit is not None


def test_coalesced_records_never_skew_stage_reservoirs():
    """Regression: a coalesced duplicate must contribute ONLY its own
    end-to-end latency — even if a buggy caller fills its stage times
    in, record_done drops them. One poisoned twin would otherwise drag
    a stage percentile toward a time that stage never spent."""
    from repro.serve_graph.metrics import RequestMetrics, ServiceMetrics
    m = ServiceMetrics()
    for i in range(4):
        m.record_done(RequestMetrics(
            request_id=i, app="pagerank", fingerprint="f",
            t_queue_ms=1.0, t_store_ms=1.0, t_plan_ms=1.0,
            t_execute_ms=10.0, t_total_ms=12.0))
    # a coalesced twin with (bogus) stage times filled in
    m.record_done(RequestMetrics(
        request_id=99, app="pagerank", fingerprint="f", coalesced=True,
        t_queue_ms=9999.0, t_store_ms=9999.0, t_plan_ms=9999.0,
        t_execute_ms=9999.0, t_total_ms=50.0))
    snap = m.snapshot()
    for stage in ("queue", "store", "plan", "execute"):
        assert snap[f"p99_{stage}_ms"] < 9999.0, stage
    # its end-to-end time DOES count (it is a real request outcome)
    assert snap["p99_total_ms"] == 50.0
    assert snap["completed"] == 5


def test_distinct_requests_do_not_coalesce(graphs):
    with make_service(workers=1) as svc:
        a = svc.submit(graphs[0], "bfs", app_kwargs={"root": 0}, n_lanes=2)
        b = svc.submit(graphs[0], "bfs", app_kwargs={"root": 5}, n_lanes=2)
        c = svc.submit(graphs[0], "bfs", app_kwargs={"root": 0}, n_lanes=1)
        for h in (a, b, c):
            h.result(timeout=WAIT)
        assert svc.metrics.executions == 3
        assert svc.metrics.coalesced == 0
        assert not np.array_equal(a.result()[0], b.result()[0])


def test_cold_warm_parity_with_compile(graphs):
    """Serving must be a pure routing layer: cold AND warm results are
    bit-identical to the direct api.compile path."""
    g = graphs[0]
    with make_service(workers=2) as svc:
        cold = svc.submit(g, "pagerank", n_lanes=2, max_iters=6)
        p_cold, m_cold = cold.result(timeout=WAIT)
        warm = svc.submit(g, "pagerank", n_lanes=2, max_iters=6)
        p_warm, m_warm = warm.result(timeout=WAIT)
        assert cold.metrics.store_hit is False
        assert warm.metrics.store_hit is True and warm.metrics.plan_hit
    ref, meta = api.compile(g, "pagerank", geom=GEOM, n_lanes=2,
                            path="ref").run(max_iters=6)
    assert m_cold["iterations"] == meta["iterations"]
    np.testing.assert_array_equal(p_cold, ref)
    np.testing.assert_array_equal(p_warm, ref)


def test_warm_mixed_workload_hit_rate(graphs):
    """Acceptance: five builtin apps × three graphs; after a cold pass,
    the warm pass is 100% store-cache hits and the overall store hit
    rate is >= 80%."""
    with make_service(workers=2) as svc:
        for _round in range(2):
            hs = [svc.submit(g, name, app_kwargs=kw, n_lanes=2, max_iters=3)
                  for g in graphs for name, kw in FIVE_APPS]
            for h in hs:
                h.result(timeout=WAIT)
        assert all(h.metrics.store_hit for h in hs)      # warm round
        assert svc.metrics.store_hit_rate >= 0.8
        assert svc.metrics.plan_hit_rate >= 0.8
        snap = svc.stats()
        assert snap["service"]["executions"] == 2 * len(graphs) * 5
        assert snap["store_cache"]["stores"] == len(graphs)
        assert snap["service"]["p50_total_ms"] is not None
        assert snap["service"]["p99_execute_ms"] is not None


def test_eviction_under_pressure_never_breaks_requests(graphs):
    """max_stores=1 forces an eviction on nearly every alternation;
    every request must still complete and match the direct path."""
    refs = [api.compile(g, "pagerank", geom=GEOM, n_lanes=2,
                        path="ref").run(max_iters=3)[0] for g in graphs[:2]]
    with make_service(workers=2, max_stores=1) as svc:
        handles = [(i % 2, svc.submit(graphs[i % 2], "pagerank", n_lanes=2,
                                      max_iters=3))
                   for i in range(6)]
        for gi, h in handles:
            props, _ = h.result(timeout=WAIT)
            np.testing.assert_array_equal(props, refs[gi])
        assert svc.cache.evictions > 0
        assert svc.cache.stats()["stores"] <= 2


def test_submit_by_fingerprint_and_register(graphs):
    g = graphs[0]
    with make_service(workers=1) as svc:
        with pytest.raises(KeyError):
            svc.submit(fingerprint=g.fingerprint(), app="pagerank")
        fp = svc.register(g)
        assert fp == g.fingerprint()
        assert svc.cache.stats()["stores"] == 1     # prepared eagerly
        h = svc.submit(fingerprint=fp, app="pagerank", n_lanes=2,
                       max_iters=3)
        props, _ = h.result(timeout=WAIT)
        assert h.metrics.store_hit is True
        # registered graphs survive eviction: the store is rebuilt
        svc.cache.clear()
        h2 = svc.submit(fingerprint=fp, app="pagerank", n_lanes=2,
                        max_iters=3)
        p2, _ = h2.result(timeout=WAIT)
        assert h2.metrics.store_hit is False
        np.testing.assert_array_equal(p2, props)
        # submitting a raw Graph does NOT pin it in the registry
        other = rmat(8, 6, seed=9)
        svc.submit(other, "wcc", n_lanes=2, max_iters=2).result(timeout=WAIT)
        svc.cache.clear()
        with pytest.raises(KeyError):
            svc.submit(fingerprint=other.fingerprint(), app="wcc")
        # unregister drops the rebuild path for registered graphs too
        assert svc.unregister(fp) and not svc.unregister(fp)
        with pytest.raises(KeyError):
            svc.submit(fingerprint=fp, app="pagerank")


def test_submit_validation_and_close(graphs):
    svc = make_service(workers=1)
    with pytest.raises(ValueError):
        svc.submit(graphs[0], "nope")
    with pytest.raises(ValueError):
        svc.submit(graphs[0], "pagerank", config=api.PlanConfig(),
                   n_lanes=2)
    with pytest.raises(ValueError):
        svc.submit(graphs[0], gas.make_pagerank(),
                   app_kwargs={"root": 0})     # kwargs need a builtin name
    with pytest.raises(ValueError):
        svc.submit()                            # no graph, no fingerprint
    h = svc.submit(graphs[0], "wcc", n_lanes=2, max_iters=3)
    svc.close()
    assert h.done() and h.exception() is None
    with pytest.raises(ServiceClosed):
        svc.submit(graphs[0], "pagerank")
    svc.close()    # idempotent


def test_request_error_propagates_to_every_twin(graphs):
    def bad_init(aux):
        raise RuntimeError("boom at init")

    app = gas.GASApp("boom", "sum", lambda *a: a[0], lambda a, v, x, it: v,
                     bad_init, lambda old, new, it: True)
    with make_service(workers=1) as svc:
        hs = [svc.submit(graphs[0], app, n_lanes=2) for _ in range(3)]
        for h in hs:
            with pytest.raises(RuntimeError, match="boom at init"):
                h.result(timeout=WAIT)
            assert "boom at init" in h.metrics.error
        assert svc.metrics.failed == 3
        # the worker survived: a good request still completes
        ok = svc.submit(graphs[0], "pagerank", n_lanes=2, max_iters=2)
        ok.result(timeout=WAIT)


def test_gasapp_instance_coalesces_only_with_itself(graphs):
    app = gas.make_pagerank(max_iters=4)
    with make_service(workers=2) as svc:
        hs = [svc.submit(graphs[0], app, n_lanes=2) for _ in range(4)]
        other = svc.submit(graphs[0], gas.make_pagerank(max_iters=4),
                           n_lanes=2)
        for h in hs + [other]:
            h.result(timeout=WAIT)
        # 4 submits of the same instance -> 1 execution; a different
        # instance (opaque params) -> its own execution
        assert svc.metrics.executions == 2


def test_concurrent_submitters_thread_safety(graphs):
    """Many client threads hammering one service: every handle resolves
    and per-graph results agree."""
    with make_service(workers=2) as svc:
        results = {}
        errs = []

        def client(i):
            try:
                g = graphs[i % 2]
                h = svc.submit(g, "wcc", n_lanes=2, max_iters=4)
                results[i] = (i % 2, h.result(timeout=WAIT)[0])
            except BaseException as e:     # surface in main thread
                errs.append(e)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=WAIT)
        assert not errs and len(results) == 12
        for gi in (0, 1):
            vals = [p for g, p in results.values() if g == gi]
            for v in vals[1:]:
                np.testing.assert_array_equal(v, vals[0])


# ------------------------------------- one executor per app program
def _fresh(g, app, kw):
    """The answer of a freshly built executor for one request."""
    return api.compile(g, gas.BUILTIN_APPS[app](**kw), geom=GEOM,
                       path="ref", n_lanes=2).run()[0]


START_CASES = {
    "bfs": [{"root": 7}, {"root": 8}, {"root": 7}],
    "sssp": [{"root": 7}, {"root": 8}],
    "closeness": [{"sources": np.arange(4)},
                  {"sources": np.arange(40, 72)}],
}


@pytest.mark.parametrize("app", sorted(START_CASES))
def test_new_start_state_reuses_the_executor(graphs, app):
    """A new root or source set runs on the cached executor of the app's
    program, and each answer equals a fresh executor's for that
    request: no start state leaks from an earlier request."""
    g, kws = graphs[0], START_CASES[app]
    with make_service(workers=1) as svc:
        hs, got = [], []
        for kw in kws:
            hs.append(svc.submit(g, app, app_kwargs=kw, n_lanes=2))
            got.append(hs[-1].result(timeout=WAIT)[0])
            np.testing.assert_array_equal(got[-1], _fresh(g, app, kw))
        assert not np.array_equal(got[0], got[1])
        assert svc.stats()["cached_executors"] == 1
        assert [h.metrics.executor_hit for h in hs] == \
            [False] + [True] * (len(kws) - 1)
        snap = svc.stats()["service"]
        assert (snap["executor_misses"], snap["executor_hits"]) == \
            (1, len(kws) - 1)
        assert snap["executor_hit_rate"] == (len(kws) - 1) / len(kws)
        text = svc.metrics.render_prometheus()
        for event, n in (("hit", len(kws) - 1), ("miss", 1)):
            assert (f'regraph_cache_events_total{{layer="executor",'
                    f'event="{event}"}} {n}') in text.splitlines()
        assert svc.metrics.executions == len(kws)


@pytest.mark.parametrize("app,kws", [
    ("pagerank", [{"damping": 0.85}, {"damping": 0.5}]),
    ("bfs", [{"root": 3, "max_iters": 2}, {"root": 3, "max_iters": 64}]),
], ids=["pagerank-damping", "bfs-max_iters"])
def test_program_kwargs_keep_separate_executors(graphs, app, kws):
    g = graphs[0]
    with make_service(workers=1) as svc:
        hs = [svc.submit(g, app, app_kwargs=kw, n_lanes=2) for kw in kws]
        for h, kw in zip(hs, kws):
            np.testing.assert_array_equal(h.result(timeout=WAIT)[0],
                                          _fresh(g, app, kw))
        assert svc.stats()["cached_executors"] == 2
        assert [h.metrics.executor_hit for h in hs] == [False, False]


def test_concurrent_roots_share_an_executor_but_never_coalesce(graphs):
    """Twelve workers (more than a typical test host's cores) and a
    short switch interval: requests with different roots run at once on
    shared executors, never coalesce, and each gets its own root's
    answer."""
    g, roots = graphs[0], range(1, 13)
    refs = [_fresh(g, "bfs", {"root": r}) for r in roots]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with make_service(workers=len(roots)) as svc:
            hs = [svc.submit(g, "bfs", app_kwargs={"root": r}, n_lanes=2)
                  for r in roots]
            got = [h.result(timeout=WAIT)[0] for h in hs]
            stats = svc.stats()
    finally:
        sys.setswitchinterval(old)
    for props, ref in zip(got, refs):
        np.testing.assert_array_equal(props, ref)
    snap = stats["service"]
    assert snap["coalesced"] == 0 and snap["executions"] == len(roots)
    assert not any(h.metrics.coalesced for h in hs)
    assert stats["cached_executors"] == 1
    assert 1 <= snap["executor_misses"] <= len(roots)


START_VALUES = {"root": (0, 5),
                "sources": (np.arange(32), np.arange(7, 39))}


@pytest.mark.parametrize("app,kwarg", [
    (app, kw) for app, kws in sorted(gas.START_KWARGS.items())
    for kw in kws])
def test_start_kwargs_reach_only_init(app, kwarg):
    """The table's claim, checked per app and kwarg: two apps that
    differ in it compute the same scatter, apply and converged on the
    same inputs, and a different init."""
    factory = gas.BUILTIN_APPS[app]
    assert kwarg in inspect.signature(factory).parameters
    lo, hi = START_VALUES[kwarg]
    a, b = factory(**{kwarg: lo}), factory(**{kwarg: hi})
    rng = np.random.default_rng(0)
    n = 256
    if a.gather == "or":
        src = rng.integers(-2**31, 2**31 - 1, n, dtype=np.int32)
        prop = rng.integers(-2**31, 2**31 - 1, n, dtype=np.int32)
    else:
        src = np.where(rng.random(n) < 0.3, gas.INF,
                       rng.random(n) * 9).astype(np.float32)
        prop = np.where(rng.random(n) < 0.5, gas.INF,
                        rng.random(n) * 9).astype(np.float32)
    w = (rng.random(n) + 1).astype(np.float32)
    aux = {"outdeg": rng.integers(0, 5, n).astype(np.float32),
           "num_v": n, "num_v_pad": n}
    np.testing.assert_array_equal(a.scatter(src, w), b.scatter(src, w))
    accum = a.scatter(src, w)
    for it in (0, 3):
        np.testing.assert_array_equal(a.apply(accum, prop, aux, it),
                                      b.apply(accum, prop, aux, it))
        new = a.apply(accum, prop, aux, it)
        for old in (prop, new):
            assert a.converged(old, new, it) == b.converged(old, new, it)
    assert (a.gather, a.prop_dtype, a.max_iters, a.needs_weights) == \
        (b.gather, b.prop_dtype, b.max_iters, b.needs_weights)
    assert not np.array_equal(a.init(aux), b.init(aux))
