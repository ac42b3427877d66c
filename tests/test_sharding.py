"""Sharded execution: per-device lane ownership with plan-aware
placement.

Three layers of coverage:
  * bit-identical parity of the sharded path vs the single-device fused
    path for every builtin app on ref and pallas-interpret (in-process,
    1 device — the reduction/apply program restructure is exercised
    regardless of device count — plus an 8-device subprocess);
  * placement properties: every fresh LPT placement respects the greedy
    bound max_load <= total/n + max_est (hypothesis), kinds interleave,
    keep= pins owners;
  * streaming: after apply_delta, clean lanes' resident device payloads
    are NOT re-transferred (shards_moved accounting).

Multi-device tests spawn subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (conftest keeps the
main process at exactly one device).
"""
import os
import subprocess
import sys
import textwrap
import types

import numpy as np
import pytest

from repro import api
from repro.core import gas
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.sharding import place_lanes, resolve_devices
from repro.streaming import apply_delta, random_delta

GEOM = Geometry(U=128, W=128, T=128, E_BLK=128, big_batch=2)
APPS = ("pagerank", "bfs", "sssp", "wcc", "closeness")

ENV = {**os.environ,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.abspath(
           os.path.join(os.path.dirname(__file__), "..", "src"))}


def run_py(code: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


@pytest.fixture(scope="module")
def shard_graph():
    return rmat(9, 8, seed=3)       # 512 vertices, 4 partitions at U=128


@pytest.fixture(scope="module")
def shard_store(shard_graph):
    return api.GraphStore(shard_graph, geom=GEOM)


# -- parity (single device; program restructure is the risky part) -----

@pytest.mark.parametrize("app", APPS)
@pytest.mark.parametrize("path", ["ref", "pallas"])
def test_sharded_bit_identical(shard_store, app, path):
    cfg = api.PlanConfig(n_lanes=4)
    f = api.compile(None, app, store=shard_store, config=cfg, path=path)
    s = api.compile(None, app, store=shard_store, config=cfg, path=path,
                    shard=1)
    pf, mf = f.run(max_iters=3)
    ps, ms = s.run(max_iters=3)
    assert mf["iterations"] == ms["iterations"]
    np.testing.assert_array_equal(pf, ps)
    d = s.executor.dispatch_stats()
    assert d["shard"] and d["cross_device_merges"] == 1


def test_sharded_multi_device_bit_identical_all_apps():
    """8 forced CPU devices: every builtin app bit-identical to the
    single-device fused path on both kernel paths, payloads resident on
    their owner devices, dispatch counts matching the placement, and
    exactly one cross-device merge."""
    run_py("""
        import numpy as np, jax
        from repro import api
        from repro.core.types import Geometry
        from repro.graphs.rmat import rmat
        assert jax.device_count() == 8
        g = rmat(9, 8, seed=3)
        geom = Geometry(U=128, W=128, T=128, E_BLK=128, big_batch=2)
        store = api.GraphStore(g, geom=geom)
        cfg = api.PlanConfig(n_lanes=8)
        for path in ("ref", "pallas"):
            for app in ("pagerank", "bfs", "sssp", "wcc", "closeness"):
                f = api.compile(None, app, store=store, config=cfg,
                                path=path)
                s = api.compile(None, app, store=store, config=cfg,
                                path=path, shard=8)
                pf, mf = f.run(max_iters=3)
                ps, ms = s.run(max_iters=3)
                assert mf["iterations"] == ms["iterations"], (path, app)
                np.testing.assert_array_equal(pf, ps)
        d = s.executor.dispatch_stats()
        assert d["n_devices"] == 8
        assert d["cross_device_merges"] == 1
        sh = store.shard(cfg, 8)
        devs = jax.devices()
        per_dev = d["kernel_dispatches_per_device"]
        for i, lane in enumerate(sh.lanes):
            owner = sh.placement.device_of_lane[i]
            for p in lane:
                loc = next(iter(p["src_local"].devices()))
                assert loc == devs[owner], (i, owner, loc)
        assert per_dev == [len(sh.payloads_of(dv)) for dv in range(8)]
        assert sum(1 for n in per_dev if n) >= 2   # work actually spreads
        print("OK")
    """)


def test_sharded_mixed_lane_parity(shard_store):
    """n_lanes=1 puts both kinds in one lane; the sharded path must
    keep the per-kind payload split and still match exactly."""
    cfg = api.PlanConfig(mode="model", n_lanes=1)
    f = api.compile(None, "pagerank", store=shard_store, config=cfg,
                    path="ref")
    s = api.compile(None, "pagerank", store=shard_store, config=cfg,
                    path="ref", shard=1)
    pf, _ = f.run(max_iters=3)
    ps, _ = s.run(max_iters=3)
    np.testing.assert_array_equal(pf, ps)


# -- placement ----------------------------------------------------------

def _fake_plan(ests, m):
    lanes = [[types.SimpleNamespace(est_time=e)] if e else [] for e in ests]
    return types.SimpleNamespace(lanes=lanes, num_little_lanes=m)


def test_placement_balance_property():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=100, deadline=None)
    @given(ests=st.lists(st.floats(0.0, 10.0), min_size=1, max_size=24),
           n_dev=st.integers(1, 8), data=st.data())
    def check(ests, n_dev, data):
        m = data.draw(st.integers(0, len(ests)))
        pl = place_lanes(_fake_plan(ests, m), n_dev)
        assert len(pl.device_of_lane) == len(ests)   # every lane owned
        assert all(0 <= d < n_dev for d in pl.device_of_lane)
        # the greedy guarantee no fresh placement may exceed
        assert max(pl.loads) <= pl.lpt_bound() + 1e-9
        assert pl.imbalance >= 1.0 - 1e-9

    check()


def test_placement_interleaves_kinds():
    """2 Little + 2 Big lanes on 2 devices: each device must get one of
    each kind (the shared-load two-pass LPT), not kind-segregated."""
    pl = place_lanes(_fake_plan([1.0, 1.0, 1.0, 1.0], 2), 2)
    for d in range(2):
        kinds = {("little" if i < 2 else "big") for i in pl.lanes_of(d)}
        assert kinds == {"little", "big"}


def test_placement_keep_pins_owners():
    pl = place_lanes(_fake_plan([5.0, 4.0, 3.0, 2.0], 2), 2,
                     keep={0: 1, 1: 1})
    assert pl.device_of_lane[0] == 1 and pl.device_of_lane[1] == 1
    # free lanes fill the other device first (it has zero kept load)
    assert pl.device_of_lane[2] == 0 and pl.device_of_lane[3] == 0


def test_placement_rejects_bad_args():
    plan = _fake_plan([1.0, 2.0], 1)
    with pytest.raises(ValueError):
        place_lanes(plan, 0)
    with pytest.raises(ValueError):
        place_lanes(plan, 2, keep={5: 0})
    with pytest.raises(ValueError):
        place_lanes(plan, 2, lane_ests=[1.0])


def test_resolve_devices():
    import jax
    assert resolve_devices(None) == tuple(jax.devices())
    assert resolve_devices(True) == tuple(jax.devices())
    assert resolve_devices(1) == (jax.devices()[0],)
    with pytest.raises(ValueError):
        resolve_devices(jax.device_count() + 1)
    with pytest.raises(ValueError):
        resolve_devices(())


# -- store / bundle integration ----------------------------------------

def test_shard_memoized_and_counted(shard_store):
    cfg = api.PlanConfig(n_lanes=2)
    sh1 = shard_store.shard(cfg, 1)
    sh2 = shard_store.shard(cfg, 1)
    assert sh1 is sh2                     # memoized per device tuple
    bundle = shard_store.plan(cfg)
    db = bundle.device_bytes()
    assert db["sharded_bytes"] == sh1.nbytes() > 0
    st = shard_store.stats()
    assert st["placement"]["devices"] >= 1
    assert st["placement"]["sharded_plans"] >= 1
    assert sum(st["placement"]["bytes_per_device"]) >= sh1.nbytes()
    assert st["placement"]["imbalance"] >= 1.0
    # sharded payload bytes count toward the plan-cache accounting
    assert shard_store.memory_footprint()["plan_bytes"] >= sh1.nbytes()


def test_sharded_run_start_matches_fresh(shard_store):
    """One ShardedExecutor serves two roots through ``run(start=...)``;
    each answer equals a freshly built executor's for that root."""
    cfg = api.PlanConfig(n_lanes=4)
    shared = api.compile(None, gas.make_bfs(root=0), store=shard_store,
                         config=cfg, path="ref", shard=1).executor
    got = []
    for root in (5, 9):
        props, meta = shared.run(start=gas.make_bfs(root=root))
        fresh = api.compile(None, gas.make_bfs(root=root),
                            store=shard_store, config=cfg, path="ref",
                            shard=1)
        ref, ref_meta = fresh.run()
        np.testing.assert_array_equal(props, ref)
        assert meta["iterations"] == ref_meta["iterations"]
        got.append(props)
    assert not np.array_equal(got[0], got[1])


def test_merge_program_is_single_scatter(shard_store):
    """Program-derived gate: the traced merge+apply program contains
    exactly one scatter op — the single cross-device merge."""
    ex = shard_store.executor(gas.make_pagerank(max_iters=2),
                              api.PlanConfig(n_lanes=4), path="ref",
                              shard=1)
    assert ex.merge_trace_stats()["merge_scatter_ops"] == 1


def test_sharded_executor_footprint(shard_store):
    ex = shard_store.executor(gas.make_pagerank(max_iters=2),
                              api.PlanConfig(n_lanes=2), path="ref",
                              shard=1)
    assert ex.memory_footprint() == ex.sharded.nbytes() > 0
    st = ex.stats()
    assert st["placement"]["n_devices"] == 1
    assert st["kernel_dispatches"] == sum(
        st["kernel_dispatches_per_device"])


# -- streaming: clean lanes stay resident ------------------------------

def test_streaming_clean_lanes_not_retransferred():
    """After a small skewed-churn delta, at least half of the resident
    sharded lane payloads must be reused without re-transfer — asserted
    via the shards_moved accounting apply_delta surfaces."""
    g = rmat(11, 8, seed=5)
    store = api.GraphStore(g, geom=Geometry(U=128, W=128, T=128,
                                            E_BLK=128, big_batch=4))
    cfg = api.PlanConfig(n_lanes=8)
    ex = store.executor(gas.make_pagerank(max_iters=2), cfg, path="ref",
                        shard=1)
    ex.run(max_iters=2)
    delta = random_delta(g, churn=0.01, hot_frac=0.05,
                         base_fp=store.fingerprint())
    res = apply_delta(store, delta)
    s = res.stats
    assert s["shards_moved"] + s["shards_reused"] > 0
    assert s["shards_reused"] >= s["shards_moved"], s
    assert s["shard_bytes_reused"] > 0
    # reused payload objects are literally the resident ones (no copy)
    old_sh = store.plan(cfg).sharded_lanes(ex.devices)
    new_sh = res.store.plan(cfg).sharded_lanes(ex.devices)
    shared = sum(1 for a, b in zip(old_sh.lanes, new_sh.lanes)
                 if a and a is b)
    assert shared == s["shards_reused"]
    # and the derived store's sharded run is still exact
    pf, _ = res.store.executor(gas.make_pagerank(max_iters=2), cfg,
                               path="ref").run(max_iters=2)
    ps, _ = res.store.executor(gas.make_pagerank(max_iters=2), cfg,
                               path="ref", shard=1).run(max_iters=2)
    np.testing.assert_array_equal(pf, ps)


def test_streaming_shard_reuse_multi_device():
    """Same residency guarantee on a real 8-device topology: clean
    lanes keep their owner device and are not re-uploaded."""
    run_py("""
        import jax, numpy as np
        from repro import api
        from repro.core import gas
        from repro.core.types import Geometry
        from repro.graphs.rmat import rmat
        from repro.streaming import apply_delta, random_delta
        g = rmat(11, 8, seed=5)
        store = api.GraphStore(g, geom=Geometry(U=128, W=128, T=128,
                                                E_BLK=128, big_batch=4))
        cfg = api.PlanConfig(n_lanes=8)
        ex = store.executor(gas.make_pagerank(max_iters=2), cfg,
                            path="ref", shard=8)
        ex.run(max_iters=2)
        old_sh = store.plan(cfg).sharded_lanes(ex.devices)
        delta = random_delta(g, churn=0.01, hot_frac=0.05,
                             base_fp=store.fingerprint())
        res = apply_delta(store, delta)
        s = res.stats
        assert s["shards_reused"] >= s["shards_moved"], s
        new_sh = res.store.plan(cfg).sharded_lanes(ex.devices)
        for i, (a, b) in enumerate(zip(old_sh.lanes, new_sh.lanes)):
            if a and a is b:     # reused: same owner, same arrays
                assert (old_sh.placement.device_of_lane[i]
                        == new_sh.placement.device_of_lane[i])
        p1, _ = res.store.executor(gas.make_pagerank(max_iters=2), cfg,
                                   path="ref").run(max_iters=2)
        p2, _ = res.store.executor(gas.make_pagerank(max_iters=2), cfg,
                                   path="ref", shard=8).run(max_iters=2)
        np.testing.assert_array_equal(p1, p2)
        print("OK")
    """)


# -- serving ------------------------------------------------------------

def test_service_shard_requests(shard_graph):
    from repro.serve_graph import GraphService
    with GraphService(workers=1, default_path="ref") as svc:
        r1, _ = svc.run(shard_graph, "pagerank", max_iters=2, n_lanes=2,
                        shard=1)
        r2, _ = svc.run(shard_graph, "pagerank", max_iters=2, n_lanes=2)
        np.testing.assert_array_equal(r1, r2)
        # sharded and unsharded requests cache distinct executors
        assert svc.stats()["cached_executors"] == 2
        with pytest.raises(ValueError):
            svc.submit(shard_graph, "pagerank", shard="all")
        with pytest.raises(ValueError):
            svc.submit(shard_graph, "pagerank", shard=0)
        # shard=True resolves to the device count at submit time, so it
        # shares keys with an equal explicit int (on 1 device: shard=1)
        r3, _ = svc.run(shard_graph, "pagerank", max_iters=2, n_lanes=2,
                        shard=True)
        np.testing.assert_array_equal(r1, r3)
        assert svc.stats()["cached_executors"] == 2


def test_service_default_shard(shard_graph):
    from repro.serve_graph import GraphService
    with GraphService(workers=1, default_path="ref",
                      default_shard=1) as svc:
        r1, _ = svc.run(shard_graph, "pagerank", max_iters=2, n_lanes=2)
        # shard=False opts a single request out of the default
        r2, _ = svc.run(shard_graph, "pagerank", max_iters=2, n_lanes=2,
                        shard=False)
        np.testing.assert_array_equal(r1, r2)
        assert svc.stats()["cached_executors"] == 2
