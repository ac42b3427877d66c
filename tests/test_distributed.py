"""Multi-device tests — each spawns a CPU-only subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test
process keeps seeing exactly one device."""
import os
import subprocess
import sys
import textwrap

ENV = {**os.environ, "JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": os.path.abspath(
           os.path.join(os.path.dirname(__file__), "..", "src"))}


def run_py(code: str, timeout=600):
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=ENV, capture_output=True, text=True,
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_distributed_graph_engine_matches_single():
    """DistributedEngine now builds directly on the layered API: one
    GraphStore shared by the single-device baseline and the shard_map
    path (one plan cache, one preprocessing pass)."""
    run_py("""
        import numpy as np
        from repro.graphs.rmat import rmat
        from repro.core.types import Geometry
        from repro.core import gas
        from repro.core.planner import PlanConfig
        from repro.core.store import GraphStore
        from repro.core.distributed import DistributedEngine
        g = rmat(10, 8, seed=3)
        geom = Geometry(U=1024, W=512, T=512, E_BLK=128, big_batch=4)
        store = GraphStore(g, geom=geom)
        cfg = PlanConfig(n_lanes=8)
        for mk, iters in [(lambda: gas.make_pagerank(max_iters=4), 4),
                          (lambda: gas.make_bfs(root=2), 8)]:
            app = mk()
            p1,_ = store.executor(app, cfg, path="ref").run(max_iters=iters)
            d = DistributedEngine(store, app, config=cfg)
            p2,_ = d.run(max_iters=iters)
            assert np.allclose(p1, p2, rtol=1e-5, atol=1e-7), app.name
        assert store.stats()["cached_plans"] == 1   # one shared plan
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    run_py("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, reduced
        from repro.models.api import build_model
        from repro.optim.adamw import adamw
        from repro.train.step import make_train_step
        from repro.sharding.specs import tree_shardings, batch_shardings
        cfg = dataclasses.replace(reduced(get_config("qwen2_1p5b")),
                                  dtype="float32")
        model = build_model(cfg)
        opt = adamw(lr=1e-2, weight_decay=0.0)
        params = model.init(jax.random.key(0))
        st = opt.init(params)
        rs = np.random.RandomState(0)
        tok = jnp.asarray(rs.randint(0, cfg.vocab_size, (8, 32)), jnp.int32)
        batch = {"tokens": tok, "labels": tok}
        step = make_train_step(model, opt)
        p1, s1, m1 = jax.jit(step)(params, st, batch)
        mesh = jax.make_mesh((4, 2), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with mesh:
            psh = tree_shardings(params, mesh)
            ssh = tree_shardings(st, mesh)
            bsh = batch_shardings(batch, mesh)
            pd = jax.device_put(params, psh)
            sd = jax.device_put(st, ssh)
            bd = jax.device_put(batch, bsh)
            p2, s2, m2 = jax.jit(step, in_shardings=(psh, ssh, bsh),
                                 out_shardings=(psh, ssh, None))(pd, sd, bd)
        assert abs(float(m1['loss']) - float(m2['loss'])) < 1e-3
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=1e-3, atol=1e-4)
        print("OK")
    """)


def test_sharded_moe_matches_local():
    run_py("""
        import numpy as np, jax, jax.numpy as jnp, dataclasses
        from repro.configs import get_config, reduced
        from repro.models import moe
        cfg = dataclasses.replace(reduced(get_config("granite_moe_3b_a800m")),
                                  moe_dispatch="biglittle")
        lp_full = moe.init_layer_params(cfg, jax.random.key(1))
        lp = {k: jax.tree.map(lambda a: a.astype(jnp.float32), lp_full[k])
              for k in ("router", "we_gate", "we_up", "we_down")}
        x = jax.random.normal(jax.random.key(2), (8, 16, cfg.d_model),
                              jnp.float32) * 0.5
        out_local, _ = moe.moe_ffn(cfg, lp, x, capacity_factor=50.0)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        with mesh:
            out_shard, _ = jax.jit(lambda lp, x: moe.moe_ffn(
                cfg, lp, x, capacity_factor=50.0))(lp, x)
        assert np.allclose(np.asarray(out_local), np.asarray(out_shard),
                           rtol=1e-4, atol=1e-5)
        print("OK")
    """)


def test_compressed_psum_cross_pod():
    run_py("""
        import numpy as np, jax, jax.numpy as jnp
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from repro.optim import grad_compress as gc
        mesh = jax.make_mesh((2, 4), ("pod", "data"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        grads = {"w": jnp.arange(32.0).reshape(4, 8) / 100}
        resid = gc.zero_residual(grads)

        @partial(jax.shard_map, mesh=mesh, in_specs=(P(), P()),
                 out_specs=(P(), P()), check_vma=False)
        def reduce_fn(g, r):
            red, r2 = gc.compressed_psum(g, r, "pod", codec="int8")
            red = jax.tree.map(lambda x: x / 2, red)  # pods held same grads
            return red, r2
        red, r2 = reduce_fn(grads, resid)
        # mean over 2 pods of identical grads == g (within int8 error)
        err = np.abs(np.asarray(red["w"]) - np.asarray(grads["w"])).max()
        assert err < 0.01, err
        print("OK")
    """)


def test_elastic_checkpoint_restore_new_mesh(tmp_path):
    run_py(f"""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
        mesh1 = jax.make_mesh((8,), ("data",),
                              axis_types=(jax.sharding.AxisType.Auto,))
        t1 = jax.device_put(tree, NamedSharding(mesh1, P("data")))
        mgr = CheckpointManager(r"{tmp_path}")
        mgr.save(5, t1, blocking=True)
        # restore onto a DIFFERENT mesh layout (elastic rescale)
        mesh2 = jax.make_mesh((2, 4), ("a", "b"),
                              axis_types=(jax.sharding.AxisType.Auto,)*2)
        sh2 = {{"w": NamedSharding(mesh2, P("b", "a"))}}
        step, back = mgr.restore(like=tree, shardings=sh2)
        assert step == 5
        np.testing.assert_array_equal(np.asarray(back["w"]),
                                      np.asarray(tree["w"]))
        print("OK")
    """)
