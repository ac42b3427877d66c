"""Per-kernel allclose validation: Pallas (interpret=True) vs ref.py
oracle, swept over shapes/dtypes/modes with hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: skip, not a collection error
from hypothesis import given, settings, strategies as st

from repro.core import partition as part
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.kernels import ops, ref as ref_mod
from repro.kernels.gas_kernel import gas_pallas_call

GEOM = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)


def _entry(graph, kind, geom=GEOM, pid=0):
    infos, edges = part.partition_graph(graph, geom)
    infos = [i for i in infos if i.num_edges > 0]
    if kind == "little":
        work = part.block_little(edges, infos[pid % len(infos)], geom)
    else:
        work = part.block_big(edges, infos[:2], geom)
    return ops.materialize_entry(work, 0, work.n_blocks)


@pytest.mark.parametrize("kind", ["little", "big"])
@pytest.mark.parametrize("mode", ["sum", "min", "max"])
def test_pallas_matches_ref_float(kind, mode, tiny_graph, rng):
    entry = _entry(tiny_graph, kind)
    V_pad = part.padded_num_vertices(tiny_graph.num_vertices, GEOM)
    vprops = jnp.asarray(rng.rand(V_pad).astype(np.float32))
    sc = (lambda p, w: p + w) if mode != "sum" else (lambda p, w: p)
    tr, _ = ops.run_entry(entry, vprops, sc, mode, "ref")
    tp, _ = ops.run_entry(entry, vprops, sc, mode, "pallas")
    np.testing.assert_allclose(np.asarray(tr), np.asarray(tp),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kind", ["little", "big"])
def test_pallas_matches_ref_int_or(kind, tiny_graph, rng):
    entry = _entry(tiny_graph, kind)
    V_pad = part.padded_num_vertices(tiny_graph.num_vertices, GEOM)
    vprops = jnp.asarray(rng.randint(0, 2**30, V_pad).astype(np.int32))
    tr, _ = ops.run_entry(entry, vprops, lambda p, w: p, "or", "ref")
    tp, _ = ops.run_entry(entry, vprops, lambda p, w: p, "or", "pallas")
    assert np.array_equal(np.asarray(tr), np.asarray(tp))


def test_slice_merge_equals_full(tiny_graph, rng):
    """Tile-snapped slices merged = whole-work result."""
    geom = GEOM
    infos, edges = part.partition_graph(tiny_graph, geom)
    infos = [i for i in infos if i.num_edges > 0]
    work = part.block_little(edges, infos[0], geom)
    V_pad = part.padded_num_vertices(tiny_graph.num_vertices, geom)
    vprops = jnp.asarray(rng.rand(V_pad).astype(np.float32))
    sc = lambda p, w: p
    full_entry = ops.materialize_entry(work, 0, work.n_blocks)
    t_full, idx_full = ops.run_entry(full_entry, vprops, sc, "sum", "ref")
    accum_full = ops.merge_tiles(jnp.zeros(V_pad), t_full, idx_full, geom.T)
    accum_sliced = jnp.zeros(V_pad)
    mid = work.n_blocks // 2
    for lo, hi in [(0, mid), (mid, work.n_blocks)]:
        e = ops.materialize_entry(work, lo, hi)
        if e is None:
            continue
        t, idx = ops.run_entry(e, vprops, sc, "sum", "ref")
        accum_sliced = ops.merge_tiles(accum_sliced, t, idx, geom.T)
    np.testing.assert_allclose(np.asarray(accum_full),
                               np.asarray(accum_sliced), rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(scale=st.integers(6, 8), ef=st.integers(2, 8),
       seed=st.integers(0, 99), mode=st.sampled_from(["sum", "min"]))
def test_property_kernel_vs_edge_oracle(scale, ef, seed, mode):
    """End-to-end property: blocked kernel == straight edge-list oracle
    for arbitrary graphs."""
    g = rmat(scale, ef, seed=seed)
    geom = Geometry(U=512, W=512, T=512, E_BLK=128, big_batch=2)
    infos, edges = part.partition_graph(g, geom)
    V_pad = part.padded_num_vertices(g.num_vertices, geom)
    rs = np.random.RandomState(seed)
    vprops = jnp.asarray(rs.rand(V_pad).astype(np.float32))
    sc = lambda p, w: p
    from repro.core.gas import GATHER_IDENTITY
    accum = jnp.full((V_pad,), GATHER_IDENTITY[mode], jnp.float32)
    for i in infos:
        if i.num_edges == 0:
            continue
        work = part.block_little(edges, i, geom)
        e = ops.materialize_entry(work, 0, work.n_blocks)
        t, idx = ops.run_entry(e, vprops, sc, mode, "ref")
        accum = ops.merge_tiles(accum, t, idx, geom.T)
    oracle = ref_mod.edge_ref(jnp.asarray(g.src), jnp.asarray(g.dst),
                              jnp.zeros(g.num_edges), vprops, sc, mode,
                              V_pad)
    np.testing.assert_allclose(np.asarray(accum), np.asarray(oracle),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("e_blk,w,t", [(128, 512, 512), (256, 512, 512),
                                       (128, 1024, 512), (128, 512, 1024)])
def test_kernel_geometry_sweep(e_blk, w, t, rng):
    """Direct pallas_call across block geometries."""
    n_blocks, n_win, n_tiles = 5, 3, 2
    vwin = jnp.asarray(rng.rand(n_win, w).astype(np.float32))
    src = jnp.asarray(rng.randint(0, w, (n_blocks, e_blk)).astype(np.int32))
    dst = jnp.asarray(rng.randint(0, t, (n_blocks, e_blk)).astype(np.int32))
    wts = jnp.asarray(rng.rand(n_blocks, e_blk).astype(np.float32))
    valid = jnp.asarray(rng.rand(n_blocks, e_blk) < 0.9, jnp.int32)
    wid = jnp.asarray(rng.randint(0, n_win, n_blocks).astype(np.int32))
    # every output tile must be touched (materialize_entry guarantees it)
    tid = jnp.asarray(np.sort(np.concatenate(
        [np.arange(n_tiles), rng.randint(0, n_tiles, n_blocks - n_tiles)]))
        .astype(np.int32))
    tf = np.ones(n_blocks, np.int32)
    tf[1:] = (np.asarray(tid)[1:] != np.asarray(tid)[:-1])
    tf = jnp.asarray(tf)
    sc = lambda p, wt: p * 2 + wt
    kw = dict(scatter_fn=sc, mode="sum", e_blk=e_blk, w=w, t=t,
              n_out_tiles=n_tiles)
    out_p = gas_pallas_call(vwin, src, dst, wts, valid, wid, tid, tf,
                            **kw, interpret=True)
    out_r = ref_mod.gas_ref(vwin, src, dst, wts, valid, wid, tid, tf,
                            scatter_fn=sc, mode="sum", t=t,
                            n_out_tiles=n_tiles)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mode", ["sum", "min", "or"])
def test_chunked_grids_bit_identical(mode, rng):
    """A payload split into several grids (the SMEM prefetch-table bound)
    resumes tiles cut at grid boundaries from the previous grid's flush:
    bit-identical to a single grid, including tiles spanning 3 grids."""
    e_blk, w, t, n_blocks, n_win, n_tiles = 128, 512, 512, 11, 3, 3
    if mode == "or":
        vwin = rng.randint(-2**31, 2**31 - 1, (n_win, w), dtype=np.int64)
        vwin = jnp.asarray(vwin.astype(np.int32))
    else:
        vwin = jnp.asarray(rng.rand(n_win, w).astype(np.float32))
    src = jnp.asarray(rng.randint(0, w, (n_blocks, e_blk)).astype(np.int32))
    dst = jnp.asarray(rng.randint(0, 40, (n_blocks, e_blk)).astype(np.int32))
    wts = jnp.asarray(rng.rand(n_blocks, e_blk).astype(np.float32))
    valid = jnp.asarray(rng.rand(n_blocks, e_blk) < 0.9, jnp.int32)
    wid = jnp.asarray(rng.randint(0, n_win, n_blocks).astype(np.int32))
    tid = np.array([0, 0, 0, 0, 0, 0, 1, 1, 2, 2, 2], np.int32)
    tf = np.ones(n_blocks, np.int32)
    tf[1:] = tid[1:] != tid[:-1]
    args = (vwin, src, dst, wts, valid, wid, jnp.asarray(tid),
            jnp.asarray(tf))
    sc = (lambda p, wt: p) if mode != "min" else (lambda p, wt: p + wt)
    kw = dict(scatter_fn=sc, mode=mode, e_blk=e_blk, w=w, t=t,
              n_out_tiles=n_tiles, interpret=True)
    one = np.asarray(gas_pallas_call(*args, **kw))
    for chunk in (1, 2, 4):
        got = np.asarray(gas_pallas_call(*args, **kw, max_grid_blocks=chunk))
        assert got.tobytes() == one.tobytes(), chunk
