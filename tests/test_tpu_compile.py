"""Ahead-of-time compiles of the GAS kernels for a described TPU v5e.

Nothing here runs on a chip: the TPU compiler that ships with jaxlib
compiles for a topology that is described, not attached, and refuses
what Mosaic or the chip's memories would refuse (block layouts, SMEM
prefetch tables, VMEM). The topology is described inside a module
fixture, never at import, so every test worker collects the same tests
and only the worker running this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import spanreduce, tracereduce
from repro.core.gas import BUILTIN_APPS
from repro.core.store import GraphStore
from repro.core.types import Geometry
from repro.graphs.rmat import rmat
from repro.kernels import ops
from repro.kernels.big_pipeline import big_pipeline_packed
from repro.kernels.gas_kernel import MAX_GRID_BLOCKS
from repro.kernels.little_pipeline import little_pipeline_packed

GEOM = Geometry()
SCATTER = {"sum": lambda p, w: p, "min": lambda p, w: p + w,
           "or": lambda p, w: p}
# largest lane payload of rmat(21, 16) under PlanConfig(n_lanes=8)
R21_LANE_BLOCKS = 123_000


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _lane_shapes(one_chip, kind, mode, n_blocks, n_windows):
    """Shape structs of one packed lane's kernel operands."""
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    dt = jnp.int32 if mode == "or" else jnp.float32
    e = GEOM.E_BLK
    vp = s((n_windows * GEOM.W,), dt)
    tables = (s((n_windows * GEOM.W,), jnp.int32),) if kind == "big" else ()
    blocks = tuple(s((n_blocks, 1, e), d) for d in
                   (jnp.int32, jnp.int32, jnp.float32, jnp.int32))
    prefetch = tuple(s((n_blocks,), jnp.int32) for _ in range(3))
    return (vp,) + tables + blocks + prefetch


def _compile_lane(one_chip, kind, mode, n_blocks, n_windows, n_out_tiles):
    run = big_pipeline_packed if kind == "big" else little_pipeline_packed

    def lane(*args):
        return run(*args, scatter_fn=SCATTER[mode], mode=mode, geom=GEOM,
                   n_out_tiles=n_out_tiles, n_segments=1, interpret=False)

    shapes = _lane_shapes(one_chip, kind, mode, n_blocks, n_windows)
    return jax.jit(lane).lower(*shapes).compile()


@pytest.mark.parametrize("kind", ["little", "big"])
@pytest.mark.parametrize("mode", ["sum", "min", "or"])
def test_packed_lane_kernel_compiles(one_chip, kind, mode):
    compiled = _compile_lane(one_chip, kind, mode, n_blocks=4096,
                             n_windows=64, n_out_tiles=256)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kind", ["little", "big"])
def test_launches_carry_their_pipeline_kind(one_chip, kind):
    """Each launch names its pipeline in the kernel metadata, which a
    profiler trace keeps in the ``XLA Ops`` event name (the instruction
    text before ``metadata=``), and is still the kernel to the trace
    reduction."""
    text = _compile_lane(one_chip, kind, "min", n_blocks=512, n_windows=16,
                         n_out_tiles=64).as_text()
    launches = re.findall(r"%gas_pallas_call\S* = .*?(?=, metadata=)", text,
                          re.S)
    assert launches
    for name in launches:
        assert tracereduce.is_kernel(name)
        assert tracereduce.op_kind(name) == tracereduce.KERNEL
        assert spanreduce.kernel_kind(name) == kind


def test_r21_lane_block_count_compiles(one_chip):
    """A lane longer than one grid's SMEM tables runs as several grids."""
    compiled = _compile_lane(one_chip, "big", "min", R21_LANE_BLOCKS,
                             n_windows=4096, n_out_tiles=4096)
    grids = -(-R21_LANE_BLOCKS // MAX_GRID_BLOCKS)
    assert grids > 1
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') \
        == grids


def test_fused_iteration_compiles(one_chip, monkeypatch):
    """The executor's fused iteration (all lanes, merge, apply) with its
    payload arrays as arguments, compiled for the chip."""
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    store = GraphStore(rmat(12, 8, seed=1), GEOM)
    ex = store.executor(BUILTIN_APPS["pagerank"](), path="pallas")

    def shape(x):
        x = jnp.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(shape, (ex.init_props(), ex.aux, 0, ex._arrays))
    compiled = jax.jit(ex._iteration_fn()).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
