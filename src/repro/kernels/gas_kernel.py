"""Shared Pallas TPU kernel body for the Big/Little GAS pipelines.

One grid step processes one E_BLK edge block that is homogeneous in
(source window, destination tile):

  * the source-vertex window (W props) arrives in VMEM via BlockSpec —
    Pallas grid pipelining double-buffers consecutive windows, which IS
    the Little pipeline's ping-pong buffer;
  * source properties are gathered with a one-hot (W x E_BLK) product —
    MXU work replacing per-lane random loads;
  * the update values are routed into the T-slot destination tile
    accumulator with a one-hot (T x E_BLK) product for 'sum' (MXU) or a
    masked lane reduce for 'min'/'max'/'or' (VPU) — the TPU analogue of
    the paper's butterfly Data Router;
  * blocks are sorted by tile, so output revisits are consecutive and the
    accumulator tile stays resident in VMEM between steps.

Layout (what Mosaic accepts): every per-block operand is 3-D with a
squeezed leading dimension — windows ``(n_windows, 1, W)``, edge fields
``(n_blocks, 1, E_BLK)``, output tiles ``(n_out_tiles, 1, T)`` — so each
block's last two dimensions equal the array's, and XLA stores them with
a (1, 128) tiling (no HBM padding). Edge and window values are lane
rows (1, N); the tile accumulator is a sublane column (T, 1), the shape
a lane reduce produces, and is turned into a row only when a tile is
flushed.

The three scalar-prefetch tables (window id, tile id, tile-first flag)
live in SMEM, which holds 1 MiB. A payload longer than
``MAX_GRID_BLOCKS`` blocks runs as several grids over the same operand
arrays; each grid takes the previous one's output tiles as an aliased
input and resumes a tile cut at a grid boundary from its flushed
partial, so the result is the single-grid result bit for bit.

The same body serves both pipelines; they differ only in what the window
input *is* (raw vprops windows for Little, compacted unique-source windows
for Big) — exactly the paper's division of labour.

Each launch carries its pipeline kind as kernel metadata
(``pipeline``: ``"little"`` or ``"big"``), which the compiled custom call
keeps as ``frontend_attributes={kernel_metadata={"pipeline":"big"}}``
and a profiler trace shows in the launch's ``XLA Ops`` event name. The
instruction keeps the name of its jitted wrapper (``%gas_pallas_call.N``)
either way; a ``pallas_call(name=...)`` would rename it.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.gas import GATHER_IDENTITY

INT_MODES = ("or",)
# blocks per grid: 3 int32 tables x 65536 = 768 KiB of the 1 MiB SMEM
MAX_GRID_BLOCKS = 1 << 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _lowest(dtype):
    return (np.iinfo(np.int32).min if jnp.issubdtype(dtype, jnp.integer)
            else -np.inf)


def _eye(t):
    return (jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (t, t), 1))


def _col_to_row(col, t):
    """(T, 1) -> (1, T), exactly: a diagonal select + sublane max."""
    return jnp.max(jnp.where(_eye(t), col, _lowest(col.dtype)), axis=0,
                   keepdims=True)


def _row_to_col(row, t):
    """(1, T) -> (T, 1), exactly: a diagonal select + lane max."""
    return jnp.max(jnp.where(_eye(t), row, _lowest(row.dtype)), axis=1,
                   keepdims=True)


def _or_lanes(x):
    """Bitwise OR over the lane axis of (R, N): fold 128-lane slabs,
    then a rotate-OR tree, then lane 0 -> (R, 1)."""
    acc = x[:, :128]
    for k in range(1, x.shape[1] // 128):
        acc = acc | x[:, 128 * k:128 * (k + 1)]
    shift = 64
    while shift:
        acc = acc | pltpu.roll(acc, shift, 1)
        shift //= 2
    return acc[:, :1]


def _gather_src(window, src_local, e_blk, w, is_int):
    """props[e] = window[src_local[e]] via a one-hot (W, E_BLK) product.
    window (1, W), src_local (1, E_BLK) -> (1, E_BLK). Every output sums
    one selected value and zeros, so the product is exact; int32 bitmasks
    go through the MXU as two 16-bit halves (exact in f32)."""
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (w, e_blk), 0)
              == src_local).astype(jnp.float32)

    def sel(x):
        return jnp.dot(x, onehot, precision=_HIGHEST,
                       preferred_element_type=jnp.float32)

    if is_int:
        lo = sel((window & 0xFFFF).astype(jnp.float32)).astype(jnp.int32)
        hi = sel(((window >> 16) & 0xFFFF).astype(jnp.float32))
        return (hi.astype(jnp.int32) << 16) | lo
    return sel(window.astype(jnp.float32)).astype(window.dtype)


def _route_dst(vals, dst_local, valid, mode, t, e_blk, acc_dtype):
    """tile_contrib[t] = gather-combine of vals routed to dst tile slots.
    vals, dst_local, valid (1, E_BLK) -> (T, 1)."""
    onehot = ((jax.lax.broadcasted_iota(jnp.int32, (t, e_blk), 0)
               == dst_local) & (valid != 0))
    if mode == "sum":
        return jax.lax.dot_general(
            onehot.astype(acc_dtype), vals.astype(acc_dtype),
            (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=acc_dtype)
    ident = GATHER_IDENTITY[mode]
    cand = jnp.where(onehot, vals.astype(acc_dtype),
                     jnp.asarray(ident, acc_dtype))
    if mode == "min":
        return jnp.min(cand, axis=1, keepdims=True)
    if mode == "max":
        return jnp.max(cand, axis=1, keepdims=True)
    if mode == "or":
        return _or_lanes(cand)
    raise ValueError(mode)


def make_gas_kernel(scatter_fn: Callable, mode: str, e_blk: int, w: int,
                    t: int, acc_dtype, n_blocks: int):
    """Build the kernel body for one grid of ``n_blocks`` blocks (closes
    over the Scatter UDF — the paper's accScatter runs inside the
    pipeline).

    The running tile accumulator lives in VMEM *scratch* (persists across
    grid steps — the Gather-PE destination buffer of the paper) and is
    flushed to the output block on the last edge block of each tile and
    on the grid's last block. A grid whose first block continues a tile
    resumes that tile from ``prev_ref`` (the previous grid's flush).
    """
    ident = GATHER_IDENTITY[mode]
    is_int = mode in INT_MODES

    def kernel(wid_ref, tid_ref, tfirst_ref, vwin_ref, src_ref, dst_ref,
               w_ref, valid_ref, prev_ref, out_ref, acc_ref):
        b = pl.program_id(0)
        first = tfirst_ref[b] == 1

        @pl.when(first)
        def _init():
            acc_ref[...] = jnp.full((t, 1), ident, acc_dtype)

        @pl.when(jnp.logical_and(b == 0, jnp.logical_not(first)))
        def _resume():
            acc_ref[...] = _row_to_col(prev_ref[...], t)

        props = _gather_src(vwin_ref[...], src_ref[...], e_blk, w, is_int)
        vals = scatter_fn(props, w_ref[...])
        contrib = _route_dst(vals, dst_ref[...], valid_ref[...], mode, t,
                             e_blk, acc_dtype)
        if mode == "sum":
            acc_ref[...] += contrib
        elif mode == "min":
            acc_ref[...] = jnp.minimum(acc_ref[...], contrib)
        elif mode == "max":
            acc_ref[...] = jnp.maximum(acc_ref[...], contrib)
        else:  # or
            acc_ref[...] = acc_ref[...] | contrib

        # flush on the last block of this tile (or of this grid)
        nxt = jnp.where(b + 1 < n_blocks,
                        tfirst_ref[jnp.minimum(b + 1, n_blocks - 1)], 1)

        @pl.when(nxt == 1)
        def _flush():
            out_ref[...] = _col_to_row(acc_ref[...], t)

    return kernel


def _gas_grid(prev, vwin, src_local, dst_local, weights, valid, window_id,
              tile_id, tile_first, *, lo, scatter_fn, mode, e_blk, w, t,
              interpret, pipeline):
    """One grid over blocks ``[lo, lo + len(window_id))`` of the full
    operand arrays (the edge index maps add ``lo``; only the small
    prefetch tables are sliced). ``prev`` is aliased to the output."""
    n_blocks = window_id.shape[0]
    acc_dtype = vwin.dtype
    kernel = make_gas_kernel(scatter_fn, mode, e_blk, w, t, acc_dtype,
                             n_blocks)
    edge = pl.BlockSpec((None, 1, e_blk),
                        lambda b, wid, tid, tf: (b + lo, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((None, 1, w), lambda b, wid, tid, tf: (wid[b], 0, 0)),
            edge, edge, edge, edge,
            pl.BlockSpec((None, 1, t), lambda b, wid, tid, tf: (tid[0], 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, t),
                               lambda b, wid, tid, tf: (tid[b], 0, 0)),
        scratch_shapes=[pltpu.VMEM((t, 1), acc_dtype)],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(prev.shape, acc_dtype),
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        metadata={"pipeline": pipeline} if pipeline else None,
    )(window_id, tile_id, tile_first, vwin, src_local, dst_local, weights,
      valid, prev)


@functools.partial(
    jax.jit,
    static_argnames=("scatter_fn", "mode", "e_blk", "w", "t", "n_out_tiles",
                     "interpret", "max_grid_blocks", "pipeline"),
)
def gas_pallas_call(vwin, src_local, dst_local, weights, valid,
                    window_id, tile_id, tile_first, *,
                    scatter_fn, mode, e_blk, w, t, n_out_tiles, interpret,
                    max_grid_blocks=MAX_GRID_BLOCKS, pipeline=None):
    """Run the blocked GAS kernel. All shape args static.

    vwin:      (n_windows, W) property windows (raw or compacted)
    src_local: (n_blocks, [1,] E_BLK) int32 — offsets within the block's
               window (dst_local / weights / valid alike)
    window_id, tile_id, tile_first: (n_blocks,) int32 prefetch tables
    interpret: run the kernel in Pallas interpret mode (hosts without a
               TPU) instead of compiling it with Mosaic.
    pipeline:  "little" or "big", kept as the launch's kernel metadata
               (module docstring); None leaves it empty.
    returns (n_out_tiles, T) accumulator tiles.
    """
    n_blocks = window_id.shape[0]
    acc_dtype = vwin.dtype
    vwin = vwin.reshape(-1, 1, w)
    edges = [x.reshape(n_blocks, 1, e_blk)
             for x in (src_local, dst_local, weights, valid)]
    out = jnp.full((n_out_tiles, 1, t), GATHER_IDENTITY[mode], acc_dtype)
    for lo in range(0, n_blocks, max_grid_blocks):
        hi = min(lo + max_grid_blocks, n_blocks)
        out = _gas_grid(out, vwin, *edges, window_id[lo:hi],
                        tile_id[lo:hi], tile_first[lo:hi], lo=lo,
                        scatter_fn=scatter_fn, mode=mode, e_blk=e_blk, w=w,
                        t=t, interpret=interpret, pipeline=pipeline)
    return out.reshape(n_out_tiles, t)


@functools.partial(
    jax.jit,
    static_argnames=("scatter_fn", "mode", "e_blk", "w", "t", "n_out_tiles",
                     "n_segments", "interpret", "pipeline"),
)
def gas_pallas_call_segmented(vwin, src_local, dst_local, weights, valid,
                              window_id, tile_id, tile_first, *,
                              scatter_fn, mode, e_blk, w, t, n_out_tiles,
                              n_segments, interpret, pipeline=None):
    """One grid over the concatenation of ``n_segments`` tile-disjoint
    block ranges (a packed lane) — the fused alternative to issuing one
    :func:`gas_pallas_call` per plan entry.

    The kernel body is shared with the per-entry call; the segment
    structure is carried entirely by the prefetch maps, which packing
    (``ops.pack_lane``) establishes and validates host-side:

      * each segment's first block has ``tile_first == 1``, so the VMEM
        accumulator re-initializes exactly at segment boundaries;
      * local tile ids are rebased to be strictly increasing across
        segments (globally disjoint output rows), so the flush check
        (next block's ``tile_first``) closes a segment's last tile
        precisely when the next segment begins;
      * ``window_id`` is rebased against the packed window table (raw
        vprops windows for Little; the concatenated unique-source
        compaction tables for Big).

    ``n_segments`` is static so fused and per-entry launches of the same
    shape trace separately (dispatch accounting stays honest); the body
    itself only depends on the total block count.
    """
    del n_segments  # static trace identity only — see docstring
    return gas_pallas_call(
        vwin, src_local, dst_local, weights, valid,
        window_id, tile_id, tile_first,
        scatter_fn=scatter_fn, mode=mode, e_blk=e_blk, w=w, t=t,
        n_out_tiles=n_out_tiles, interpret=interpret, pipeline=pipeline)
