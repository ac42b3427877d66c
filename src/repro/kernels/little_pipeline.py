"""Little pipeline — dense-partition GAS kernel (paper §III-C).

Dense partitions touch most source windows, so the kernel streams raw
vprops windows HBM→VMEM via BlockSpec (Pallas grid pipelining
double-buffers consecutive steps: the ping-pong buffer). No dedup, no
compaction — the paper's argument that locality makes those techniques
dead weight for dense partitions. The "jump access mechanism" (skipping
unread buffer ranges) falls out of the window_id prefetch map: untouched
windows are never fetched.
"""
from __future__ import annotations

from .gas_kernel import gas_pallas_call, gas_pallas_call_segmented


def little_pipeline(vprops_padded, src_local, dst_local, weights, valid,
                    window_id, tile_id, tile_first, *, scatter_fn, mode,
                    geom, n_out_tiles, interpret):
    """Run one dense-partition slice.

    vprops_padded: (V_pad,) current vertex properties, V_pad % W == 0.
    Blocked arrays as produced by partition.block_little (possibly a
    tile-aligned slice rebased by ops.materialize_entry).
    Returns (n_out_tiles, T) accumulator tiles.
    """
    vwin = vprops_padded.reshape(-1, geom.W)
    return gas_pallas_call(
        vwin, src_local, dst_local, weights, valid,
        window_id, tile_id, tile_first,
        scatter_fn=scatter_fn, mode=mode,
        e_blk=geom.E_BLK, w=geom.W, t=geom.T, n_out_tiles=n_out_tiles,
        interpret=interpret, pipeline="little")


def little_pipeline_packed(vprops_padded, src_local, dst_local, weights,
                           valid, window_id, tile_id, tile_first, *,
                           scatter_fn, mode, geom, n_out_tiles, n_segments,
                           interpret):
    """Run a whole packed Little lane (all dense entries of one lane,
    concatenated by ops.pack_lane) as ONE segmented grid. Window ids
    index the raw vprops windows, so packing needs no rebase here —
    every segment streams from the same source array.
    Returns (n_out_tiles, T) accumulator tiles for the whole lane.
    """
    vwin = vprops_padded.reshape(-1, geom.W)
    return gas_pallas_call_segmented(
        vwin, src_local, dst_local, weights, valid,
        window_id, tile_id, tile_first,
        scatter_fn=scatter_fn, mode=mode,
        e_blk=geom.E_BLK, w=geom.W, t=geom.T, n_out_tiles=n_out_tiles,
        n_segments=n_segments, interpret=interpret, pipeline="little")
