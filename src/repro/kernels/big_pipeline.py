"""Big pipeline — sparse-partition batched GAS kernel (paper §III-B).

Sparse partitions have terrible locality: streaming whole vprops windows
would waste nearly all fetched bytes. The Vertex Loader's two tricks map
to TPU as:
  * request dedup  → offline unique-source compaction (partition.block_big)
  * latency-tolerant fetch → one XLA gather of the compact table, which
    the hardware pipelines against compute (execute/access decoupling).
Many sparse partitions are batched per invocation (the Data Router let
N_gpe Gather PEs hold N_gpe partitions; here the whole batch shares one
launch), amortising partition-switch overhead exactly as in the paper.
"""
from __future__ import annotations

from .gas_kernel import gas_pallas_call, gas_pallas_call_segmented


def big_pipeline(vprops_padded, unique_src, src_local, dst_local, weights,
                 valid, window_id, tile_id, tile_first, *, scatter_fn, mode,
                 geom, n_out_tiles, interpret):
    """Run one sparse-batch slice.

    unique_src: (n_unique_pad,) int32 global ids (the dedup'd request set).
    Returns (n_out_tiles, T) accumulator tiles.
    """
    # The Vertex Loader: a single deduplicated gather of unique sources.
    compact = vprops_padded[unique_src]
    vwin = compact.reshape(-1, geom.W)
    return gas_pallas_call(
        vwin, src_local, dst_local, weights, valid,
        window_id, tile_id, tile_first,
        scatter_fn=scatter_fn, mode=mode,
        e_blk=geom.E_BLK, w=geom.W, t=geom.T, n_out_tiles=n_out_tiles,
        interpret=interpret, pipeline="big")


def big_pipeline_packed(vprops_padded, unique_src, src_local, dst_local,
                        weights, valid, window_id, tile_id, tile_first, *,
                        scatter_fn, mode, geom, n_out_tiles, n_segments,
                        interpret):
    """Run a whole packed Big lane (all sparse entries of one lane) as
    ONE segmented grid.

    unique_src here is the lane's PACKED compaction table — the distinct
    per-work unique-source tables concatenated by ops.pack_lane, with
    each segment's window_id rebased to its table's window offset. The
    Vertex Loader gather therefore runs once per LANE per iteration
    instead of once per entry.
    Returns (n_out_tiles, T) accumulator tiles for the whole lane.
    """
    compact = vprops_padded[unique_src]
    vwin = compact.reshape(-1, geom.W)
    return gas_pallas_call_segmented(
        vwin, src_local, dst_local, weights, valid,
        window_id, tile_id, tile_first,
        scatter_fn=scatter_fn, mode=mode,
        e_blk=geom.E_BLK, w=geom.W, t=geom.T, n_out_tiles=n_out_tiles,
        n_segments=n_segments, interpret=interpret, pipeline="big")
