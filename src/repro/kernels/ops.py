"""Jit'd wrappers and dispatch for the GAS pipeline kernels.

``materialize_entry`` turns a (work, block-range) plan entry into
device-resident arrays with tile indices rebased to the slice, after
snapping the range to tile boundaries — so every destination tile is
written by exactly one entry and the engine can merge with a plain
scatter-set regardless of gather mode.

``pack_lane`` / ``pack_lanes`` build the FUSED representation: all
same-kind entries of a lane concatenated host-side into one contiguous
payload (per-segment tile ids rebased to a global tile map, Big window
ids rebased against the packed unique-source tables), uploaded in one
shot. ``run_lane`` then executes an entire lane as ONE ``pallas_call``
(one ref-path call on CPU) instead of one launch per entry, so kernel
dispatches and trace size scale with the number of lanes, not the
number of materialized plan entries.

``run_entry`` dispatches to the Pallas kernel (compiled by Mosaic on a
TPU, Pallas interpret mode on hosts without one) or the pure-jnp
reference path — identical math, used both as the CPU fast path and as
the oracle.

Device payloads keep the per-block edge fields (``_BLOCK_KEYS``) in the
kernel's 3-D block layout ``(n_blocks, 1, E_BLK)`` (see
``gas_kernel``), so the compiled kernel reads them without a relayout;
the reference path views them as ``(n_blocks, E_BLK)``.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.types import BlockedEdges, Geometry
from . import ref as ref_mod
from .big_pipeline import big_pipeline, big_pipeline_packed
from .little_pipeline import little_pipeline, little_pipeline_packed

# payload keys that hold per-block / per-tile arrays and concatenate
# along axis 0 when packing a lane
_CONCAT_KEYS = ("src_local", "dst_local", "weights", "valid",
                "window_id", "tile_id", "tile_first", "tile_idx")
# payload keys uploaded to the device by _upload_payload
_DEVICE_KEYS = _CONCAT_KEYS + ("unique_src",)
# per-block edge fields, uploaded as (n_blocks, 1, E_BLK)
_BLOCK_KEYS = ("src_local", "dst_local", "weights", "valid")


def default_path() -> str:
    """Kernel path when the caller names none: compiled Pallas on a TPU,
    the jnp reference elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def interpret_mode() -> bool:
    """Pallas interpret mode only where there is no TPU to compile for;
    on a TPU the "pallas" path always runs Mosaic-compiled kernels."""
    return jax.default_backend() != "tpu"


def device_arrays(payload: dict) -> dict:
    """The device-array fields of one payload — what a jitted iteration
    takes as arguments (the rest is static metadata)."""
    return {k: payload[k] for k in _DEVICE_KEYS
            if payload.get(k) is not None}


def snap_down(blocked: BlockedEdges, x: int) -> int:
    """Largest tile boundary <= x (x == n_blocks allowed). Applying this
    one rule to both endpoints keeps adjacent slices exactly abutting."""
    n = blocked.n_blocks
    x = max(0, min(x, n))
    if x >= n:
        return n
    tf = blocked.tile_first
    while x > 0 and tf[x] != 1:
        x -= 1
    return x


def snap_to_tiles(blocked: BlockedEdges, lo: int, hi: int):
    """Snap [lo, hi) to tile boundaries; may return an empty range, which
    the engine drops (the work is covered by the neighbouring slice)."""
    return snap_down(blocked, lo), snap_down(blocked, hi)


def _entry_np(blocked: BlockedEdges, lo: int, hi: int) -> Optional[dict]:
    """Host-side payload for one plan entry (tile-snapped). Returns None
    when the snapped range is empty. ``unique_src`` stays a reference to
    the work's shared compaction table so packing can deduplicate tables
    across entries of the same Big work."""
    lo, hi = snap_to_tiles(blocked, lo, hi)
    if hi <= lo:
        return None
    t0 = int(blocked.tile_id[lo])
    t1 = int(blocked.tile_id[hi - 1]) + 1
    tf = blocked.tile_first[lo:hi].copy()
    tf[0] = 1
    return {
        "kind": blocked.kind,
        "geom": blocked.geom,
        "n_out_tiles": t1 - t0,
        "n_blocks": hi - lo,
        "n_entries": 1,
        "src_local": blocked.src_local[lo:hi],
        "dst_local": blocked.dst_local[lo:hi],
        "weights": blocked.weights[lo:hi],
        "valid": blocked.valid[lo:hi].astype(np.int32),
        "window_id": blocked.window_id[lo:hi],
        "tile_id": blocked.tile_id[lo:hi] - t0,
        "tile_first": tf,
        "tile_idx": (blocked.tile_dst_start[t0:t1]
                     // blocked.geom.T).astype(np.int32),
        "unique_src": blocked.unique_src,
        "num_real_edges": int(blocked.valid[lo:hi].sum()),
    }


def _upload_payload(p: dict, device=None) -> dict:
    """Move a host payload's array fields to the device (jnp).
    ``device=None`` targets the default device; the sharded path passes
    each lane's OWNER device so payloads land committed where they will
    execute (committed inputs pin the jit'd lane fn to that device)."""
    out = dict(p)
    for k in _DEVICE_KEYS:
        if out.get(k) is not None:
            x = np.asarray(out[k])
            if k in _BLOCK_KEYS:
                x = x.reshape(x.shape[0], 1, x.shape[-1])
            out[k] = (jnp.asarray(x) if device is None
                      else jax.device_put(x, device))
    return out


def materialize_entry(blocked: BlockedEdges, lo: int, hi: int):
    """Build the device payload for one plan entry (tile-snapped).
    Returns None when the snapped range is empty."""
    p = _entry_np(blocked, lo, hi)
    return None if p is None else _upload_payload(p)


def materialize_lanes(plan, little_works, big_works):
    """Materialize every plan entry, preserving the plan's lane structure.
    Empty (fully snapped-away) entries are dropped — their tiles are
    covered by the neighbouring slice. Shared by the Executor and any
    harness that replays a SchedulePlan."""
    lanes = []
    for lane in plan.lanes:
        mat = []
        for e in lane:
            work = (little_works[e.work_id] if e.kind == "little"
                    else big_works[e.work_id])
            p = materialize_entry(work, e.block_lo, e.block_hi)
            if p is not None:
                mat.append(p)
        lanes.append(mat)
    return lanes


# ---------------------------------------------------------------------------
# Packed (fused) lane payloads
# ---------------------------------------------------------------------------

def _pack_group(entries: List[dict]) -> dict:
    """Concatenate same-kind host entry payloads into one packed payload.

    Per-segment rebasing:
      * ``tile_id`` shifts by the running tile count, so packed local
        tile ids are strictly increasing across segments and the global
        ``tile_idx`` map is a plain concatenation;
      * Big ``window_id`` shifts by its work's offset in the packed
        unique-source table (tables shared by split entries of the same
        work are packed once); Little window ids index raw vprops
        windows and need no rebase.
    """
    kind, geom = entries[0]["kind"], entries[0]["geom"]
    tile_off = 0
    win_parts, tid_parts = [], []
    tables: List[np.ndarray] = []        # distinct tables, first-use order
    table_off: dict = {}                 # id(table) -> window offset
    n_windows = 0
    for e in entries:
        assert e["kind"] == kind and e["geom"] == geom
        tid_parts.append(e["tile_id"] + tile_off)
        tile_off += e["n_out_tiles"]
        if kind == "big":
            tab = e["unique_src"]
            off = table_off.get(id(tab))
            if off is None:
                off = n_windows
                table_off[id(tab)] = off
                tables.append(tab)
                n_windows += tab.shape[0] // geom.W
            win_parts.append(e["window_id"] + off)
        else:
            win_parts.append(e["window_id"])
    packed = {
        "kind": kind,
        "geom": geom,
        "n_out_tiles": tile_off,
        "n_blocks": int(sum(e["n_blocks"] for e in entries)),
        "n_entries": len(entries),
        "segment_starts": np.cumsum(
            [0] + [e["n_blocks"] for e in entries])[:-1].astype(np.int64),
        "tile_id": np.concatenate(tid_parts).astype(np.int32),
        "window_id": np.concatenate(win_parts).astype(np.int32),
        "unique_src": (np.concatenate(tables) if kind == "big" else None),
        "num_real_edges": int(sum(e["num_real_edges"] for e in entries)),
    }
    for k in ("src_local", "dst_local", "weights", "valid", "tile_first",
              "tile_idx"):
        packed[k] = np.concatenate([e[k] for e in entries])
    _validate_packed(packed)
    return packed


def _validate_packed(p: dict) -> None:
    """Pack-time invariants the segmented grid relies on (host numpy —
    zero device cost). Violations mean a scheduling/packing bug, not bad
    user input, hence asserts."""
    starts = p["segment_starts"]
    # every segment opens a fresh tile -> the VMEM accumulator re-inits
    assert np.all(p["tile_first"][starts] == 1), \
        "packed segment does not start on a tile boundary"
    # local tile ids are a 0..n_out_tiles-1 relabeling, non-decreasing
    tid = p["tile_id"]
    assert tid.shape[0] == 0 or (
        tid[0] == 0 and np.all(np.diff(tid) >= 0)
        and int(tid[-1]) + 1 == p["n_out_tiles"]), \
        "packed tile ids are not a dense non-decreasing relabeling"
    # entries write disjoint output tiles -> one scatter-set merge is safe
    idx = p["tile_idx"]
    assert np.unique(idx).shape[0] == idx.shape[0], \
        "packed entries write overlapping destination tiles"


def estimate_working_set(entries: List[dict], geom: Geometry) -> int:
    """Estimated on-chip (VMEM) working set, in bytes, of packing these
    same-kind host entries into ONE payload: the full output-tile
    accumulator, the gathered unique-source table (Big; distinct tables
    counted once, matching :func:`_pack_group`'s dedup) or one streamed
    source window (Little), plus one edge-block slab. The HBM-resident
    edge stream itself is excluded — it is streamed block-by-block."""
    ws = geom.E_BLK * 16                     # src+dst+weights+valid slab
    ws += sum(e["n_out_tiles"] for e in entries) * geom.T * 4
    if entries and entries[0]["kind"] == "big":
        seen, tot = set(), 0
        for e in entries:
            tab = e["unique_src"]
            if id(tab) not in seen:
                seen.add(id(tab))
                tot += int(tab.shape[0])
        ws += tot * 4
    else:
        ws += geom.W * 4
    return int(ws)


def payload_footprint(p: dict) -> dict:
    """Byte/FLOP accounting of ONE (packed or single-entry) payload, by
    traffic class — the per-payload half of
    :class:`repro.obs.profile.LaneFootprint`. All byte counts come from
    the actual arrays (``.nbytes``), not re-derived shapes, so they are
    exact for whatever this payload holds:

    ``edge_bytes``     the streamed edge slab (src/dst/weights/valid)
    ``index_bytes``    per-block routing metadata (window/tile ids,
                       tile_first flags, the global tile_idx map)
    ``table_bytes``    the deduped unique-source compaction table
                       (Big only; :func:`_pack_group` packs shared
                       tables once and this reads the packed array)
    ``vertex_bytes``   property values the kernel actually reads: the
                       gathered unique sources (Big) or the touched
                       source windows (Little — W values per distinct
                       window id)
    ``tile_bytes``     the merge scatter traffic: output tiles plus the
                       tile_idx scatter indices
    ``flops``          one-hot gather (E·W) + router (E·T) MACs over
                       padded edges, ×2 (multiply+add) — the numerator
                       of arithmetic intensity
    """
    geom: Geometry = p["geom"]
    nb = {k: (int(p[k].nbytes) if p.get(k) is not None
              and hasattr(p[k], "nbytes") else 0)
          for k in _DEVICE_KEYS}
    edge = nb["src_local"] + nb["dst_local"] + nb["weights"] + nb["valid"]
    index = (nb["window_id"] + nb["tile_id"] + nb["tile_first"]
             + nb["tile_idx"])
    table = nb["unique_src"]
    if p["kind"] == "big":
        # vwin = vprops[unique_src]: one property per table slot
        vertex = (int(p["unique_src"].shape[0]) * 4
                  if p.get("unique_src") is not None else 0)
    else:
        # Little streams whole windows; count each touched window once
        wids = np.asarray(p["window_id"])
        vertex = int(np.unique(wids).shape[0]) * geom.W * 4
    tiles = int(p["n_out_tiles"]) * geom.T * 4 + nb["tile_idx"]
    padded_e = int(p["n_blocks"]) * geom.E_BLK
    return {
        "kind": p["kind"],
        "edge_bytes": edge,
        "index_bytes": index,
        "table_bytes": table,
        "vertex_bytes": vertex,
        "tile_bytes": tiles,
        "flops": 2 * padded_e * (geom.W + geom.T),
        "padded_edges": padded_e,
        "real_edges": int(p["num_real_edges"]),
    }


def _chunk_entries(entries: List[dict], geom: Geometry,
                   budget: float) -> List[List[dict]]:
    """Greedily split a same-kind entry list so each chunk's estimated
    working set stays under ``budget`` bytes (0/negative = no limit).
    Chunk boundaries fall on ENTRY boundaries, which are tile-snapped
    already — each chunk is a valid packed payload and the lane's
    results stay bit-identical (the merge is one scatter-set over
    globally disjoint tiles either way; only launch count changes).
    A single entry over budget still forms its own chunk — entry
    granularity is the floor (the scheduler's block splits control it)."""
    if budget <= 0 or not entries:
        return [entries] if entries else []
    chunks, cur = [], []
    for e in entries:
        if cur and estimate_working_set(cur + [e], geom) > budget:
            chunks.append(cur)
            cur = []
        cur.append(e)
    if cur:
        chunks.append(cur)
    return chunks


def _pack_lane_np(lane, little_works, big_works,
                  max_working_set: float = 0.0) -> List[dict]:
    """Host-side packed payloads for one lane: at most one per kind (a
    lane may mix Little and Big entries when there are fewer lanes than
    pipeline classes), more when ``max_working_set`` (bytes) forces
    VMEM-pressure chunking. Returns [] for a fully snapped-away lane."""
    groups = {"little": [], "big": []}
    geom = None
    for e in lane:
        work = (little_works[e.work_id] if e.kind == "little"
                else big_works[e.work_id])
        geom = work.geom
        p = _entry_np(work, e.block_lo, e.block_hi)
        if p is not None:
            groups[e.kind].append(p)
    return [_pack_group(chunk)
            for g in (groups["little"], groups["big"]) if g
            for chunk in _chunk_entries(g, geom, max_working_set)]


def pack_lane(lane, little_works, big_works,
              max_working_set: float = 0.0) -> List[dict]:
    """Pack one lane's plan entries into at most two device payloads
    (more under VMEM chunking): materialized host-side, concatenated,
    validated, uploaded once."""
    return [_upload_payload(p)
            for p in _pack_lane_np(lane, little_works, big_works,
                                   max_working_set)]


def pack_lanes(plan, little_works, big_works,
               reuse: Optional[dict] = None,
               max_working_set: float = 0.0) -> List[List[dict]]:
    """Fused counterpart of :func:`materialize_lanes`: one packed payload
    per (lane, kind) instead of one payload per entry.

    ``reuse`` maps lane index -> already-packed device payload list (the
    streaming layer seeds it with payloads carried over from a
    pre-delta bundle whose lane is structurally unchanged). Reused lanes
    skip host-side packing AND the device upload entirely; they still
    participate in the global tile-disjointness check below.

    ``max_working_set`` (bytes; 0 = off) chunks a lane's packed segments
    when their estimated VMEM working set exceeds the device spec's
    per-lane budget (``HW.vmem_lane_budget``) — bit-identical results,
    just more launches on that lane."""
    reuse = reuse or {}
    host = [None if i in reuse
            else _pack_lane_np(lane, little_works, big_works,
                               max_working_set)
            for i, lane in enumerate(plan.lanes)]
    _check_lanes_disjoint(host, reuse)
    return [reuse[i] if lane is None else [_upload_payload(p) for p in lane]
            for i, lane in enumerate(host)]


def _check_lanes_disjoint(host, reuse) -> None:
    """Global tile disjointness ACROSS lanes: merge_all's single
    scatter-set (and the sharded path's single psum-style merge) rely on
    every destination tile being written by exactly one payload —
    duplicate scatter indices have an unspecified winner in XLA.
    ``_validate_packed`` only covers within-payload; this checks across.
    Runs on host copies (reused payloads' tile_idx pulled back — tiny
    per-tile arrays), before anything new is uploaded."""
    idx = []
    for i, lane in enumerate(host):
        if lane is None:
            idx += [np.asarray(p["tile_idx"]) for p in reuse[i]]
        else:
            idx += [p["tile_idx"] for p in lane]
    all_idx = np.concatenate(idx) if idx else np.zeros(0, np.int32)
    assert np.unique(all_idx).shape[0] == all_idx.shape[0], \
        "plan assigns the same destination tile to multiple lanes"


def pack_lanes_sharded(plan, little_works, big_works, owners, devices,
                       reuse: Optional[dict] = None,
                       max_working_set: float = 0.0):
    """Sharded counterpart of :func:`pack_lanes`: pack each lane
    host-side and upload its payloads to the OWNER device
    (``devices[owners[i]]`` for lane ``i``) instead of the default one.

    ``reuse`` maps lane index -> payload list already RESIDENT on the
    right device (streaming carry-over of clean, placement-stable
    lanes); reused lanes skip packing and the transfer entirely but
    still participate in the global disjointness check.

    Returns ``(lanes, moved, bytes_moved)`` where ``moved`` counts the
    non-empty lanes actually uploaded this call and ``bytes_moved``
    their device bytes — the ``shards_moved`` accounting streaming
    updates surface.
    """
    reuse = reuse or {}
    host = [None if i in reuse
            else _pack_lane_np(lane, little_works, big_works,
                               max_working_set)
            for i, lane in enumerate(plan.lanes)]
    _check_lanes_disjoint(host, reuse)
    lanes, moved, bytes_moved = [], 0, 0
    for i, lane in enumerate(host):
        if lane is None:
            lanes.append(reuse[i])
            continue
        up = [_upload_payload(p, device=devices[owners[i]]) for p in lane]
        if up:
            moved += 1
            bytes_moved += sum(payload_nbytes(p) for p in up)
        lanes.append(up)
    return lanes, moved, bytes_moved


def payload_nbytes(payload: dict) -> int:
    """Device bytes pinned by one (entry or packed) payload."""
    total = 0
    for k in _DEVICE_KEYS:
        v = payload.get(k)
        if v is not None and hasattr(v, "nbytes"):
            total += int(v.nbytes)
    return total


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def _kernel_args(p: dict, path: str) -> tuple:
    """The seven per-block kernel operands of one payload; the reference
    path views the 3-D edge fields as (n_blocks, E_BLK)."""
    blocks = [p[k] for k in _BLOCK_KEYS]
    if path == "ref":
        blocks = [x.reshape(x.shape[0], x.shape[-1]) for x in blocks]
    return (*blocks, p["window_id"], p["tile_id"], p["tile_first"])


def run_entry(entry: dict, vprops_padded, scatter_fn, mode: str,
              path: Optional[str] = None):
    """Returns (tiles (n_out_tiles, T), tile_idx (n_out_tiles,))."""
    path = path or default_path()
    geom: Geometry = entry["geom"]
    args = _kernel_args(entry, path)
    if path == "ref":
        if entry["kind"] == "big":
            vwin = vprops_padded[entry["unique_src"]].reshape(-1, geom.W)
        else:
            vwin = vprops_padded.reshape(-1, geom.W)
        tiles = ref_mod.gas_ref(vwin, *args, scatter_fn=scatter_fn, mode=mode,
                                t=geom.T, n_out_tiles=entry["n_out_tiles"])
    else:
        interpret = interpret_mode()
        if entry["kind"] == "big":
            tiles = big_pipeline(vprops_padded, entry["unique_src"], *args,
                                 scatter_fn=scatter_fn, mode=mode, geom=geom,
                                 n_out_tiles=entry["n_out_tiles"],
                                 interpret=interpret)
        else:
            tiles = little_pipeline(vprops_padded, *args,
                                    scatter_fn=scatter_fn, mode=mode,
                                    geom=geom,
                                    n_out_tiles=entry["n_out_tiles"],
                                    interpret=interpret)
    return tiles, entry["tile_idx"]


def run_lane(packed: dict, vprops_padded, scatter_fn, mode: str,
             path: Optional[str] = None):
    """Execute one packed lane payload (all same-kind entries of a lane)
    as a single kernel launch. Same contract as :func:`run_entry`:
    returns (tiles (n_out_tiles, T), tile_idx (n_out_tiles,))."""
    path = path or default_path()
    geom: Geometry = packed["geom"]
    args = _kernel_args(packed, path)
    if path == "ref":
        if packed["kind"] == "big":
            vwin = vprops_padded[packed["unique_src"]].reshape(-1, geom.W)
        else:
            vwin = vprops_padded.reshape(-1, geom.W)
        tiles = ref_mod.gas_ref(vwin, *args, scatter_fn=scatter_fn, mode=mode,
                                t=geom.T, n_out_tiles=packed["n_out_tiles"])
    else:
        interpret = interpret_mode()
        kw = dict(scatter_fn=scatter_fn, mode=mode, geom=geom,
                  n_out_tiles=packed["n_out_tiles"],
                  n_segments=packed["n_entries"], interpret=interpret)
        if packed["kind"] == "big":
            tiles = big_pipeline_packed(vprops_padded, packed["unique_src"],
                                        *args, **kw)
        else:
            tiles = little_pipeline_packed(vprops_padded, *args, **kw)
    return tiles, packed["tile_idx"]


def merge_tiles(accum_padded, tiles, tile_idx, t: int):
    """Scatter-set entry results into the global accumulator. Tiles are
    disjoint across entries by construction (snap_to_tiles)."""
    acc = accum_padded.reshape(-1, t)
    acc = acc.at[tile_idx].set(tiles.astype(acc.dtype))
    return acc.reshape(-1)


def merge_all(accum_padded, outputs, t: int):
    """Fused merge: one tile-indexed scatter-set over ALL lanes' output
    tiles (``outputs`` is a list of (tiles, tile_idx) pairs, globally
    tile-disjoint by construction)."""
    if not outputs:
        return accum_padded
    tiles = jnp.concatenate([o[0] for o in outputs])
    idx = jnp.concatenate([o[1] for o in outputs])
    return merge_tiles(accum_padded, tiles, idx, t)
