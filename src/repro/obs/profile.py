"""Pipeline utilization profiler: analytic lane footprints × measured
lane times → achieved GB/s and %-of-peak.

ReGraph's headline claim is *bandwidth* efficiency — the heterogeneous
Little/Big pipelines exist to keep every HBM channel busy — and the
comparison lens of the FPGA graph-accelerator literature (Dann et al.'s
memory-access-pattern survey, GraphScale) is achieved bandwidth as a
fraction of the device peak. This module closes that gap for the repro:

* :class:`LaneFootprint` — per-lane byte and FLOP accounting derived
  ANALYTICALLY from the packed-lane payloads (``kernels.ops`` already
  knows every array: edge slabs, deduped unique-source tables, merge
  scatter tiles). Two totals matter:

  - ``hbm_bytes``: the traffic model (what the kernel streams/gathers/
    scatters per execution) — the numerator of achieved GB/s;
  - ``total_bytes``: the jaxpr-comparable count (payload arrays +
    the full vprops operand + outputs) — validated against
    :func:`jaxpr_lane_bytes` to ±10% in ``benchmarks/bench_profile.py``.

* :func:`jaxpr_lane_bytes` — an independent byte count from the traced
  jaxpr's constvar/invar/outvar avals; the footprint's ground truth.

* :class:`UtilizationAccumulator` — thread-safe (bytes, flops, seconds)
  aggregator per pipeline kind with per-lane last samples, chained
  executor → service exactly like :class:`~repro.obs.drift.
  DriftAccumulator`, surfaced in ``Executor.stats()["utilization"]``,
  the ``regraph_lane_bandwidth_gbps`` / ``regraph_pipeline_utilization``
  Prometheus gauges, and the control-plane dashboard's per-lane bars.

The %-of-peak denominator is the running device's published HBM peak
(``perf_model.DEVICE_PEAKS``, keyed by ``device_kind``); an unknown
device kind reports no utilization — see docs/OBSERVABILITY.md for the
formulas.
"""
from __future__ import annotations

import dataclasses
import threading
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["LaneFootprint", "UtilizationAccumulator", "jaxpr_lane_bytes",
           "lane_footprint", "lane_footprints"]


@dataclasses.dataclass(frozen=True)
class LaneFootprint:
    """Analytic byte/FLOP accounting of one lane's packed payloads.

    Byte classes (summed over the lane's payloads; see
    ``kernels.ops.payload_footprint`` for the per-payload derivation):
    ``edge_bytes`` streamed edge slabs, ``index_bytes`` routing
    metadata, ``table_bytes`` deduped Big compaction tables,
    ``vertex_bytes`` property values actually read (unique sources for
    Big, touched W-windows for Little), ``tile_bytes`` the merge
    scatter traffic, ``vprops_bytes`` the full padded property operand.
    """

    lane: int
    kind: str                  # "little" | "big" | "mixed" | "idle"
    n_payloads: int
    edge_bytes: int
    index_bytes: int
    table_bytes: int
    vertex_bytes: int
    tile_bytes: int
    vprops_bytes: int
    flops: int
    padded_edges: int
    real_edges: int

    @property
    def hbm_bytes(self) -> int:
        """Modelled memory traffic of one lane execution: edge stream +
        routing metadata + gather tables + gathered/streamed vertex
        values + merge scatter tiles. This is the achieved-GB/s
        numerator (the full vprops array is NOT included — only the
        values the kernel touches are)."""
        return (self.edge_bytes + self.index_bytes + self.table_bytes
                + self.vertex_bytes + self.tile_bytes)

    @property
    def total_bytes(self) -> int:
        """Jaxpr-comparable operand+result bytes: every payload array
        (the traced constvars) + the padded vprops operand (the invar)
        + output tiles and scatter indices (the outvars). Gated within
        ±10% of :func:`jaxpr_lane_bytes` in bench_profile."""
        return (self.edge_bytes + self.index_bytes + self.table_bytes
                + self.vprops_bytes + self.tile_bytes)

    @property
    def intensity(self) -> float:
        """Arithmetic intensity (FLOPs per HBM byte) — the roofline
        x-coordinate of this lane."""
        b = self.hbm_bytes
        return self.flops / b if b else 0.0

    def as_dict(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["hbm_bytes"] = self.hbm_bytes
        d["total_bytes"] = self.total_bytes
        d["intensity"] = self.intensity
        return d


def lane_footprint(payloads: List[dict], v_pad: int,
                   lane: int = 0) -> Optional["LaneFootprint"]:
    """Build one lane's :class:`LaneFootprint` from its (packed or
    per-entry) payload dicts. Returns None for an empty lane."""
    if not payloads:
        return None
    from ..kernels import ops
    parts = [ops.payload_footprint(p) for p in payloads]
    kinds = {p["kind"] for p in parts}
    kind = kinds.pop() if len(kinds) == 1 else "mixed"
    return LaneFootprint(
        lane=lane,
        kind=kind,
        n_payloads=len(parts),
        edge_bytes=sum(p["edge_bytes"] for p in parts),
        index_bytes=sum(p["index_bytes"] for p in parts),
        table_bytes=sum(p["table_bytes"] for p in parts),
        vertex_bytes=sum(p["vertex_bytes"] for p in parts),
        tile_bytes=sum(p["tile_bytes"] for p in parts),
        vprops_bytes=int(v_pad) * 4,
        flops=sum(p["flops"] for p in parts),
        padded_edges=sum(p["padded_edges"] for p in parts),
        real_edges=sum(p["real_edges"] for p in parts),
    )


def lane_footprints(lanes: List[List[dict]],
                    v_pad: int) -> List[Optional[LaneFootprint]]:
    """Footprints for every lane of an executor's payload structure
    (None entries for fully snapped-away lanes)."""
    return [lane_footprint(lane, v_pad, lane=i)
            for i, lane in enumerate(lanes)]


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for s in shape:
        n *= int(s)
    return n * dtype.itemsize


def jaxpr_lane_bytes(executor, lane_idx: int) -> Optional[int]:
    """Ground-truth byte count of one lane execution, derived from the
    traced jaxpr: the sum of constvar (payload arrays), invar (vprops)
    and outvar (tiles + scatter indices) aval sizes of the same lane fn
    the traced run path jits. Returns None for an empty lane. Traces
    fresh on every call — benchmark/validation use, not a hot path."""
    import jax

    lanes = (executor.packed_lanes if executor.fuse_lanes
             else executor.bundle.lane_entries())
    if lane_idx >= len(lanes) or not lanes[lane_idx]:
        return None
    lane = lanes[lane_idx]

    def lane_fn(vp):
        return [executor._run_payload(p, vp) for p in lane]

    closed = jax.make_jaxpr(lane_fn)(executor.init_props())
    jaxpr = closed.jaxpr
    total = 0
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        total += _aval_bytes(v)
    for v in jaxpr.outvars:
        total += _aval_bytes(v)
    return total


class UtilizationAccumulator:
    """Thread-safe (bytes, flops, seconds) aggregator per pipeline kind.

    Mirrors :class:`~repro.obs.drift.DriftAccumulator`: executors feed
    per-lane samples (analytic footprint bytes × measured seconds), an
    executor-local accumulator forwards to the service-level one via
    ``parent=``, and :meth:`report` renders the utilization block that
    ``stats()``, the Prometheus gauges and the dashboard read.

    A sample's ``peak_bps`` (the published HBM peak of the device the
    lane ran on; 0 when unknown) rides along so %-of-peak is computed
    against the device the lane actually ran on.
    """

    # per-lane last-sample retention bound (lanes × kinds is small, but
    # a service-level accumulator sees every executor's lanes)
    _MAX_LANES = 128

    def __init__(self, parent: Optional["UtilizationAccumulator"] = None,
                 window: int = 512):
        self._parent = parent
        self._window = int(window)
        self._lock = threading.Lock()
        self._tot: Dict[str, Dict[str, float]] = {}
        self._recent: Dict[str, deque] = {}
        self._peak: Dict[str, float] = {}       # kind -> last peak_bps
        self._lanes: Dict[int, Dict[str, Any]] = {}

    def set_parent(self,
                   parent: Optional["UtilizationAccumulator"]) -> None:
        if parent is self:
            raise ValueError(
                "a UtilizationAccumulator cannot parent itself")
        self._parent = parent

    def add(self, kind: str, nbytes: float, flops: float,
            measured_s: float, peak_bps: float = 0.0,
            lane: Optional[int] = None) -> None:
        """Record one lane execution: analytic ``nbytes``/``flops``
        moved in ``measured_s`` wall seconds against a ``peak_bps``
        bandwidth ceiling (0 = unknown; utilization reported as None)."""
        nbytes = float(nbytes)
        flops = float(flops)
        measured_s = float(measured_s)
        gbps = (nbytes / measured_s / 1e9) if measured_s > 0 else 0.0
        with self._lock:
            tot = self._tot.get(kind)
            if tot is None:
                tot = self._tot[kind] = {"n": 0, "bytes": 0.0,
                                         "flops": 0.0, "seconds": 0.0}
                self._recent[kind] = deque(maxlen=self._window)
            tot["n"] += 1
            tot["bytes"] += max(0.0, nbytes)
            tot["flops"] += max(0.0, flops)
            tot["seconds"] += max(0.0, measured_s)
            if measured_s > 0:
                self._recent[kind].append(gbps)
            if peak_bps > 0:
                self._peak[kind] = float(peak_bps)
            if lane is not None:
                if (lane not in self._lanes
                        and len(self._lanes) >= self._MAX_LANES):
                    self._lanes.pop(next(iter(self._lanes)))
                self._lanes[lane] = {
                    "kind": kind, "bytes": nbytes, "flops": flops,
                    "measured_s": measured_s, "gbps": gbps,
                    "utilization": (gbps * 1e9 / peak_bps
                                    if peak_bps > 0 else None),
                }
        if self._parent is not None:
            self._parent.add(kind, nbytes, flops, measured_s,
                             peak_bps=peak_bps, lane=lane)

    def report(self) -> Dict[str, Any]:
        """``{"kinds": {kind: {...}}, "lanes": {lane: last sample},
        "peak_bandwidth_gbps": ...}``; empty sub-dicts before the first
        sample. Per-kind fields: n, bytes, seconds, gbps (aggregate
        bytes/seconds), gbps_p50 (median of recent per-sample rates),
        flops_per_s, intensity (flops/byte), utilization (gbps as a
        fraction of the last peak seen, None when no peak known)."""
        out: Dict[str, Any] = {"kinds": {}, "lanes": {}}
        with self._lock:
            peaks = [p for p in self._peak.values() if p > 0]
            out["peak_bandwidth_gbps"] = (max(peaks) / 1e9 if peaks
                                          else None)
            for kind, tot in self._tot.items():
                recent = sorted(self._recent[kind])
                secs = tot["seconds"]
                gbps = tot["bytes"] / secs / 1e9 if secs > 0 else 0.0
                peak = self._peak.get(kind, 0.0)
                entry: Dict[str, Any] = {
                    "n": int(tot["n"]),
                    "bytes": tot["bytes"],
                    "flops": tot["flops"],
                    "seconds": secs,
                    "gbps": gbps,
                    "flops_per_s": (tot["flops"] / secs
                                    if secs > 0 else 0.0),
                    "intensity": (tot["flops"] / tot["bytes"]
                                  if tot["bytes"] > 0 else 0.0),
                    "utilization": (gbps * 1e9 / peak
                                    if peak > 0 else None),
                }
                if recent:
                    entry["gbps_p50"] = recent[len(recent) // 2]
                out["kinds"][kind] = entry
            out["lanes"] = {lane: dict(s)
                            for lane, s in self._lanes.items()}
        return out

    def clear(self) -> None:
        with self._lock:
            self._tot.clear()
            self._recent.clear()
            self._peak.clear()
            self._lanes.clear()
