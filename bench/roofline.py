"""The GAS kernel's operations and bytes, and the table of chip peaks.

Counts are the algorithm's minimum for one iteration, whatever
implements the gather, so the share reads the same work across layouts.
Each app's file states them (``bench/apps/<app>.py``): ``EDGE_BYTES``
per real edge (8 for src and dst as int32, 12 with a float32 weight),
``VERTEX_BYTES`` per vertex (8: its property read and written) and
``EDGE_OPS`` per real edge (2: scatter and gather).

At 2 operations per 8-12 bytes the kernel sits far under the ridge point
of a TPU v5e (197 TFLOP/s over 819 GB/s, about 240 operations per byte),
so HBM bounds it and the roofline time is the bytes term.
"""
from __future__ import annotations

import json
from pathlib import Path

from . import compare
from .loader import BenchError


def iteration_bytes(app: str, num_edges: int, num_vertices: int,
                    root: Path = compare.ROOT) -> int:
    mod = compare.app(app, root)
    return mod.EDGE_BYTES * num_edges + mod.VERTEX_BYTES * num_vertices


def iteration_ops(app: str, num_edges: int, num_vertices: int,
                  root: Path = compare.ROOT) -> int:
    return compare.app(app, root).EDGE_OPS * num_edges


def peaks(device_kind: str, path: Path = Path(__file__).with_name("peaks.json")) -> dict:
    """Published peaks of ``device_kind``; an unknown kind is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def roofline_s(app: str, num_edges: int, num_vertices: int, iterations: int,
               device_kind: str, root: Path = compare.ROOT) -> float:
    """The least time the chip could take for ``iterations`` iterations:
    the larger of bytes over peak bandwidth and operations over peak."""
    pk = peaks(device_kind)
    t_bytes = iteration_bytes(app, num_edges, num_vertices, root) \
        / pk["hbm_bytes_per_s"]
    t_ops = iteration_ops(app, num_edges, num_vertices, root) \
        / pk["flops_per_s"]
    return iterations * max(t_bytes, t_ops)
