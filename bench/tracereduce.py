"""Reduce a profiler trace of the window to device time per layer.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes. The window is
the span of the benchmark's own host annotations (``request:<app>`` and
``between requests``). On each TPU plane the operations are the events
of the ``XLA Ops`` line, each named by its HLO instruction text
(``%gas_pallas_call.14 = f32[189,1,512]{...} custom-call(...)``); a
Pallas GAS kernel launch is a ``custom-call`` whose instruction is named
after ``gas_pallas_call``, the jitted wrapper of ``pallas_call`` in
``kernels/gas_kernel.py``. From them, clipped to the window and averaged
over the chips:

* ``busy_s``: the union of all operation intervals;
* ``kernel_s``: the union of the kernel events;
* ``other_s``: busy time outside the kernel (merge scatter, apply, the
  Big source gather, convergence reads);
* ``breakdown``: the ten kinds of operation (instruction names without
  their number) that took most device time, and the ten longest idle
  gaps, each named by the annotation the host was in.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
KERNEL = "gas_pallas_call"
REQUEST = "request"
BETWEEN = "between requests"

Interval = Tuple[float, float]


def load(path: str):
    """``ProfileData`` of an ``.xplane.pb`` file, or of the one under a
    trace directory."""
    import jax
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} .xplane.pb under {path}")
        path = found[0]
    return jax.profiler.ProfileData.from_file(path)


def op_kind(name: str) -> str:
    """``%gas_pallas_call.14 = f32[...] custom-call(...)`` ->
    ``gas_pallas_call``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+(\.clone)?$", "", head)


def is_kernel(name: str) -> bool:
    return op_kind(name) == KERNEL and " custom-call(" in name


def union(intervals: List[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of ``intervals``."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals: List[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(cover: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi) that ``cover`` (disjoint, sorted) leaves."""
    out, t = [], lo
    for s, e in cover:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def annotations(pd) -> List[Tuple[float, float, str]]:
    """The benchmark's host annotations, as (start_ns, end_ns, name)."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(REQUEST) or ev.name == BETWEEN:
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


def _label(notes, t: float) -> str:
    for s, e, name in notes:
        if s <= t < e:
            return name
    return "outside annotations"


def reduce(pd, n_devices: int = 1) -> Dict:
    notes = annotations(pd)
    if not notes:
        raise ValueError("no request annotations in the trace")
    lo, hi = notes[0][0], max(e for _, e, _ in notes)
    busy = kernel = 0.0
    op_time: Dict[str, float] = collections.Counter()
    idle: List[Tuple[float, str]] = []
    planes = 0
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        ops, kern = [], []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                ops.append((s, e))
                op_time[op_kind(ev.name)] += (e - s) / 1e9
                if is_kernel(ev.name):
                    kern.append((s, e))
        if not ops:
            continue
        planes += 1
        cover = union(ops)
        busy += total(cover)
        kernel += total(union(kern))
        idle += [((e - s) / 1e9, _label(notes, (s + e) / 2))
                 for s, e in gaps(cover, lo, hi)]
    if planes == 0:
        raise ValueError("no device operations in the traced window")
    n = max(planes, n_devices)
    busy_s, kernel_s = busy / n / 1e9, kernel / n / 1e9
    idle.sort(key=lambda x: -x[0])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_s,
        "kernel_s": kernel_s,
        "other_s": busy_s - kernel_s,
        "devices_traced": planes,
        "breakdown": {
            "device_ops": [[k, v] for k, v in
                           sorted(op_time.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[name, s] for s, name in idle[:10]],
        },
    }
