"""Pin device idle time on the program's own spans.

Reads the same ``.xplane.pb`` as ``bench/tracereduce.py`` and the same
window (the benchmark's ``request:<app>`` / ``between requests``
annotations), and takes three things from it:

* the program's spans: host events named in ``repro.obs``'s taxonomy
  (``service.*``, ``executor.*``, ``store.*``, ``plan.*``, ``pool.*``),
  which every ``obs.span`` writes as a profiler annotation;
* the device operations of each TPU plane's ``XLA Ops`` line, clipped to
  the window, as ``tracereduce`` takes them;
* the pipeline kind of each GAS kernel launch, which the kernel carries
  as metadata in its instruction text
  (``kernel_metadata={"pipeline":"little"}``).

Every nanosecond of the window in which a device runs nothing goes to
the innermost program span open at that time on any host thread (the
one that started last), or to ``outside program spans``; averaged over
the chips, the buckets sum to the window less ``tracereduce``'s
``busy_s``. A program without these spans or kernel kinds yields empty
tables, and the readers built on them read nothing.

    python3 -m bench.spanreduce <trace dir or .xplane.pb>
"""
from __future__ import annotations

import collections
import json
import re
import statistics
import sys
from typing import Dict, List, Optional, Tuple

from bench import tracereduce as tr

OUTSIDE = "outside program spans"
COMPILE = "executor.compile"
HOST_LOOP = ("executor.iteration", "executor.sync", "executor.converged")
_SPAN = re.compile(r"^(service|executor|store|plan|pool)\.[a-z_]+$")
_PIPELINE = re.compile(r'"pipeline"\s*:\s*"(\w+)"')

Span = Tuple[float, float, str]


def program_spans(pd) -> List[Span]:
    """The program's spans on the host planes, as (start_ns, end_ns,
    name), sorted."""
    out = []
    for plane in pd.planes:
        if plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        for line in plane.lines:
            for ev in line.events:
                if _SPAN.match(ev.name):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name))
    return sorted(out)


def kernel_kind(name: str) -> Optional[str]:
    """``"little"`` / ``"big"`` of a kernel event's instruction text, or
    None where it carries no kind."""
    m = _PIPELINE.search(name)
    return m.group(1) if m else None


def innermost(spans: List[Span]) -> List[Span]:
    """Disjoint, sorted pieces of the time line, each named by the
    innermost span open there (the latest start; on a tie the earliest
    end). Time under no span is left out."""
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    out: List[Span] = []
    active: List[Span] = []
    i = 0
    for a, b in zip(points, points[1:]):
        while i < len(spans) and spans[i][0] <= a:
            active.append(spans[i])
            i += 1
        active = [sp for sp in active if sp[1] > a]
        if not active:
            continue
        name = max(active, key=lambda sp: (sp[0], -sp[1]))[2]
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def attribute(idle: List[tr.Interval], pieces: List[Span],
              into: Dict[str, float]) -> None:
    """Add each idle interval's nanoseconds to ``into`` under the piece
    it falls in, or under ``OUTSIDE``. Both lists disjoint and sorted."""
    j = 0
    for s, e in idle:
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered = 0.0
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            lo, hi = max(s, pieces[k][0]), min(e, pieces[k][1])
            if hi > lo:
                into[pieces[k][2]] += hi - lo
                covered += hi - lo
            k += 1
        if e - s > covered:
            into[OUTSIDE] += e - s - covered


def reduce(pd, n_devices: int = 1) -> Dict:
    notes = tr.annotations(pd)
    if not notes:
        raise ValueError("no request annotations in the trace")
    lo, hi = notes[0][0], max(e for _, e, _ in notes)
    spans = program_spans(pd)
    pieces = innermost(spans)
    idle: Dict[str, float] = collections.Counter()
    kinds: Dict[str, float] = collections.Counter()
    planes = 0
    for plane in pd.planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            continue
        ops, by_kind = [], collections.defaultdict(list)
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                ops.append((s, e))
                if tr.is_kernel(ev.name):
                    kind = kernel_kind(ev.name)
                    if kind is not None:
                        by_kind[kind].append((s, e))
        if not ops:
            continue
        planes += 1
        attribute(tr.gaps(tr.union(ops), lo, hi), pieces, idle)
        for kind, ivs in by_kind.items():
            kinds[kind] += tr.total(tr.union(ivs))
    if planes == 0:
        raise ValueError("no device operations in the traced window")
    n = max(planes, n_devices)
    for _ in range(n - planes):     # a chip with no operation idles
        attribute([(lo, hi)], pieces, idle)
    per_req = [sum(min(e, re_) - max(s, rs) for s, e, name in spans
                   if name == COMPILE and s < re_ and e > rs) / 1e6
               for rs, re_, note in notes if note.startswith(tr.REQUEST)]
    return {
        "window_s": (hi - lo) / 1e9,
        "program_spans": sum(lo <= s < hi for s, _, _ in spans),
        "idle_s": {k: v / n / 1e9 for k, v in
                   sorted(idle.items(), key=lambda x: -x[1])},
        "kernel_kind_s": {k: v / n / 1e9 for k, v in sorted(kinds.items())},
        "compile_ms_per_request": per_req,
    }


def idle_pct(spans: Optional[Dict], names) -> Optional[float]:
    """Percent of the window that idles under the spans ``names``; None
    where the trace holds no program span."""
    if not spans or not spans["program_spans"] or spans["window_s"] <= 0:
        return None
    return 100.0 * sum(spans["idle_s"].get(n, 0.0)
                       for n in names) / spans["window_s"]


def kind_pct(spans: Optional[Dict], kind: str) -> Optional[float]:
    """Percent of the window under kernel launches of one pipeline kind;
    None where no launch carries a kind."""
    if not spans or not spans["kernel_kind_s"] or spans["window_s"] <= 0:
        return None
    return 100.0 * spans["kernel_kind_s"].get(kind, 0.0) / spans["window_s"]


def compile_ms(spans: Optional[Dict]) -> Optional[float]:
    """Median over requests of the ``executor.compile`` milliseconds
    inside each; None where the trace holds no program span."""
    if not spans or not spans["program_spans"]:
        return None
    per = spans["compile_ms_per_request"]
    return statistics.median(per) if per else None


if __name__ == "__main__":
    print(json.dumps(reduce(tr.load(sys.argv[1])), indent=1))
