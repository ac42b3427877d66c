#!/usr/bin/env python3
"""Run the served graph engine end to end on a TPU and check every answer.

One chip (the default):

  * generates the paper's R21 graph from ``--seed``: Graph500 R-MAT at
    scale 21, edge factor 32 (~2.1M vertices, ~67M directed edges), with
    dyadic edge weights in [1, 2) so float32 path sums are exact;
  * registers it with a default ``GraphService`` (worker thread, no
    process pool) and submits all five builtin apps under the default
    model-guided plan, then pagerank and bfs under a Little-only plan
    (``mode="fixed"``) so both pipeline kinds run; the Little-only pair
    runs at the largest scale whose Little payload fits next to the
    rest, which the script reckons on the host and prints;
  * requires compiled Pallas: the executors' path is ``"pallas"`` and
    the lowered iteration holds ``tpu_custom_call``;
  * compares every result with a plain numpy/scipy reference written
    here (no ``repro.kernels``): pagerank by pull power iteration at
    rtol 1e-4, bfs/sssp by ``scipy.sparse.csgraph`` exactly, wcc by
    min-label propagation to its fixpoint exactly, closeness
    (reachability bits of 32 sources) by csgraph BFS exactly.

``--four-chips`` runs only the sharded path: the same graph and plan
through ``ShardedExecutor`` over 4 devices against the single-device
fused executor, pagerank and bfs, asserting bit-identical results.

The last line of standard output is ``{"ok": true, "device": {...}}``
when, and only when, every check passed; any failure exits non-zero
without it. Without a TPU (e.g. ``JAX_PLATFORMS=cpu``) the script fails.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` beside this file.

    python chip_smoke.py [--seed 0] [--scale 21] [--edge-factor 32]
    python chip_smoke.py --four-chips
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
T_START = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:7.1f}s] {msg}", flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


# --------------------------------------------------------------------------
# graph and references (numpy / scipy only)
# --------------------------------------------------------------------------

def make_graph(scale: int, edge_factor: int, seed: int):
    """Graph500 R-MAT from ``repro.graphs.rmat`` plus dyadic weights
    (256 + k) / 256, k uniform in [0, 256), drawn from the seed."""
    import numpy as np

    from repro.graphs.formats import Graph, freeze
    from repro.graphs.rmat import rmat

    g = rmat(scale, edge_factor, seed=seed)
    rng = np.random.default_rng(seed + 1)
    w = ((256 + rng.integers(0, 256, g.num_edges)) / 256).astype(np.float32)
    return freeze(Graph(num_vertices=g.num_vertices, src=g.src, dst=g.dst,
                        weights=w, name=f"{g.name}-w"))


def _matrix(g, weighted: bool):
    import numpy as np
    import scipy.sparse as sp

    data = (g.weights.astype(np.float64) if weighted
            else np.ones(g.num_edges, np.float64))
    return sp.csr_matrix((data, (g.src, g.dst)),
                         shape=(g.num_vertices, g.num_vertices))


def ref_pagerank(g, iters: int, damping: float = 0.85):
    """Pull power iteration on rank / out-degree (float64)."""
    import numpy as np

    n = g.num_vertices
    outdeg = np.maximum(np.bincount(g.src, minlength=n), 1).astype(np.float64)
    at = _matrix(g, False).T.tocsr()
    p = np.full(n, 1.0 / n) / outdeg
    for _ in range(iters):
        p = ((1 - damping) / n + damping * (at @ p)) / outdeg
    return p


def _levels(dist):
    import numpy as np

    from repro.core.gas import INF
    out = np.where(np.isinf(dist), INF, dist)
    return out.astype(np.float32)


def ref_bfs(g, root: int):
    from scipy.sparse import csgraph
    return _levels(csgraph.shortest_path(_matrix(g, False), directed=True,
                                         unweighted=True, indices=root))


def ref_sssp(g, root: int):
    from scipy.sparse import csgraph
    return _levels(csgraph.dijkstra(_matrix(g, True), directed=True,
                                    indices=root))


def ref_min_label(g, labels0):
    """Fixpoint of label[v] = min(label[v], min over edges u->v of
    label[u]) — the wcc app's semantics on a directed graph."""
    import numpy as np

    order = np.argsort(g.dst, kind="stable")
    src, dst = g.src[order], g.dst[order]
    starts = np.flatnonzero(np.r_[True, dst[1:] != dst[:-1]])
    heads = dst[starts]
    lab = labels0.astype(np.float64)
    while True:
        m = np.minimum.reduceat(lab[src], starts)
        new = lab.copy()
        new[heads] = np.minimum(lab[heads], m)
        if np.array_equal(new, lab):
            return lab.astype(np.float32)
        lab = new


def ref_reach_bits(g, sources):
    """Bit b of vertex v set iff v is reachable from sources[b]."""
    import numpy as np
    from scipy.sparse import csgraph

    a = _matrix(g, False)
    bits = np.zeros(g.num_vertices, np.uint32)
    for b, s in enumerate(sources):
        reach = csgraph.breadth_first_order(a, int(s), directed=True,
                                            return_predecessors=False)
        bits[reach] |= np.uint32(1 << b)
    return bits.view(np.int32)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def setup_jax(want: int):
    """Place the compile cache, start jax, and insist on TPUs."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        import jax
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"no TPU: jax.devices()[0].platform is {devs[0].platform!r}")
    if len(devs) < want:
        fail(f"need {want} TPU devices, found {len(devs)}")
    log(f"device: {devs[0].device_kind} x{len(devs)} "
        f"(compile cache {jax.config.jax_compilation_cache_dir})")
    return devs


def payload_bytes(bundle) -> int:
    """Host reckoning of a plan's packed device payload: 16 B per padded
    edge slot, 12 B of prefetch tables per block, the Big tables."""
    works = list(bundle.little_works.values()) + list(bundle.big_works)
    e_blk = bundle.plan.geometry.E_BLK
    nb = sum(w.n_blocks for w in works)
    tables = sum(w.unique_src.nbytes for w in bundle.big_works)
    return nb * (16 * e_blk + 12) + tables


def little_payload_bytes(store) -> int:
    """Host reckoning of a Little-only plan's payload, from the
    partition stats (exact padded Little block counts)."""
    e_blk = store.geom.E_BLK
    return sum(i.blocks_little for i in store.infos) * (16 * e_blk + 12)


def graph_line(tag, store, bundle):
    padded = sum(w.num_padded_edges for w in bundle.little_works.values())
    padded += sum(w.num_padded_edges for w in bundle.big_works)
    real = store.graph.num_edges
    blocks = [sum(p["n_blocks"] for p in lane)
              for lane in bundle.packed_lanes()]
    log(f"{tag}: V={store.graph.num_vertices} E={real} "
        f"padding_efficiency={real / max(padded, 1):.4f} "
        f"payload_bytes={payload_bytes(bundle)} "
        f"largest_lane_blocks={max(blocks)} "
        f"little_lanes={bundle.plan.num_little_lanes} "
        f"big_lanes={bundle.plan.num_big_lanes}")


def check_compiled(store, config, app_name: str) -> None:
    from repro.core.gas import BUILTIN_APPS
    ex = store.executor(BUILTIN_APPS[app_name](), config)
    if ex.path != "pallas":
        fail(f"{app_name}: executor path is {ex.path!r}, not 'pallas'")
    if "tpu_custom_call" not in ex.lower_iteration().as_text():
        fail(f"{app_name}: lowered iteration has no tpu_custom_call")
    kinds = sorted({p["kind"] for lane in ex.packed_lanes for p in lane})
    log(f"{app_name}: compiled Pallas, payload kinds {kinds}")


def pick_vertices(g, seed: int, k: int):
    """k distinct vertices with out-degree > 0, drawn from the seed."""
    import numpy as np
    cand = np.flatnonzero(np.bincount(g.src, minlength=g.num_vertices))
    rng = np.random.default_rng(seed + 2)
    return [int(v) for v in rng.choice(cand, size=k, replace=False)]


def run_apps(svc, fp, g, store, config, specs, tag: str) -> list:
    """Submit every (app, kwargs) twice through the service — the first
    pass compiles, the second is warm — and check each result."""
    import numpy as np

    handles = {name: svc.submit(fingerprint=fp, app=name, app_kwargs=kw,
                                config=config)
               for name, kw in specs.items()}
    first = {name: h.result(timeout=900) for name, h in handles.items()}
    cold_ms = {n: h.metrics.t_execute_ms for n, h in handles.items()}
    warm = {name: svc.submit(fingerprint=fp, app=name, app_kwargs=kw,
                             config=config)
            for name, kw in specs.items()}
    errors = []
    for name, kw in specs.items():
        props, meta = warm[name].result(timeout=900)
        iters = meta["iterations"]
        if not np.array_equal(props, first[name][0]):
            fail(f"{tag} {name}: warm re-run differs from the first run")
        props = props[:g.num_vertices]
        if name == "pagerank":
            ref = ref_pagerank(g, iters)
            err = float(np.max(np.abs(props - ref) / np.abs(ref)))
            ok = err <= 1e-4
        else:
            if name == "bfs":
                ref = ref_bfs(g, kw["root"])
            elif name == "sssp":
                ref = ref_sssp(g, kw["root"])
            elif name == "wcc":
                ref = ref_min_label(g, store.perm)
            else:
                ref = ref_reach_bits(g, kw["sources"])
            err = float(np.count_nonzero(props != ref))
            ok = err == 0
        warm_ms = warm[name].metrics.t_execute_ms
        log(f"{tag} {name}: iterations={iters} wall_ms={warm_ms:.3f} "
            f"first_ms={cold_ms[name]:.3f} "
            f"compile_s~{(cold_ms[name] - warm_ms) / 1e3:.2f} "
            f"{'max_rel_err' if name == 'pagerank' else 'mismatches'}="
            f"{err:.3g}")
        if not ok:
            errors.append(f"{tag} {name}: error {err} against the reference")
    return errors


def one_chip(args) -> None:
    from repro.core.planner import PlanConfig
    from repro.serve_graph import GraphService
    from repro.serve_graph.fingerprint import store_key

    devs = setup_jax(1)
    limit = devs[0].memory_stats()["bytes_limit"]
    svc = GraphService()
    model = PlanConfig()

    def register(scale, ef):
        t0 = time.perf_counter()
        g = make_graph(scale, ef, args.seed)
        t1 = time.perf_counter()
        fp = svc.register(g)
        t2 = time.perf_counter()
        store = svc.cache.peek(store_key(fp, svc.default_geom,
                                         svc.default_use_dbg))
        log(f"R{scale} ef{ef}: generate_s={t1 - t0:.2f} "
            f"store_build_s={t2 - t1:.2f}")
        return g, fp, store

    # -- main graph: reckon the payload before anything is uploaded -------
    ef = args.edge_factor
    g, fp, store = register(args.scale, ef)
    t0 = time.perf_counter()
    bundle = store.plan(model)
    log(f"plan_s={time.perf_counter() - t0:.2f}")
    need = payload_bytes(bundle)
    if need > 0.6 * limit and ef > 16:
        log(f"CUT: model payload {need} B exceeds 60% of {limit} B; "
            f"edge factor {ef} -> 16")
        svc.unregister(fp)
        ef = 16
        g, fp, store = register(args.scale, ef)
        bundle = store.plan(model)
    t0 = time.perf_counter()
    bundle.packed_lanes()
    log(f"upload_s={time.perf_counter() - t0:.2f}")
    graph_line(f"R{args.scale} ef{ef} model plan", store, bundle)
    for app in ("pagerank", "bfs", "closeness"):
        check_compiled(store, model, app)

    root, *srcs = pick_vertices(g, args.seed, 33)
    specs = {"pagerank": {}, "bfs": {"root": root}, "sssp": {"root": root},
             "wcc": {}, "closeness": {"sources": tuple(srcs)}}
    errors = run_apps(svc, fp, g, store, model, specs, f"R{args.scale}")

    # -- the other pipeline kind: pagerank + bfs under a Little-only plan
    # at the largest scale that fits (Big-only when the model plan ran
    # no Big lane, as on graphs whose partitions are all dense) ---------
    ran = {p["kind"] for lane in bundle.packed_lanes() for p in lane}
    stats = devs[0].memory_stats()
    budget = stats["bytes_limit"] - stats["bytes_in_use"] - (2 << 30)
    scale, lg, lfp, lstore = args.scale, g, fp, store
    if "big" in ran:
        pair, tag = PlanConfig(mode="fixed", forced_little=model.n_lanes,
                               forced_big=0), "Little-only"
        need = little_payload_bytes(store)
        log(f"{tag} payload at R{scale}: {need} B (budget {budget} B)")
        while need > budget:
            # payload ~ 2**scale: jump to the scale that should just miss,
            # then step down one at a time to the largest that fits
            step = max(1, math.floor(math.log2(need / budget)))
            scale = max(1, scale - step)
            lg, lfp, lstore = register(scale, ef)
            need = little_payload_bytes(lstore)
            log(f"{tag} payload at R{scale}: {need} B (budget {budget} B)")
    else:
        pair, tag = PlanConfig(mode="monolithic"), "Big-only"
    log(f"{tag} pair runs at scale {scale} (edge factor {ef})")
    lbundle = lstore.plan(pair)
    lbundle.packed_lanes()
    graph_line(f"R{scale} ef{ef} {tag} plan", lstore, lbundle)
    check_compiled(lstore, pair, "pagerank")
    lroot = pick_vertices(lg, args.seed, 1)[0]
    errors += run_apps(svc, lfp, lg, lstore, pair,
                       {"pagerank": {}, "bfs": {"root": lroot}},
                       f"R{scale}-{tag}")
    kinds = ran | {p["kind"] for lane in lbundle.packed_lanes()
                   for p in lane}
    if kinds != {"little", "big"}:
        fail(f"pipeline kinds run on the chip: {sorted(kinds)}")
    svc.close()
    peak = devs[0].memory_stats().get("peak_bytes_in_use")
    log(f"peak_bytes_in_use={peak}")
    if errors:
        fail("; ".join(errors))
    finish(devs, 1)


def four_chips(args) -> None:
    import jax
    import numpy as np

    from repro.core.gas import BUILTIN_APPS
    from repro.core.planner import PlanConfig
    from repro.core.store import GraphStore

    devs = setup_jax(4)
    t0 = time.perf_counter()
    g = make_graph(args.scale, args.edge_factor, args.seed)
    t1 = time.perf_counter()
    store = GraphStore(g)
    log(f"R{args.scale} ef{args.edge_factor}: generate_s={t1 - t0:.2f} "
        f"store_build_s={time.perf_counter() - t1:.2f}")
    cfg = PlanConfig()
    root = pick_vertices(g, args.seed, 1)[0]
    for name, kw in (("pagerank", {}), ("bfs", {"root": root})):
        app = BUILTIN_APPS[name](**kw)
        single = store.executor(app, cfg)
        sharded = store.executor(app, cfg, shard=4)
        if single.path != "pallas" or sharded.path != "pallas":
            fail(f"{name}: not on the compiled Pallas path")
        t0 = time.perf_counter()
        p1, m1 = single.run()
        t1 = time.perf_counter()
        p4, m4 = sharded.run()
        t2 = time.perf_counter()
        same = (m1["iterations"] == m4["iterations"]
                and np.array_equal(p1, p4))
        log(f"{name}: iterations={m1['iterations']}/{m4['iterations']} "
            f"single_s={t1 - t0:.2f} sharded_s={t2 - t1:.2f} "
            f"bit_identical={same}")
        if not same:
            fail(f"{name}: sharded result differs from single-device")
    st = store.shard(cfg, 4).stats()
    for d, dev in enumerate(devs[:4]):
        ms = dev.memory_stats()
        log(f"device {d}: lanes={st['lanes_per_device'][d]} "
            f"payload_bytes={st['bytes_per_device'][d]} "
            f"bytes_in_use={ms['bytes_in_use']} "
            f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")
    finish(jax.devices(), 4)


def finish(devs, count: int) -> None:
    log("all checks passed")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": count}}), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=21)
    ap.add_argument("--edge-factor", type=int, default=32)
    ap.add_argument("--four-chips", action="store_true",
                    help="sharded vs single-device parity on 4 chips only")
    args = ap.parse_args()
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"the repro package is not beside this script ({ROOT})")
    sys.path.insert(0, str(ROOT / "src"))
    (four_chips if args.four_chips else one_chip)(args)


if __name__ == "__main__":
    main()
