"""Chip benchmark of the served graph engine: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process, in this order:

1. find the TPU (fail without one, or with fewer chips than the cell asks);
2. make the cell's graph from ``--seed`` (``bench/graphgen.py`` and
   the configuration's family file under ``bench/generators/``);
3. register it with a default ``GraphService`` (1 worker, no pool);
4. plan it with the model-guided ``PlanConfig()`` and upload the payloads;
5. check that the executors run compiled Pallas (path ``"pallas"``, the
   lowered iteration holds ``tpu_custom_call``) for every gather mode;
6. warm every app of the mix with one one-iteration request;
7. drive the window: the mix's closed-loop clients, each a loop of
   ``GraphService.submit(...).result()``, for ``--seconds`` seconds (with
   ``--trace 1`` under the profiler, each request inside a
   ``TraceAnnotation``);
8. compare a seeded sample of the window's answers with the plain
   references (each app's file under ``bench/apps/``,
   ``bench/compare.py``);
9. print the result as the last line of standard output.

Set-up phases go to standard error as they finish. The numbers compared,
each beside its limit, are the last lines of standard error and the last
key (``checks``) of the result. A run that cannot measure exits non-zero
and prints no result. The compile cache is ``$JAX_COMPILATION_CACHE_DIR``
when set, else ``.jax_cache/`` at the root of the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import compare, graphgen, reference, traffic  # noqa: E402
from bench.loader import Bench, BenchError  # noqa: E402

RESULT_TIMEOUT_S = 120.0   # one request; a hung request fails the run


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.2f}s] {msg}", file=sys.stderr,
          flush=True)


@dataclasses.dataclass
class Request:
    """One request of the window, as the client saw it."""

    app: str
    kwargs: dict
    latency_s: float
    iterations: int = 0
    t_total_ms: Optional[float] = None      # RequestMetrics (program)
    t_execute_ms: Optional[float] = None
    answer: Optional[np.ndarray] = None
    error: Optional[str] = None
    iteration_traces: Optional[int] = None  # RequestMetrics, where present
    executor_hit: Optional[bool] = None


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader (``bench/metrics/<name>.py``) reads.

    setup:    seconds of each set-up phase (generate_s, store_build_s,
              plan_s, upload_s, check_s, warmup_s) and setup_s in all.
    plan:     counts of the plan executed: num_vertices, num_edges (real
              edges), padded_edge_slots, blocks, little_lanes, big_lanes.
    requests: the window's requests in order.
    window_s: the window's length (first submit to last answer).
    trace:    ``bench/tracereduce.reduce`` of the traced window, or None.
    spans:    ``bench/spanreduce.reduce`` of the same trace, or None.
    """

    workload: str
    device_kind: str
    setup: dict
    plan: dict
    requests: List[Request]
    window_s: float
    trace: Optional[dict] = None
    spans: Optional[dict] = None


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def find_chips(chips: int):
    """The cell's devices; no TPU, or too few, is an error."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: jax.devices()[0].platform is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def plan_counts(store, bundle) -> dict:
    payloads = [p for lane in bundle.packed_lanes() for p in lane]
    e_blk = store.geom.E_BLK
    blocks = sum(p["n_blocks"] for p in payloads)
    return {"num_vertices": store.graph.num_vertices,
            "num_edges": sum(p["num_real_edges"] for p in payloads),
            "padded_edge_slots": blocks * e_blk, "blocks": blocks,
            "little_lanes": bundle.plan.num_little_lanes,
            "big_lanes": bundle.plan.num_big_lanes}


def check_pallas(store, config, apps) -> None:
    """Every gather mode of the mix runs compiled Pallas kernels."""
    from repro.core.gas import BUILTIN_APPS
    seen = set()
    for app in apps:
        made = BUILTIN_APPS[app]()
        if made.gather in seen:
            continue
        seen.add(made.gather)
        ex = store.executor(made, config)
        if ex.path != "pallas":
            raise BenchError(f"{app}: executor path is {ex.path!r}, "
                             f"not 'pallas'")
        if "tpu_custom_call" not in ex.lower_iteration().as_text():
            raise BenchError(f"{app}: lowered iteration has no "
                             f"tpu_custom_call")


def submit(svc, fp, config, app, kwargs, **kw) -> Request:
    t0 = time.perf_counter()
    h = svc.submit(fingerprint=fp, app=app, app_kwargs=kwargs, config=config,
                   **kw)
    try:
        props, meta = h.result(timeout=RESULT_TIMEOUT_S)
    except Exception as e:  # the request failed; the run goes on
        return Request(app, kwargs, time.perf_counter() - t0,
                       error=f"{type(e).__name__}: {e}")
    lat = time.perf_counter() - t0
    m = h.metrics
    return Request(app, kwargs, lat, meta["iterations"], m.t_total_ms,
                   m.t_execute_ms, np.asarray(props),
                   iteration_traces=getattr(m, "iteration_traces", None),
                   executor_hit=getattr(m, "executor_hit", None))


@contextlib.contextmanager
def count_compiles():
    """Count, while open, what JAX compiles or fetches from its
    persistent cache, and the seconds it spends tracing and lowering."""
    import jax.monitoring as mon
    counts = {"backend_compiles": 0, "backend_compile_s": 0.0,
              "cache_hits": 0, "cache_misses": 0, "trace_lower_s": 0.0}

    def event(name, **_):
        if name.endswith("/cache_hits"):
            counts["cache_hits"] += 1
        elif name.endswith("/cache_misses"):
            counts["cache_misses"] += 1

    def duration(name, secs, **_):
        if name.endswith("/backend_compile_duration"):
            counts["backend_compiles"] += 1
            counts["backend_compile_s"] += secs
        elif name.endswith(("/jaxpr_trace_duration",
                            "/jaxpr_to_mlir_module_duration")):
            counts["trace_lower_s"] += secs

    mon.register_event_listener(event)
    mon.register_event_duration_secs_listener(duration)
    try:
        yield counts
    finally:
        mon.unregister_event_listener(event)
        mon.unregister_event_duration_listener(duration)


def drive(svc, fp, config, requests, seconds: float, annotate,
          clients: int = 1):
    """Closed loop: each of ``clients`` clients sends the next request of
    the shared stream when its last is answered, until ``seconds`` have
    passed; the window closes with the last answer."""
    done: List[Request] = []
    lock = threading.Lock()
    t0 = time.perf_counter()
    deadline = t0 + seconds

    def client():
        while time.perf_counter() < deadline:
            with lock:
                app, kwargs = next(requests)
            with annotate(f"request:{app}"):
                r = submit(svc, fp, config, app, kwargs)
            with annotate("between requests"), lock:
                done.append(r)

    if clients == 1:
        client()
    else:
        threads = [threading.Thread(target=client, name=f"client-{i}")
                   for i in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return done, time.perf_counter() - t0


def pick_checked(reqs: List[Request], seed: int, per_app: dict) -> List[int]:
    """Indices of the answers compared: for each app its request with the
    most iterations plus a sample drawn from the seed, up to
    ``per_app[app]`` (all where the app is not listed)."""
    rng = np.random.default_rng([int(seed), 3])
    out = []
    for app in sorted({r.app for r in reqs}):
        idx = [i for i, r in enumerate(reqs) if r.app == app and r.error is None]
        k = per_app.get(app, len(idx))
        if len(idx) <= k:
            out += idx
            continue
        first = max(idx, key=lambda i: reqs[i].iterations)
        rest = [i for i in idx if i != first]
        out += [first] + [int(i) for i in rng.choice(rest, k - 1, replace=False)]
    return sorted(out)


def check_answers(g, reqs, idx, root: Path = ROOT):
    """``(app, number)`` readings of the compared answers."""
    cache: dict = {}
    return [(reqs[i].app, compare.reading(g, reqs[i].app, reqs[i].kwargs,
                                          reqs[i].answer, cache, root))
            for i in idx]


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def per_app_ms(reqs: List[Request]) -> dict:
    out: dict = {}
    for r in reqs:
        out.setdefault(r.app, []).append(r.latency_s * 1e3)
    return out


# --------------------------------------------------------------------------
# one run
# --------------------------------------------------------------------------

def run_cell(bench: Bench, workload: str, seed: int, seconds: float,
             trace: bool, *, find=find_chips, require_pallas=True,
             t_start: float = T_START) -> dict:
    from repro.core.planner import PlanConfig
    from repro.serve_graph import GraphService
    from repro.serve_graph.fingerprint import store_key

    wl = bench.workload(workload)
    cfg = bench.config(wl["config"])
    graphgen.family(cfg, bench.root)
    mix = bench.traffic(wl["traffic"])
    traffic.validate(mix)
    metric_entries = bench.metrics(workload, trace)
    readers = {m["name"]: bench.reader(m["name"]) for m in metric_entries} \
        if trace else {}
    devs = find(wl["chips"])
    setup = {}

    t = time.perf_counter()
    g = graphgen.make_graph(cfg, seed, bench.root)
    setup["generate_s"] = time.perf_counter() - t
    log(f"generate: {cfg['name']} V={g.num_vertices} E={g.num_edges} "
        f"({setup['generate_s']:.2f}s)")

    svc = GraphService()
    try:
        t = time.perf_counter()
        fp = svc.register(g)
        setup["store_build_s"] = time.perf_counter() - t
        store = svc.cache.peek(store_key(fp, svc.default_geom,
                                         svc.default_use_dbg))
        config = PlanConfig()
        t = time.perf_counter()
        bundle = store.plan(config)
        setup["plan_s"] = time.perf_counter() - t
        t = time.perf_counter()
        bundle.packed_lanes()
        setup["upload_s"] = time.perf_counter() - t
        plan = plan_counts(store, bundle)
        log(f"store {setup['store_build_s']:.2f}s, plan "
            f"{setup['plan_s']:.2f}s, upload {setup['upload_s']:.2f}s: "
            f"{plan}")
        apps = [a["app"] for a in mix["apps"]]
        t = time.perf_counter()
        if require_pallas:
            check_pallas(store, config, apps)
        setup["check_s"] = time.perf_counter() - t

        cand = traffic.candidates(g.num_vertices, g.src)
        t = time.perf_counter()
        for app, kwargs in traffic.warmup(mix, cand, seed):
            r = submit(svc, fp, config, app, kwargs, max_iters=1)
            if r.error:
                raise BenchError(f"warm-up {app} failed: {r.error}")
        setup["warmup_s"] = time.perf_counter() - t
        setup["setup_s"] = time.perf_counter() - t_start
        log(f"checks {setup['check_s']:.2f}s, warm-up "
            f"{setup['warmup_s']:.2f}s; set-up {setup['setup_s']:.2f}s")

        requests = traffic.stream(mix, cand, seed)
        tdir = None
        if trace:
            import jax
            tdir = tempfile.mkdtemp(prefix="bench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
            annotate = jax.profiler.TraceAnnotation
        else:
            annotate = contextlib.nullcontext
        try:
            with count_compiles() as compiles:
                reqs, window_s = drive(svc, fp, config, requests, seconds,
                                       annotate, mix["clients"])
        finally:
            if trace:
                jax.profiler.stop_trace()
        log(f"in the window: {compiles}")
        peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use")
    finally:
        svc.close()
    del svc, store, bundle
    gc.collect()

    summary = spans = None
    if trace:
        from bench import spanreduce, tracereduce
        t = time.perf_counter()
        pd = tracereduce.load(tdir)
        summary = tracereduce.reduce(pd, n_devices=len(devs))
        spans = spanreduce.reduce(pd, len(devs))
        del pd
        shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.2f}s: "
            f"{ {k: v for k, v in summary.items() if k != 'breakdown'} }; "
            f"idle by innermost span {spans['idle_s']}")

    ok = [r for r in reqs if r.error is None]
    failed = len(reqs) - len(ok)
    for r in reqs:
        if r.error:
            log(f"request {r.app} {r.kwargs} failed: {r.error}")
    log(f"window {window_s:.2f}s: {len(reqs)} requests, {failed} failed; "
        + ", ".join(f"{a}={sum(r.app == a for r in reqs)}"
                    for a in sorted({r.app for r in reqs}))
        + f"; pagerank iterations "
        f"{sorted({r.iterations for r in ok if r.app == 'pagerank'})}; "
        "median ms " + ", ".join(
            f"{a}={percentile(ms, 50):.1f}" for a, ms in sorted(
                per_app_ms(ok).items())))

    # the comparison, after the window and with the program's state freed
    t = time.perf_counter()
    ref_g = reference.Graph(g.num_vertices, g.src, g.dst, g.weights)
    idx = pick_checked(reqs, seed, mix.get("check_per_app", {}))
    values = compare.worst(check_answers(ref_g, reqs, idx, bench.root),
                           bench.root)
    correct, checks = compare.verdict(values, failed, bench.root)
    log(f"compared {len(idx)} answers in {time.perf_counter() - t:.2f}s")

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    metrics = {}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        record = RunRecord(workload, devs[0].device_kind, setup, plan, reqs,
                           window_s, summary, spans)
        for m in metric_entries:
            v = readers[m["name"]](record)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        lat_ms = [r.latency_s * 1e3 for r in ok]
        e2e = {"requests_per_s": len(ok) / window_s,
               "latency_p50_ms": percentile(lat_ms, 50) if ok else None,
               "latency_p95_ms": percentile(lat_ms, 95) if ok else None,
               "setup_s": setup["setup_s"]}
        for m in metric_entries:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": len(reqs),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = summary["breakdown"]
    result["checks"] = checks
    return result


def setup_jax() -> None:
    """Persistent compile cache at a fixed path inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` names one; every program is cached."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise BenchError(f"the program (src/repro) is not in {ROOT}")
        bench = Bench(ROOT)
        setup_jax()
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except BenchError as e:
        print(f"bench: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
