"""Graph families, undirected graphs and apps join the benchmark as files.

A family file, an app file, a configuration and a traffic file added to
a benchmark tree run through ``run.run_cell`` with no edit to a file
that is there; what the files cannot stand for is refused before
set-up. The two accepted configurations keep their edges, and a small
seeded comparison and the control keep their numbers, to the byte, as
the checkout before the files existed made them
(``fixtures/edges.sha256.json``, ``fixtures/small_run.checks.json``).
"""
import hashlib
import itertools
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import compare, control, graphgen, reference, roofline, run, \
    traffic
from bench.loader import Bench, BenchError

from .conftest import ROOT, TINY_CELL, make_root

FIXTURES = Path(__file__).with_name("fixtures")
EDGES = json.loads((FIXTURES / "edges.sha256.json").read_text())

GRID = '''"""A side x side grid, each vertex linked to its right and lower
neighbour: a graph of high diameter and no skew."""
import jax.numpy as jnp

from bench import graphgen

KEYS = ("side", "structure_seed")


def num_vertices(config):
    return config["side"] ** 2


def _draw(key, n, m, side):
    v = jnp.arange(n, dtype=jnp.int32)
    right = jnp.where(v % side < side - 1, v + 1, v)    # v: a self-loop
    down = jnp.where(v < n - side, v + side, v)
    return jnp.concatenate([v, v]), jnp.concatenate([right, down])


def edges(config, seed):
    n = num_vertices(config)
    return graphgen.seeded_edges(_draw, n, 2 * n, config["structure_seed"],
                                 seed, (config["side"],))
'''

HOPS = '''"""hops: bfs levels under a name of its own."""
import numpy as np

from bench import reference

NUMBER = "hops_mismatches"
LIMIT = 0
EDGE_BYTES, VERTEX_BYTES, EDGE_OPS = 8, 8, 2


def answer(g, kwargs):
    return reference.bfs(g, kwargs["root"])


def measure(served, ref):
    return np.count_nonzero(served != ref)


def control(g, kwargs, dtype="bfloat16"):
    return reference.control_fixpoint(g, kwargs["root"], level=True,
                                      dtype=dtype)
'''


def _cpu(chips):
    import jax
    return jax.devices()[:chips]


def _no_chip(chips):
    raise AssertionError("the run looked for a chip before it refused")


def _run(root, cell, seed=2**33 + 9, find=_cpu):
    return run.run_cell(Bench(root), cell, seed, 1.0, False, find=find,
                        require_pallas=False, t_start=time.perf_counter())


def _add_cell(root, config, mix, cell="new.cell"):
    """Write a configuration and a mix as files, and their entries."""
    (root / f"bench/configs/{config['name']}.json").write_text(
        json.dumps(config))
    (root / f"bench/traffic/{cell}.json").write_text(json.dumps(mix))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": config["name"], "source": "test",
                            "file": f"bench/configs/{config['name']}.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": cell, "config": config["name"],
                              "traffic": cell, "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return cell


def _tiny_config(root, **changes):
    cfg = json.loads((root / "bench/configs/tiny.json").read_text())
    cfg.update(changes)
    return cfg


@pytest.fixture
def hops_app(monkeypatch):
    """The program serves bfs under the name ``hops`` too."""
    from repro.core import gas
    monkeypatch.setitem(gas.BUILTIN_APPS, "hops", gas.make_bfs)


def test_new_family_app_and_undirected_config_join_by_files(tiny_root,
                                                             hops_app):
    before = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()}
    (tiny_root / "bench/generators/grid.py").write_text(GRID)
    (tiny_root / "bench/apps/hops.py").write_text(HOPS)
    cell = _add_cell(tiny_root, {
        "name": "grid-20", "source": "test", "generator": "grid",
        "side": 20, "structure_seed": 1, "directed": False,
        "precision": "float32"}, {
        "loop": "closed", "clients": 1, "apps": [
            {"app": "hops", "per_deck": 2, "params": {"root": "vertex"}},
            {"app": "wcc", "per_deck": 1}]})
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data
    res = _run(tiny_root, cell)
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"hops_mismatches", "wcc_violations",
                                  "failed_requests"}
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert roofline.iteration_bytes("hops", 10, 4, tiny_root) == 112


def test_an_app_file_that_fails_its_answers_makes_the_run_not_correct(
        tiny_root, hops_app):
    """The new app's own file decides: with its reference off by one
    level the same run reads its number above the limit."""
    (tiny_root / "bench/apps/hops.py").write_text(HOPS.replace(
        "return reference.bfs(g, kwargs[\"root\"])",
        "return reference.bfs(g, kwargs[\"root\"]) + 1"))
    cell = _add_cell(tiny_root, _tiny_config(tiny_root, name="tiny2"), {
        "loop": "closed", "clients": 1,
        "apps": [{"app": "hops", "per_deck": 1,
                  "params": {"root": "vertex"}}]})
    res = _run(tiny_root, cell)
    assert res["correct"] is False
    assert res["checks"]["hops_mismatches"]["value"] > 0


@pytest.mark.parametrize("change", [
    {"edge_factr": 16},                         # a key no code reads
    {"generator": "uniform", "rmat_abcd": [0.25] * 4},   # not uniform's
    {"precision": "bfloat16"},
    {"directed": "no"},
    {"generator": "kron"},                      # no family file
])
def test_a_configuration_no_code_can_serve_fails_before_set_up(tiny_root,
                                                               change):
    cell = _add_cell(tiny_root, _tiny_config(tiny_root, name="bad",
                                             **change),
                     json.loads((ROOT / "bench/traffic/analytics.json")
                                .read_text()))
    with pytest.raises(BenchError):
        _run(tiny_root, cell, find=_no_chip)


def test_an_app_without_a_file_fails_before_set_up(tiny_root):
    cell = _add_cell(tiny_root, _tiny_config(tiny_root, name="tiny2"), {
        "loop": "closed", "clients": 1,
        "apps": [{"app": "bc", "per_deck": 1}]})
    with pytest.raises(BenchError, match="apps 'bc'"):
        _run(tiny_root, cell, find=_no_chip)


@pytest.fixture(scope="module")
def undirected():
    cfg = json.loads((ROOT / "bench/configs/rmat-19-32.json").read_text())
    cfg.update(name="tiny-undirected", scale=10, edge_factor=16,
               directed=False)
    return cfg, graphgen.make_graph(cfg, 2**33 + 13)


def test_undirected_config_serves_every_edge_both_ways(undirected):
    cfg, g = undirected
    n, src, dst, w = graphgen.edges(dict(cfg, directed=True), 2**33 + 13)
    key = g.src.astype(np.int64) * g.num_vertices + g.dst
    rev = g.dst.astype(np.int64) * g.num_vertices + g.src
    assert np.all(np.diff(key) > 0) and np.all(g.src != g.dst)
    order = np.argsort(rev)
    np.testing.assert_array_equal(rev[order], key)
    np.testing.assert_array_equal(np.asarray(g.weights)[order], g.weights)
    # each pair of the directed draws once, with the weight of its first
    # draw in (src, dst) order: the one from the lower id where drawn
    want = {}
    for s, d, x in zip(src.tolist(), dst.tolist(), w.tolist()):
        want.setdefault((min(s, d), max(s, d)), x)
    lo = g.src < g.dst
    got = dict(zip(zip(g.src[lo].tolist(), g.dst[lo].tolist()),
                   np.asarray(g.weights)[lo].tolist()))
    assert got == want


@pytest.mark.parametrize("app", ["bfs", "wcc"])
def test_program_on_an_undirected_graph_matches_the_reference(undirected,
                                                              app):
    from repro.core.gas import BUILTIN_APPS
    from repro.core.planner import PlanConfig
    from repro.core.store import GraphStore
    _, g = undirected
    rg = reference.Graph(g.num_vertices, g.src, g.dst, g.weights)
    kwargs = {"root": int(g.src[len(g.src) // 2])} if app == "bfs" else {}
    ex = GraphStore(g).executor(BUILTIN_APPS[app](**kwargs), PlanConfig(),
                                path="ref")
    props, _ = ex.run()
    assert compare.reading(rg, app, kwargs, props, {}) == 0


def test_rmat_reads_its_quadrant_probabilities():
    cfg = {"name": "t", "generator": "rmat", "scale": 12, "edge_factor": 16,
           "structure_seed": 1}
    skewed = graphgen.edges(cfg, 5)[1]
    flat = graphgen.edges(dict(cfg, rmat_abcd=[0.25] * 4), 5)[1]
    deg, fdeg = (np.bincount(s, minlength=4096) for s in (skewed, flat))
    assert deg.max() > 10 * fdeg.max()
    g500 = graphgen.edges(dict(cfg, rmat_abcd=[0.57, 0.19, 0.19, 0.05]), 5)
    np.testing.assert_array_equal(g500[1], skewed)


@pytest.mark.parametrize("entry", sorted(EDGES))
def test_accepted_configurations_keep_their_edges(entry):
    name, rest = entry.split("@")
    scale, seed = (int(x) for x in rest.split("/"))
    cfg = json.loads((ROOT / f"bench/configs/{name}.json").read_text())
    g = graphgen.make_graph(dict(cfg, scale=scale), seed)
    h = hashlib.sha256()
    for a in (g.src, g.dst, g.weights):
        h.update(a.dtype.str.encode())
        h.update(a.tobytes())
    assert [g.num_vertices, int(g.num_edges), h.hexdigest()] == EDGES[entry]


def test_small_seeded_comparison_and_control_keep_their_numbers(tmp_path):
    """Eleven requests of the tiny cell answered by the program's jnp
    path, compared as a run compares them, and the bfloat16 control on
    two seeds: the numbers the accepted checkout read."""
    from repro.core.gas import BUILTIN_APPS
    from repro.core.planner import PlanConfig
    from repro.core.store import GraphStore
    bench = Bench(make_root(tmp_path))
    mix = bench.traffic("analytics")
    seed = 2**33 + 5
    g = graphgen.make_graph(bench.config("tiny"), seed, bench.root)
    store = GraphStore(g)
    reqs = []
    stream = traffic.stream(mix, traffic.candidates(g.num_vertices, g.src),
                            seed)
    for app, kwargs in itertools.islice(stream, 11):
        props, meta = store.executor(BUILTIN_APPS[app](**kwargs),
                                     PlanConfig(), path="ref").run()
        reqs.append(run.Request(app, kwargs, 0.0, meta["iterations"],
                                answer=props))
    rg = reference.Graph(g.num_vertices, g.src, g.dst, g.weights)
    idx = run.pick_checked(reqs, seed, mix["check_per_app"])
    correct, checks = compare.verdict(compare.worst(
        run.check_answers(rg, reqs, idx, bench.root), bench.root), 0,
        bench.root)
    out = {"served": {"correct": correct, "checks": checks, "checked": idx}}
    for s in (2**33 + 1, 12345):
        out[f"control/{s}"] = control.control_readings(bench, TINY_CELL, s,
                                                       20)
    want = (FIXTURES / "small_run.checks.json").read_text()
    assert json.dumps(out, indent=1, sort_keys=True) + "\n" == want


@pytest.mark.parametrize("app, number, limit, edge_bytes", [
    ("pagerank", "pagerank_max_rel_err", 1e-4, 8),
    ("bfs", "bfs_mismatches", 0, 8),
    ("sssp", "sssp_mismatches", 0, 12),
    ("wcc", "wcc_violations", 0, 8),
    ("closeness", "closeness_mismatches", 0, 8)])
def test_app_files_keep_the_accepted_numbers_limits_and_bytes(
        app, number, limit, edge_bytes):
    assert compare.number_name(app) == number
    assert compare.LIMITS[number] == limit
    assert roofline.iteration_bytes(app, 1000, 64) == \
        edge_bytes * 1000 + 8 * 64
    assert roofline.iteration_ops(app, 1000, 64) == 2 * 1000


def test_submit_keeps_the_programs_request_counters():
    """Each request carries the service's ``executor_hit`` and
    ``iteration_traces``: the first bfs builds and traces its executor,
    the next roots reuse it."""
    from repro.core.planner import PlanConfig
    from repro.serve_graph import GraphService
    g = graphgen.make_graph({"name": "t", "generator": "rmat", "scale": 9,
                             "edge_factor": 8, "structure_seed": 1}, 5)
    svc = GraphService()
    try:
        fp = svc.register(g)
        cand = traffic.candidates(g.num_vertices, g.src)
        reqs = [run.submit(svc, fp, PlanConfig(), "bfs", {"root": int(r)})
                for r in cand[:3]]
    finally:
        svc.close()
    assert [r.error for r in reqs] == [None] * 3
    assert [r.executor_hit for r in reqs] == [False, True, True]
    assert [r.iteration_traces for r in reqs] == [1, 0, 0]
    rec = run.RunRecord("t", "cpu", {}, {}, reqs, 1.0)
    assert Bench(ROOT).reader("service.executor_hit_rate")(rec) == 2 / 3
