"""The benchmark's references agree with the program's jnp path, and its
generator and traffic are fixed by the seed."""
import numpy as np
import pytest

from bench import compare, graphgen, reference, traffic

SEED = 2**33 + 11


@pytest.fixture(scope="module")
def small():
    from repro.core.store import GraphStore
    g = graphgen.make_graph({"name": "t", "generator": "rmat", "scale": 10,
                             "edge_factor": 16, "structure_seed": 1}, SEED)
    return g, GraphStore(g), reference.Graph(g.num_vertices, g.src, g.dst,
                                             g.weights)


@pytest.mark.parametrize("app", ["pagerank", "bfs", "sssp", "wcc",
                                 "closeness"])
def test_reference_agrees_with_ref_executor(small, app):
    from repro.core.gas import BUILTIN_APPS
    from repro.core.planner import PlanConfig
    g, store, rg = small
    cand = traffic.candidates(g.num_vertices, g.src)
    kwargs = {"bfs": {"root": int(cand[3])}, "sssp": {"root": int(cand[5])},
              "closeness": {"sources": tuple(int(v) for v in cand[:32])},
              }.get(app, {})
    ex = store.executor(BUILTIN_APPS[app](**kwargs), PlanConfig(), path="ref")
    props, meta = ex.run()
    value = compare.reading(rg, app, kwargs, props, {})
    assert value <= compare.LIMITS[compare.number_name(app)], value
    if app == "pagerank":   # the reference's stopping rule is the app's
        assert meta["iterations"] == reference.pagerank(rg)[1]


@pytest.fixture(scope="module")
def wcc_answer(small):
    from repro.core.gas import BUILTIN_APPS
    from repro.core.planner import PlanConfig
    g, store, rg = small
    ex = store.executor(BUILTIN_APPS["wcc"](), PlanConfig(), path="ref")
    props, meta = ex.run(collect_history=True)
    return rg, np.asarray(props), meta


def _stopped_early(rg, labels, meta, perm):
    return np.asarray(meta["history"][-3])[perm]


def _label_raised(rg, labels, meta, perm):
    # the head of an edge inside a class takes a larger label
    e = np.flatnonzero(labels[rg.src] == labels[rg.dst])[0]
    out = labels.copy()
    out[rg.dst[e]] += 1
    return out


def _label_lowered(rg, labels, meta, perm):
    # the tail of an edge inside a class takes a smaller label
    e = np.flatnonzero(labels[rg.src] == labels[rg.dst])[0]
    out = labels.copy()
    out[rg.src[e]] -= 1
    return out


def _renumbered(rg, labels, meta, perm):
    # the answer under another numbering: still a correct answer
    rng = np.random.default_rng(5)
    other = rng.permutation(rg.n).astype(np.float64)
    while True:
        new = other.copy()
        np.minimum.at(new, rg.dst, other[rg.src])
        if np.array_equal(new, other):
            return other
        other = new


@pytest.mark.parametrize("change,ok", [(_stopped_early, False),
                                       (_label_raised, False),
                                       (_label_lowered, False),
                                       (_renumbered, True)])
def test_wcc_violations_are_zero_exactly_for_a_fixpoint(small, wcc_answer,
                                                        change, ok):
    _, store, _ = small
    rg, labels, meta = wcc_answer
    assert reference.wcc_violations(rg, labels) == 0
    changed = change(rg, labels, meta, np.asarray(store.perm))
    assert (reference.wcc_violations(rg, changed) == 0) is ok


def _edges(generator, scale, edge_factor, structure_seed, seed):
    return graphgen.edges({"name": "t", "generator": generator,
                           "scale": scale, "edge_factor": edge_factor,
                           "structure_seed": structure_seed}, seed)[1:]


@pytest.mark.parametrize("generator", ["rmat", "uniform"])
def test_generator_is_fixed_by_the_seed(generator):
    a = _edges(generator, 9, 8, 1, SEED)
    b = _edges(generator, 9, 8, 1, SEED)
    c = _edges(generator, 9, 8, 1, SEED + 1)
    d = _edges(generator, 9, 8, 2, SEED)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    # another seed: the same edges and ids, other weights; another
    # structure seed: another graph
    np.testing.assert_array_equal(a[0], c[0])
    np.testing.assert_array_equal(a[1], c[1])
    assert not np.array_equal(a[2], c[2])
    assert a[0].size != d[0].size or not np.array_equal(a[0], d[0])
    src, dst, w = a
    assert np.all(src != dst)
    key = src.astype(np.int64) * 512 + dst
    assert np.all(np.diff(key) > 0)          # sorted, no duplicates
    assert set(np.unique(w * 256).astype(int)) <= set(range(256, 512))


def test_rmat_keeps_the_graph500_skew():
    src, _, _ = _edges("rmat", 12, 16, 1, SEED)
    usrc, _, _ = _edges("uniform", 12, 16, 1, SEED)
    deg = np.bincount(src, minlength=4096)
    udeg = np.bincount(usrc, minlength=4096)
    assert deg.max() > 10 * udeg.max()
    assert (deg == 0).mean() > 0.2 > (udeg == 0).mean()


MIX = {"loop": "closed", "clients": 1, "apps": [
    {"app": "bfs", "per_deck": 4, "params": {"root": "vertex"}},
    {"app": "sssp", "per_deck": 4, "params": {"root": "vertex"}},
    {"app": "pagerank", "per_deck": 1, "kwargs": {"damping": 0.85}},
    {"app": "wcc", "per_deck": 1},
    {"app": "closeness", "per_deck": 1, "params": {"sources": 32}}]}


def _first(mix, seed, n=44, cand=np.arange(100, 200)):
    s = traffic.stream(mix, cand, seed)
    return [next(s) for _ in range(n)]


def test_traffic_decks_hold_the_mix_in_a_seeded_order():
    cand = np.arange(100, 200)
    a, b, c = _first(MIX, SEED), _first(MIX, SEED), _first(MIX, SEED + 1)
    assert a == b and a != c
    counts = {x["app"]: x["per_deck"] for x in MIX["apps"]}
    for reqs in (a[:11], a[11:22], c[:11]):
        apps = [r[0] for r in reqs]
        assert {x: apps.count(x) for x in set(apps)} == counts
    # any run of consecutive requests holds each app within two of its share
    apps = [r[0] for r in a]
    for lo in range(0, 22):
        for n in (7, 13, 20):
            for x, k in counts.items():
                assert abs(apps[lo:lo + n].count(x) - n * k / 11) <= 2
    for app, kw in a:
        if app == "closeness":
            assert len(set(kw["sources"])) == 32
            assert set(kw["sources"]) <= set(cand.tolist())
        if app in ("bfs", "sssp"):
            assert kw["root"] in cand


def test_zipf_pool_roots_come_from_one_seeded_pool():
    mix = {"loop": "closed", "clients": 8, "apps": [
        {"app": "bfs", "per_deck": 3, "params": {"root": {"pool": 8,
                                                           "zipf": 1.0}}},
        {"app": "pagerank", "per_deck": 1}]}
    a, b = _first(mix, SEED, 400), _first(mix, SEED, 400)
    assert a == b
    roots = [kw["root"] for app, kw in a if app == "bfs"]
    assert len(set(roots)) <= 8 and set(roots) <= set(range(100, 200))
    top = max(set(roots), key=roots.count)
    assert roots.count(top) > len(roots) / 4    # 1 / H_8 = 0.37 expected


@pytest.mark.parametrize("bad", [{"clients": 0}, {"loop": "open"},
                                 {"apps": [{"app": "bfs", "per_deck": 0}]}])
def test_traffic_files_outside_the_generator_are_refused(bad):
    from bench.loader import BenchError
    with pytest.raises(BenchError):
        traffic.validate({**MIX, **bad})
