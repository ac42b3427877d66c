"""Files are found by the names in BENCHMARK.json, with no edit to a
file that is there; the peaks table refuses a device it does not know."""
import json

import pytest

from bench import roofline
from bench.loader import Bench, BenchError
from bench.run import RunRecord

from .conftest import TINY_CELL


def test_new_config_traffic_and_metric_are_found_by_name(tiny_root):
    before = {p: p.read_bytes() for p in tiny_root.rglob("*") if p.is_file()}
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "other"
    (tiny_root / "bench/configs/other.json").write_text(json.dumps(cfg))
    (tiny_root / "bench/traffic/only_bfs.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1,
         "apps": [{"app": "bfs", "per_deck": 1,
                   "params": {"root": "vertex"}}]}))
    (tiny_root / "bench/metrics/requests.count.py").write_text(
        "def read(record):\n    return len(record.requests)\n")
    # the entries a later change adds; every file already there is kept
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "other", "source": "test",
                            "file": "bench/configs/other.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "other.bfs", "config": "other",
                              "traffic": "only_bfs", "chips": 1,
                              "why": "test"})
    spec["per_layer"].append({"name": "requests.count", "unit": "req",
                              "better": "higher", "source": "host_clock",
                              "layer": "service", "moves": "requests_per_s",
                              "workloads": ["other.bfs"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    for p, data in before.items():
        if p.name != "BENCHMARK.json":
            assert p.read_bytes() == data

    bench = Bench(tiny_root)
    assert bench.config("other")["scale"] == cfg["scale"]
    assert bench.traffic("only_bfs")["apps"][0]["app"] == "bfs"
    names = [m["name"] for m in bench.metrics("other.bfs", trace=True)]
    assert names == ["requests.count"]
    read = bench.reader("requests.count")
    rec = RunRecord("other.bfs", "TPU v5 lite", {}, {}, [object()] * 3, 1.0)
    assert read(rec) == 3
    # the tiny cell keeps its own metrics and not the new one
    assert "requests.count" not in [
        m["name"] for m in bench.metrics(TINY_CELL, trace=True)]


def test_unknown_names_are_errors(tiny_root):
    bench = Bench(tiny_root)
    for call in (lambda: bench.workload("nope"), lambda: bench.config("nope"),
                 lambda: bench.traffic("nope"), lambda: bench.reader("nope"),
                 lambda: bench.traffic("../../etc")):
        with pytest.raises(BenchError):
            call()


def test_unknown_device_kind_in_peaks_is_an_error():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(BenchError):
        roofline.peaks("TPU v9 imaginary")
    with pytest.raises(BenchError):
        roofline.roofline_s("bfs", 100, 10, 1, "cpu")


def test_every_metric_has_a_reader_and_every_file_exists():
    from .conftest import ROOT
    bench = Bench(ROOT)
    for w in bench.spec["workloads"]:
        bench.config(w["config"])
        bench.traffic(w["traffic"])
        for m in bench.metrics(w["name"], trace=True):
            assert callable(bench.reader(m["name"]))
