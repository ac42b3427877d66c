"""The command fails, with no result line, where it cannot measure."""
import os
import shutil
import subprocess
import sys

from .conftest import ROOT


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script, "--workload", "rmat19-32.analytics",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run(ROOT, "bench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_with_only_the_benchmark_files_the_run_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, "bench/run.py")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_an_executor_off_the_pallas_path_fails_the_run():
    import pytest

    from bench import graphgen, run
    from bench.loader import BenchError
    from repro.core.planner import PlanConfig
    from repro.core.store import GraphStore

    g = graphgen.make_graph({"name": "t", "generator": "rmat", "scale": 9,
                             "edge_factor": 8, "structure_seed": 1}, 5)
    with pytest.raises(BenchError, match="not 'pallas'"):
        run.check_pallas(GraphStore(g), PlanConfig(), ["pagerank"])
