"""A small benchmark tree for CPU tests: the repo's traffic mixes, graph
families, apps and metric readers, one tiny R-MAT configuration and one
cell on it."""
import json
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
TINY_CELL = "tiny.analytics"


def make_root(dest: Path, scale: int = 10) -> Path:
    (dest / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics", "generators", "apps"):
        shutil.copytree(ROOT / "bench" / sub, dest / "bench" / sub)
    cfg = json.loads((ROOT / "bench/configs/rmat-19-32.json").read_text())
    cfg.update(name="tiny", scale=scale, edge_factor=16)
    (dest / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": TINY_CELL, "config": "tiny",
                          "traffic": "analytics", "chips": 1, "why": "test"}]
    for m in spec["per_layer"] + spec["end_to_end"]:
        m["workloads"] = [TINY_CELL]
    (dest / "BENCHMARK.json").write_text(json.dumps(spec))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_root(tmp_path)
