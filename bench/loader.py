"""Find the benchmark's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one graph family, one
app, one traffic mix or one per-layer metric is a file of its own, found
by its name:

* a configuration: the ``file`` of its entry under ``configs``;
* a graph family: ``bench/generators/<generator>.py``, named by the
  configuration's ``generator`` key (see ``bench/graphgen.py``);
* an app: ``bench/apps/<app>.py``, named by the traffic mix (see
  ``bench/compare.py``);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* a per-layer metric: ``bench/metrics/<metric name>.py``, a module with a
  ``read(record)`` function (see ``bench/run.py``'s ``RunRecord``).

Adding a cell, a family, an app, a mix or a metric adds files and
entries; no file that is there needs an edit.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from pathlib import Path
from types import ModuleType
from typing import Callable

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class BenchError(RuntimeError):
    """A run that cannot produce a result: it exits non-zero."""


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not _NAME.match(name):
        raise BenchError(f"{kind} name {name!r} is not a valid name")
    return name


def load_file(root: Path, kind: str, name: str) -> ModuleType:
    """The module ``<root>/bench/<kind>/<name>.py``, imported once per
    process: ``kind`` is ``generators``, ``apps`` or ``metrics``."""
    path = Path(root).resolve() / "bench" / kind / f"{_check_name(kind, name)}.py"
    if not path.is_file():
        raise BenchError(f"no file {path} for {kind} {name!r}")
    return _import(path, f"bench_{kind}_" + re.sub(r"\W", "_", name))


@functools.lru_cache(maxsize=None)
def _import(path: Path, mod_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Bench:
    """``BENCHMARK.json`` at ``root`` and the files it names."""

    def __init__(self, root: Path):
        self.root = Path(root)
        path = self.root / "BENCHMARK.json"
        if not path.is_file():
            raise BenchError(f"no BENCHMARK.json in {self.root}")
        self.spec = json.loads(path.read_text())

    def _entry(self, section: str, name: str) -> dict:
        for e in self.spec.get(section, []):
            if e["name"] == name:
                return e
        raise BenchError(f"no {section} entry named {name!r}")

    def workload(self, name: str) -> dict:
        return self._entry("workloads", _check_name("workload", name))

    def config(self, name: str) -> dict:
        entry = self._entry("configs", _check_name("config", name))
        cfg = json.loads((self.root / entry["file"]).read_text())
        if cfg.get("name") != name:
            raise BenchError(f"{entry['file']} names {cfg.get('name')!r}, "
                             f"not {name!r}")
        return cfg

    def traffic(self, name: str) -> dict:
        """The mix, once every app it names has its file."""
        path = self.root / "bench" / "traffic" / f"{_check_name('traffic', name)}.json"
        if not path.is_file():
            raise BenchError(f"no traffic file {path}")
        mix = json.loads(path.read_text())
        for a in mix.get("apps", []):
            load_file(self.root, "apps", a.get("app"))
        return mix

    def metrics(self, workload: str, trace: bool) -> list:
        """The metric entries a run of ``workload`` reports: its
        ``end_to_end`` metrics, or with ``trace`` its ``per_layer`` ones
        (each only where its ``workloads`` list names the cell)."""
        section = "per_layer" if trace else "end_to_end"
        return [m for m in self.spec.get(section, [])
                if workload in m.get("workloads", [workload])]

    def reader(self, metric: str) -> Callable:
        """The ``read(record) -> float | None`` of a per-layer metric
        (None where it found nothing to read)."""
        return load_file(self.root, "metrics", metric).read
