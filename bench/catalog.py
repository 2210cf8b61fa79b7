"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; the benchmark names its
metrics. Each lives in a file of its own, so a new cell, configuration,
mix or metric is new files and new entries, never an edit:

- ``<root>/<file>``: the configuration (JSON), its path in ``configs``;
- ``<root>/bench/traffic/<mix>.json``: the traffic mix's parameters;
- ``<root>/bench/metrics/<metric>.py``: ``read(ctx)`` of a per-layer metric;
- ``<root>/bench/roofline/<kernel>.py``: a kernel's operations and bytes;
- ``bench.families.<family>`` / ``bench.reference.<family>``: how the
  program under test is built for a family, and its plain reference.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


class Catalog:
    def __init__(self, root: Path | str = ROOT):
        self.root = Path(root)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                conf = json.loads((self.root / c["file"]).read_text())
                conf["name"] = name
                return conf
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads(
            (self.root / "bench" / "traffic" / f"{name}.json").read_text())

    def metrics(self, kind: str, cell: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports."""
        return [m for m in self.spec[kind]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        return _load(self.root / "bench" / "metrics" / f"{metric}.py").read

    def roofline(self, kernel: str):
        return _load(self.root / "bench" / "roofline" / f"{kernel}.py")


def _load(path: Path):
    name = "bench_piece_" + "".join(
        c if c.isalnum() else "_" for c in str(path.relative_to(path.parents[2])))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def family(name: str):
    return importlib.import_module(f"bench.families.{name}")


def reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")
