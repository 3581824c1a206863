"""Finds everything that belongs to one cell by the names in
BENCHMARK.json: the cell's file, its configuration's file, and the file of
each per-layer metric. A later PR adds files and entries; nothing here
names a cell, a configuration or a metric."""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path):
    with open(path) as f:
        return json.load(f)


class Spec:
    """One cell, resolved."""

    def __init__(self, workload: str, bench_dir: str = BENCH_DIR,
                 manifest: dict | None = None):
        self.bench_dir = bench_dir
        self.manifest = manifest if manifest is not None else _load(
            os.path.join(os.path.dirname(bench_dir), "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if workload not in cells:
            raise SystemExit(f"benchmark: no workload {workload!r}; "
                             f"BENCHMARK.json has {sorted(cells)}")
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry["chips"])
        self.cell = _load(os.path.join(bench_dir, "workloads",
                                       workload + ".json"))
        cfgs = {c["name"]: c for c in self.manifest["configs"]}
        root = os.path.dirname(bench_dir)
        self.config = _load(os.path.join(root,
                                         cfgs[self.entry["config"]]["file"]))
        self.kind = self.cell["kind"]

    @property
    def stack(self):
        """`benchmark/stacks/<stack>.py`, named by the configuration."""
        return load_by_name("stacks", self.config["stack"])

    @property
    def dims(self) -> dict:
        """The stack's dimensions of this configuration, which the counts
        of operations and bytes read."""
        return self.stack.dims(self.config)

    def _reports(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    def end_to_end(self):
        return [m for m in self.manifest["end_to_end"] if self._reports(m)]

    def per_layer(self):
        """[(manifest entry, metric file)] of this cell's per-layer
        metrics: a metric with no `workloads` key belongs to every cell
        that reports the end-to-end metric it moves."""
        mine = {m["name"] for m in self.end_to_end()}
        out = []
        for m in self.manifest["per_layer"]:
            if not self._reports(m) or m["moves"] not in mine:
                continue
            out.append((m, _load(os.path.join(
                self.bench_dir, "metrics", m["name"] + ".json"))))
        return out


def load_by_name(package: str, name: str):
    """`benchmark/<package>/<name>.py`, found by the name a data file
    gives (a reader, a stack, a reference, a driver, a kernel's work)."""
    return importlib.import_module(f"benchmark.{package}.{name}")
