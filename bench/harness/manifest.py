"""``BENCHMARK.json`` and the files it names, found by name.

A configuration is the ``file`` its entry names; a traffic mix is
``traffic/<name>.json``; the limits of a cell's check are
``limits/<cell>.json``; a per-layer metric is read by
``readers/<metric>.py``, or by ``readers/<stem>.py`` where metrics
``<stem>.sweep`` and ``<stem>.point`` read one quantity in cells of
either kind.  Adding one is adding its file.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Manifest:
    def __init__(self, root: pathlib.Path = ROOT, bench: pathlib.Path = BENCH):
        self.root, self.bench = root, bench
        self.data = json.loads((root / "BENCHMARK.json").read_text())

    def workload(self, name: str) -> dict:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.data["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def limits(self, workload: str) -> dict:
        return json.loads((self.bench / "limits" / f"{workload}.json")
                          .read_text())

    def end_to_end(self, workload: str) -> list[dict]:
        """The end-to-end metrics this cell reports."""
        return [m for m in self.data["end_to_end"]
                if workload in m.get("workloads", [workload])]

    def per_layer(self, workload: str) -> list[dict]:
        """The per-layer metrics this cell reports: those that list it, and
        those with no list in every cell that reports what they move."""
        e2e = {m["name"] for m in self.end_to_end(workload)}
        return [m for m in self.data["per_layer"]
                if (workload in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]

    def reader(self, metric: str):
        """``read(ctx)`` of the metric's reader."""
        path = self.bench / "readers" / f"{metric}.py"
        if not path.exists():
            path = self.bench / "readers" / f"{metric.split('.')[0]}.py"
        spec = importlib.util.spec_from_file_location(
            "reader_" + metric.replace(".", "_").replace("-", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
