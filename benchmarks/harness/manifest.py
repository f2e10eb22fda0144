"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own, found by the name in the manifest:

    configuration   the ``file`` its entry gives (benchmarks/configs/*.json)
    traffic mix     benchmarks/traffic/<traffic>.json
    cell settings   benchmarks/cells/<workload name>.json
    per-layer metric  benchmarks/layer_metrics/<metric name>.py : read(run)
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
from typing import Any, Callable


class Manifest:
    def __init__(self, root: str) -> None:
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json"), encoding="utf-8") as fh:
            self.data = json.load(fh)

    def _json(self, *parts: str) -> dict[str, Any]:
        with open(os.path.join(self.root, *parts), encoding="utf-8") as fh:
            return json.load(fh)

    def workload(self, name: str) -> dict[str, Any]:
        for w in self.data["workloads"]:
            if w["name"] == name:
                return w
        known = ", ".join(w["name"] for w in self.data["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (has: {known})")

    def config(self, name: str) -> dict[str, Any]:
        for c in self.data["configs"]:
            if c["name"] == name:
                return self._json(c["file"])
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, mix: str) -> dict[str, Any]:
        return self._json("benchmarks", "traffic", mix + ".json")

    def cell(self, workload: str) -> dict[str, Any]:
        return self._json("benchmarks", "cells", workload + ".json")

    def metrics_for(self, kind: str, workload: str) -> list[dict[str, Any]]:
        """The entries of ``end_to_end`` or ``per_layer`` this cell reports:
        those without a ``workloads`` key, and those that list it."""
        return [m for m in self.data[kind]
                if "workloads" not in m or workload in m["workloads"]]

    def reader_path(self, metric: str) -> str:
        return os.path.join(self.root, "benchmarks", "layer_metrics", metric + ".py")

    def reader(self, metric: str) -> Callable[[Any], float | None]:
        path = self.reader_path(metric)
        spec = importlib.util.spec_from_file_location("bench_layer_metric_" + metric.replace(".", "_"), path)
        if spec is None or spec.loader is None:
            raise FileNotFoundError(path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read


def resolve(dotted: str) -> Any:
    """``package.module:function`` -> the function (a config's factory)."""
    module, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(module), attr)
