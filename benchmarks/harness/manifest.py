"""BENCHMARK.json and the files it names. Everything that belongs to one
configuration, one traffic mix, one cell or one per-layer metric is a file
of its own, found by the name in the manifest:

    configuration   the ``file`` its entry gives (benchmarks/configs/*.json);
                    the file names its ``factory``, whose module also holds
                    the family's ``lowered_programs``, and its ``reference``:
                    the runner knows one architecture from another through
                    those alone
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


def lowering(config: dict[str, Any]) -> Callable[..., Any]:
    """The configuration's lowering: ``lowered_programs`` in its factory's
    module, the one way to name it. Called with the engine and the
    warm-up's prompt sizes, it returns (program name -> lowered text, the
    names that must hold a compiled kernel on the chip)."""
    return resolve(config["factory"].partition(":")[0] + ":lowered_programs")


def reference_module(config: dict[str, Any]) -> Any:
    """The plain reference the configuration's file names: ``reference``
    is the module's path from the root of the checkout, ``a/b/c.py``, and
    the module is ``a.b.c`` as an import gives it — one object a file.
    Its contract: ``served_gaps(config, weights, prompt, served, pad_len=,
    control_bits=)``, ``pad_to(n, multiple)`` and, for the tests,
    ``logits(config, weights, ids, weight_bits=)``."""
    path = config.get("reference") or ""
    if not path.endswith(".py") or path.startswith("/") or ".." in path.split("/"):
        raise ValueError(f"reference {path!r}: a module's path from the root of the checkout, a/b/c.py")
    module = importlib.import_module(path[:-3].replace("/", "."))
    missing = [name for name in ("served_gaps", "pad_to", "logits") if not callable(getattr(module, name, None))]
    if missing:
        raise AttributeError(f"reference {path} lacks {', '.join(missing)}")
    return module
