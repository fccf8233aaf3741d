"""Find everything a cell needs by the names in BENCHMARK.json.

A cell is an entry of ``workloads``: a configuration (its file is
named by the ``configs`` entry) under a traffic mix
(``benchmark/traffic/<traffic>.json``). A per-layer metric is
``benchmark/layer_metrics/<name>.py`` with a ``read(run)`` function.
Nothing here knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional


def load_module(path: str):
    """Import one file by its path (metric readers have dots in their
    names, and share helpers that sit beside them)."""
    name = "benchmark_file_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class SpecError(Exception):
    """BENCHMARK.json or one of the files it names is missing or
    inconsistent; the message says which."""


def _load_json(path: str) -> Any:
    try:
        with open(path) as fh:
            return json.load(fh)
    except (OSError, ValueError) as exc:
        raise SpecError(f"{path}: {exc}") from None


class Cell:
    """One workload with its configuration, traffic and metric lists."""

    def __init__(self, root: str, name: str) -> None:
        self.root = root
        self.bench = _load_json(os.path.join(root, "BENCHMARK.json"))
        entry = next(
            (w for w in self.bench["workloads"] if w["name"] == name), None
        )
        if entry is None:
            known = ", ".join(w["name"] for w in self.bench["workloads"])
            raise SpecError(f"no workload {name!r}; BENCHMARK.json has {known}")
        self.name = name
        self.chips = int(entry["chips"])
        config_entry = next(
            (c for c in self.bench["configs"] if c["name"] == entry["config"]),
            None,
        )
        if config_entry is None:
            raise SpecError(f"workload {name!r} names no listed configuration")
        self.config_path = os.path.join(root, config_entry["file"])
        self.config = _load_json(self.config_path)
        self.traffic_name = entry["traffic"]
        self.traffic_path = os.path.join(
            root, self.bench["paths"][0], "traffic",
            f"{entry['traffic']}.json",
        )
        self.traffic = _load_json(self.traffic_path)
        self.run_seconds = int(self.bench["run_seconds"])

    def _reports(self, metric: Dict[str, Any]) -> bool:
        listed = metric.get("workloads")
        return listed is None or self.name in listed

    def end_to_end(self) -> List[Dict[str, Any]]:
        return [m for m in self.bench["end_to_end"] if self._reports(m)]

    def per_layer(self) -> List[Dict[str, Any]]:
        """Metrics that list this cell, and metrics without a list
        whose ``moves`` names an end-to-end metric this cell reports."""
        reported = {m["name"] for m in self.end_to_end()}
        out = []
        for metric in self.bench["per_layer"]:
            listed = metric.get("workloads")
            if listed is not None:
                if self.name in listed:
                    out.append(metric)
            elif metric["moves"] in reported:
                out.append(metric)
        return out

    def reader(self, metric_name: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        path = os.path.join(
            self.root, self.bench["paths"][0], "layer_metrics",
            f"{metric_name}.py",
        )
        if not os.path.isfile(path):
            raise SpecError(f"per-layer metric {metric_name!r} has no {path}")
        return load_module(path).read
