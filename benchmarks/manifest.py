"""Reads ``BENCHMARK.json`` and finds each entry's file by its name."""
from __future__ import annotations

import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class ManifestError(Exception):
    """The manifest or one of the files it names is missing or malformed."""


def load_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise ManifestError("cannot read %s: %s" % (path, e)) from e


class Manifest:
    """The manifest with look-ups by name. ``root`` is the checkout."""

    def __init__(self, root: str = ROOT) -> None:
        self.root = root
        self.data = load_json(os.path.join(root, "BENCHMARK.json"))
        self.bench_dir = os.path.join(root, os.path.basename(HERE))

    def _entry(self, section: str, name: str) -> dict:
        for e in self.data.get(section, []):
            if e.get("name") == name:
                return e
        raise ManifestError(
            "%r is not among BENCHMARK.json's %s: %s"
            % (name, section, ", ".join(e["name"] for e in self.data.get(section, []))))

    def workload(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        """The configuration's own file, as it is run."""
        entry = self._entry("configs", name)
        return load_json(os.path.join(self.root, entry["file"]))

    def traffic(self, name: str) -> dict:
        return load_json(os.path.join(self.bench_dir, "traffic", name + ".json"))

    def limits(self, workload: str) -> dict:
        """The limits ``correct`` holds this cell's numbers to."""
        return load_json(os.path.join(self.bench_dir, "limits", workload + ".json"))

    def metrics(self, section: str, workload: str) -> List[dict]:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m for m in self.data.get(section, [])
                if "workloads" not in m or workload in m["workloads"]]

    def reader(self, metric: str) -> Callable[[dict], Optional[float]]:
        """``read(ctx)`` of ``metrics/<metric>.py``: a number, or None where
        the reader finds nothing to read."""
        path = os.path.join(self.bench_dir, "metrics", metric + ".py")
        if not os.path.isfile(path):
            raise ManifestError("metric %r has no reader at %s" % (metric, path))
        spec = importlib.util.spec_from_file_location(
            "benchmarks_metric_" + "".join(c if c.isalnum() else "_" for c in metric), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    def listing(self) -> Dict[str, List[str]]:
        """What the harness can run, by name: the `--list` output."""
        return {
            "configs": [c["name"] for c in self.data["configs"]],
            "workloads": [w["name"] for w in self.data["workloads"]],
            "traffic": sorted({w["traffic"] for w in self.data["workloads"]}),
            "end_to_end": [m["name"] for m in self.data["end_to_end"]],
            "per_layer": [m["name"] for m in self.data["per_layer"]],
        }
