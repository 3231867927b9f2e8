"""Shared by the harness's tests: a temporary copy of the benchmark whose
configurations are cut to a size a test run can hold."""
import json
import os
import shutil

from benchmarks.manifest import ROOT, Manifest

TINY_ROWS = 20000
TINY_LEAVES = 15
TINY_FEATURES = 28


def tiny_copy(dest: str, rows: int = TINY_ROWS, leaves: int = TINY_LEAVES,
              features: int = TINY_FEATURES) -> Manifest:
    """BENCHMARK.json and benchmarks/ copied under ``dest``, every
    configuration's rows, columns and leaves cut down (a test is about the
    harness, not about a cell's size); returns its manifest."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "*.pyc"))
    man = Manifest(dest)
    for entry in man.data["configs"]:
        path = os.path.join(dest, entry["file"])
        with open(path, encoding="utf-8") as fh:
            config = json.load(fh)
        config["rows"] = rows
        config["features"] = min(features, config["features"])
        config["params"]["num_leaves"] = leaves
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(config, fh)
    return man

