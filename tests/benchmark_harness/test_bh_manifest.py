"""The manifest and the data files it names: the contract's limits on names,
units and lengths, the keys each file has to carry, and that a cell, a
configuration, a traffic mix or a metric is added by files and entries alone."""
import json
import os
import re

import pytest

from benchmarks import datagen
from benchmarks.manifest import ROOT, Manifest, ManifestError

from bh_util import tiny_copy

MAN = Manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

ALL_METRICS = MAN.data["end_to_end"] + MAN.data["per_layer"]
ALL_NAMES = ([m["name"] for m in ALL_METRICS]
             + [c["name"] for c in MAN.data["configs"]]
             + [w["name"] for w in MAN.data["workloads"]]
             + [w["traffic"] for w in MAN.data["workloads"]]
             + [k for c in MAN.data["configs"] for k in c["reduced"]])


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MAN.data) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN.data["run_seconds"] <= 51
    assert all(isinstance(w, str) and 0 < len(w) <= 200 for w in MAN.data["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("name", sorted(set(ALL_NAMES)))
def test_name_holds_only_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", ALL_METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MAN.data["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"})
    assert keys <= set(metric) <= keys | {"workloads"}
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in [m["name"] for m in MAN.data["end_to_end"]]
        assert 0 < len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    for cell in metric.get("workloads", []):
        MAN.workload(cell)
    assert callable(MAN.reader(metric["name"]))


def test_every_cell_reports_setup_and_one_more_of_each_kind():
    for cell in MAN.data["workloads"]:
        e2e = [m["name"] for m in MAN.metrics("end_to_end", cell["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert MAN.metrics("per_layer", cell["name"])


@pytest.mark.parametrize("entry", MAN.data["configs"], ids=lambda c: c["name"])
def test_configuration_file(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"].startswith(tuple(p + "/" for p in MAN.data["paths"]))
    assert 0 < len(entry["source"]) <= 200 and 0 < len(entry["why"]) <= 200
    config = MAN.config(entry["name"])
    for key in ("source", "generator", "rows", "features", "params", "reduced",
                "assumed"):
        assert key in config, key
    assert 0 < len(config["source"]) <= 200
    assert config["reduced"] == entry["reduced"] and len(entry["reduced"]) <= 16
    assert callable(datagen.generator(config["generator"]).make)
    for key in ("objective", "num_leaves", "learning_rate", "max_bin",
                "min_data_in_leaf", "min_sum_hessian_in_leaf"):
        assert key in config["params"], key


@pytest.mark.parametrize("cell", MAN.data["workloads"], ids=lambda w: w["name"])
def test_cell_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 0 < len(cell["why"]) <= 200
    assert cell["name"] == "%s.%s" % (cell["config"], cell["traffic"])
    traffic = MAN.traffic(cell["traffic"])
    for key in ("kind", "why", "params", "devices", "warmup_iterations",
                "max_iterations", "precision"):
        assert key in traffic, key
    assert traffic["precision"]["stated"] and traffic["precision"]["control"]
    assert traffic["devices"] == cell["chips"]
    limits = MAN.limits(cell["name"])
    assert set(limits["follow"]) <= {"first", "window_last"} and limits["follow"]
    assert limits["limits"]["exact_mismatch"] == 0
    assert limits["limits"]["compiles_in_window"] == 0
    for number in ("split_gap", "leaf_value_gap", "loss_gap", "score_gap"):
        assert 0 < limits["limits"][number] < 1
    assert 1 < limits["limits"]["bin_width"] < 4


def test_four_chip_cells_are_at_most_a_quarter():
    cells = MAN.data["workloads"]
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)


def test_files_under_paths_are_named_from_a_names_characters():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MAN.data["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert ok.match(rel), rel


def test_unknown_names_are_refused():
    with pytest.raises(ManifestError):
        MAN.workload("no-such.cell")
    with pytest.raises(ManifestError):
        MAN.reader("no.such_metric")


def test_adding_by_files_and_entries_alone(tmp_path):
    """A later PR's configuration, traffic mix, cell and per-layer metric:
    new files and new entries, no edit to a file that is there."""
    man = tiny_copy(str(tmp_path))
    bench = os.path.join(str(tmp_path), "benchmarks")
    first = man.data["workloads"][0]
    config = man.config(first["config"])
    config["params"]["max_bin"] = 15
    config["generator"] = "dummy_ones"
    with open(os.path.join(bench, "generators", "dummy_ones.py"), "w") as fh:
        fh.write("import numpy as np\n\n\ndef make(rows, features, seed, **args):\n"
                 "    return np.ones((rows, features), np.float32), np.zeros(rows, np.float32)\n")
    with open(os.path.join(bench, "configs", "dummy-15bin.json"), "w") as fh:
        json.dump(config, fh)
    traffic = man.traffic(first["traffic"])
    traffic["params"]["device_chunk_size"] = 2
    with open(os.path.join(bench, "traffic", "train-chunk2.json"), "w") as fh:
        json.dump(traffic, fh)
    with open(os.path.join(bench, "limits", "dummy-15bin.train-chunk2.json"), "w") as fh:
        json.dump(man.limits(first["name"]), fh)
    with open(os.path.join(bench, "metrics", "dummy.trees_per_iter.py"), "w") as fh:
        fh.write("def read(ctx):\n    return len(ctx['window_trees']) / ctx['iterations']\n")
    data = man.data
    data["configs"].append({"name": "dummy-15bin", "source": "a test",
                            "file": "benchmarks/configs/dummy-15bin.json",
                            "reduced": ["num_iterations"], "why": "a test"})
    data["workloads"].append({"name": "dummy-15bin.train-chunk2", "config": "dummy-15bin",
                              "traffic": "train-chunk2", "chips": 1, "why": "a test"})
    data["per_layer"].append({"name": "dummy.trees_per_iter", "unit": "1/iter",
                              "better": "higher", "source": "program_counter",
                              "layer": "whole step", "moves": "train_iter_s",
                              "workloads": ["dummy-15bin.train-chunk2"]})
    with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as fh:
        json.dump(data, fh)

    again = Manifest(str(tmp_path))
    listing = again.listing()
    assert "dummy-15bin" in listing["configs"]
    assert "dummy-15bin.train-chunk2" in listing["workloads"]
    assert "train-chunk2" in listing["traffic"]
    assert "dummy.trees_per_iter" in listing["per_layer"]
    assert again.config("dummy-15bin")["params"]["max_bin"] == 15
    X, y = datagen.make(again.config("dummy-15bin"), 3, again.bench_dir)
    assert X.shape == (man.config(first["config"])["rows"], config["features"]) and X.all()
    assert again.traffic("train-chunk2")["params"]["device_chunk_size"] == 2
    names = [m["name"] for m in again.metrics("per_layer", "dummy-15bin.train-chunk2")]
    assert "dummy.trees_per_iter" in names
    assert "dummy.trees_per_iter" not in [
        m["name"] for m in again.metrics("per_layer", first["name"])]
    assert again.reader("dummy.trees_per_iter")({"window_trees": [1, 2], "iterations": 2}) == 1.0
