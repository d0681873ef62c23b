"""BENCHMARK.json keeps its contract, and new cells, configurations,
traffic mixes and metrics are found as new files alone."""
import json
import os
import re

import benchpath
import pytest

from benchlib.catalog import Catalog

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def spec():
    with open(os.path.join(benchpath.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_units_and_keys():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(benchpath.ROOT, c["file"]))
    e2e = {m["name"] for m in s["end_to_end"]}
    cells = {w["name"] for w in s["workloads"]}
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1
    for m in s["end_to_end"] + s["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= cells
    assert "setup_s" in e2e


def test_every_cell_and_metric_resolves():
    cat = Catalog()
    for w in spec()["workloads"]:
        cell = cat.cell(w["name"])
        assert {m.name for m in cell.end_to_end} >= {"samples_per_s",
                                                      "setup_s"}
        assert cell.per_layer


def test_new_files_alone_add_a_cell(tmp_path):
    s = spec()
    base = json.load(open(os.path.join(benchpath.BENCH, "configs",
                                       "vit-base-16.json")))
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    base["name"] = "vit-new"
    (tmp_path / "configs" / "vit-new.json").write_text(json.dumps(base))
    (tmp_path / "traffic" / "openimages-cold.json").write_text(json.dumps(
        {"name": "openimages-cold", "dataset": {"mean_encoded_bytes": 315840}}))
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(run):\n    return 42.0\n")
    s["configs"].append({"name": "vit-new", "source": "x", "why": "x",
                         "file": "bench/configs/vit-new.json", "reduced": []})
    s["workloads"].append({"name": "new-cell", "config": "vit-new",
                           "traffic": "openimages-cold", "chips": 1,
                           "why": "x"})
    s["per_layer"].append({"name": "new.metric", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "x", "moves": "samples_per_s",
                           "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(s))
    cell = Catalog(tmp_path / "BENCHMARK.json", dirs=[tmp_path]).cell(
        "new-cell")
    assert cell.config["name"] == "vit-new"
    assert cell.traffic["dataset"]["mean_encoded_bytes"] == 315840
    assert [m.name for m in cell.per_layer] == ["new.metric"]
    assert cell.per_layer[0].read(None) == 42.0
    with pytest.raises(KeyError):
        Catalog(tmp_path / "BENCHMARK.json").cell("no-such-cell")
