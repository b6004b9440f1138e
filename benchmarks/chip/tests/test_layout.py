"""Every cell, metric, configuration and mix in BENCHMARK.json is found
by its name, as files of its own."""

import os

import pytest

import cell as cell_lib
from conftest import BENCH, ROOT

BENCHMARK = cell_lib.load_json(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("workload", [w["name"] for w in BENCHMARK["workloads"]])
def test_cell_resolves(workload):
    c = cell_lib.resolve(workload)
    assert os.path.isfile(os.path.join(BENCH, "drivers", c.kind + ".py"))
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "each cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]])
def test_metric_has_reader(metric):
    assert callable(cell_lib.reader(metric))


def test_configs_are_their_files():
    files = [c["file"] for c in BENCHMARK["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCHMARK["configs"]:
        cfg = cell_lib.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
