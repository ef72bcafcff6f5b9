"""The files fit together, and a metric that lists a cell which does not
report its ``moves`` metric is refused at load."""
import copy

import pytest

from harness import loader


def test_every_cell_loads_with_its_metrics():
    bench = loader.load_benchmark()
    for w in bench["workloads"]:
        cell = loader.load_cell(bench, w["name"])
        assert {"setup_s"} < set(cell["reports"])
        assert cell["metrics"], w["name"]
        for m in cell["metrics"]:
            assert m["moves"] in cell["reports"]
            loader.plugin("readers", m["reader"])
        loader.plugin("runners", cell["traffic"]["runner"])
        loader.plugin("checks", cell["check"]["check"])


def test_metric_listing_a_cell_without_its_moves_metric_is_refused():
    bench = copy.deepcopy(loader.load_benchmark())
    bin_s = next(m for m in bench["per_layer"] if m["name"] == "bin_s")
    bin_s["workloads"].append("h2o_defaults.score")   # reports no train_s
    with pytest.raises(loader.BenchmarkError, match="does not report train_s"):
        loader.load_cell(bench, "h2o_defaults.train")


def test_unknown_cell_and_plugin_are_errors():
    bench = loader.load_benchmark()
    with pytest.raises(loader.BenchmarkError):
        loader.load_cell(bench, "no.such.cell")
    with pytest.raises(loader.BenchmarkError):
        loader.plugin("readers", "no_such_reader")
