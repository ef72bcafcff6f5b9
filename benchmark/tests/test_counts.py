"""``counts/gbm.py`` against hand arithmetic, for the configuration as it
is run and for a second shape (depth 6, 14 quantile bins, 20 trees)."""
import copy

import pytest

from harness import counts, peaks
from harness.loader import read_json

V5E = peaks.of("TPU v5 lite")
ROWS = 10_000_000
SCORE_ROWS = 500_000


def config(depth=5, trees=50, nbins=20, edges="uniform_adaptive"):
    cfg = read_json("configs", "gbm_h2o_defaults.json")
    cfg["params"].update(max_depth=depth, ntrees=trees, nbins=nbins)
    cfg["reference"]["edges"] = edges
    return cfg


D6 = dict(depth=6, trees=20, nbins=14, edges="quantiles_global")


def totals(phases):
    return sum(p["bytes"] for p in phases), sum(p["flops"] for p in phases)


def test_one_level_is_48_bytes_and_84_adds_a_row():
    level = counts.phases("gbm.levels", config())[0]
    assert level["bytes"] == ROWS * (28 * 1 + 4 + 12 + 4) == ROWS * 48
    assert level["flops"] == ROWS * 3 * 28 == ROWS * 84


@pytest.mark.parametrize("shape,depth,trees", [({}, 5, 50), (D6, 6, 20)])
def test_levels_of_a_train(shape, depth, trees):
    phases = counts.phases("gbm.levels", config(**shape))
    assert len(phases) == trees * (depth + 1)     # one phase a kernel call
    b, f = totals(phases)
    assert b == trees * ROWS * (depth * 48 + (1 + 4 + 4 + 12))
    assert f == trees * ROWS * (depth * 84 + 3)
    least, bound = peaks.least_seconds(phases, V5E)
    assert bound == "bandwidth"
    assert least == pytest.approx(b / 819e9)


def test_whole_train_by_hand():
    # defaults: sketch 28*4, digitise 28*5, 50 x (20 + 5*48 + 21 + 12), metrics 12
    b, _ = totals(counts.phases("gbm.train", config()))
    assert b == ROWS * (112 + 140 + 50 * (20 + 240 + 21 + 12) + 12)
    # d6: 20 x (20 + 6*48 + 21 + 12)
    b, _ = totals(counts.phases("gbm.train", config(**D6)))
    assert b == ROWS * (112 + 140 + 20 * (20 + 288 + 21 + 12) + 12)


def test_score_by_hand():
    b, f = totals(counts.phases("gbm.score", config()))
    assert b == SCORE_ROWS * (28 * 4 + 12)
    assert f == SCORE_ROWS * (50 * 6 + 10)
    whole = config()
    del whole["data"]["score_rows"]          # no held-out table: the training rows
    assert totals(counts.phases("gbm.score", whole))[0] == ROWS * (28 * 4 + 12)


@pytest.mark.parametrize("count", ["gbm.levels", "gbm.train", "gbm.score"])
def test_count_ignores_kernel_name_and_lane_width(count):
    cfg = config()
    other = copy.deepcopy(cfg)
    other["expect"] = {"W": 128, "level_kernel": "some_future_kernel"}
    assert counts.phases(count, cfg) == counts.phases(count, other)


def test_a_count_is_named_by_its_file_and_entry():
    with pytest.raises(KeyError, match="no count 'nothing'"):
        counts.phases("gbm.nothing", config())
    with pytest.raises(Exception, match="no harness/counts/other.py"):
        counts.phases("other.levels", config())


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.of("TPU v9")
