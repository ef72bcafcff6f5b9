"""The cell ``airline_gbm.train`` at a size a test run can hold (4,096 rows,
kernels interpreted): its rehearsal line, the sound run by the cell's own
limits, each control of ``checks/gbm_enum_train_follow.py`` making
``correct`` false, and ``counts/gbm_enum.py`` against hand arithmetic.

At 4,096 rows a depth-10 tree ends in nodes of 20-100 rows in which most of
an airport column's 300 levels have one row and the same G/H: the program's
float32 and the reference's float64 break those ties apart differently, and
``min_rows`` then cuts inside a tie group. ``split_regret`` is therefore held
to ``DEEP_REGRET`` here and to the cell's limit on the chip, where the nodes
are 10,000 times larger (PERF.md section 2)."""
import json

import pytest

from cellrun import SEED, decide, one_step
from harness import counts, loader, peaks
from test_rehearse import run

CELL = "airline_gbm.train"
DEEP_REGRET = 0.25
ROWS = 40_000_000


@pytest.fixture(scope="module")
def trained():
    return one_step(CELL)


def limits_here(cell):
    return {**cell["check"]["limits"], "split_regret": DEEP_REGRET}


def over(cell, numbers):
    lim = limits_here(cell)
    return {n for n, v in numbers.items() if not v <= lim[n]}


def test_rehearsal_line_names_the_ragged_set_path():
    p = run("--workload", CELL, "--seed", str(2**31 + 33), "--seconds", "1",
            "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    pc = line["run"]["info"]["packed_codes"]
    assert (pc["enabled"], pc["W"], pc["dtype"], pc["lanes"],
            pc["lane_layout"], pc["set_features"]) == (
        True, 304, "int16", 896, "ragged", 6)
    assert pc["kernel"] == "binned_level_tpu_t" and pc["feature_block"] == 8
    # the train's spans and the split counters are read; what needs a
    # device trace or the chip's peaks is left out
    assert {"loop_s", "sketch_s", "digitize_s", "pack_s", "queue_s",
            "compiles_in_window.train",
            "set_split_share.airline_gbm"} <= set(line["metrics"])
    assert line["metrics"]["set_split_share.airline_gbm"]["value"] > 50
    assert not {m for m in line["metrics"] if m.startswith("level_kernel")}
    assert set(line["compared"]) == set(
        loader.read_json("workloads", CELL + ".json")["limits"])


def test_sound_run_holds_the_cells_limits(trained):
    cell, product, ok = trained
    _, compared = decide(cell, product, ok)
    assert ok
    numbers = {n: c["value"] for n, c in compared.items()}
    assert not over(cell, numbers), compared
    assert product["model"]["is_set"].sum() > 100


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "node_value_gap"), ("fp8", "leaf_gap"),
    ("half_batch", "cover_gap"), ("half_batch", "edge_gap"),
    ("bin_off_by_one", "split_regret"), ("last_step_dropped", "logloss_gap"),
    ("ordinal_sets", "split_regret")])
def test_controls_are_not_correct(trained, control, must_fail):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    numbers = check.run(cell, product, SEED, control=control)
    assert must_fail in over(cell, numbers), numbers


def test_a_train_off_the_expected_lane_layout_is_a_failed_step(monkeypatch):
    """Label encoding underneath: thresholds, the uniform layout (or no
    packing at all at 300 ordinal bins): the runner's step refuses it."""
    from harness import system
    real = system.estimator

    def ordinal(config):
        est = real(config)
        est.params["categorical_encoding"] = "label_encoder"
        return est
    monkeypatch.setattr(system, "estimator", ordinal)
    with pytest.raises(RuntimeError, match="warm-up train"):
        one_step(CELL)


def config():
    return loader.read_json("configs", "gbm_perf_airline.json")


def test_one_level_is_36_bytes_and_24_adds_a_row():
    level = counts.phases("gbm_enum.levels", config())[0]
    assert level["bytes"] == ROWS * (8 * 2 + 4 + 12 + 4) == ROWS * 36
    assert level["flops"] == ROWS * 3 * 8
    assert peaks.least_seconds([level], peaks.of("TPU v5 lite")) == (
        pytest.approx(ROWS * 36 / 819e9), "bandwidth")


def test_levels_and_whole_train_by_hand():
    phases = counts.phases("gbm_enum.levels", config())
    assert len(phases) == 10 * 11                   # one phase a kernel call
    assert sum(p["bytes"] for p in phases) == 10 * ROWS * (
        10 * 36 + (2 + 4 + 4 + 12))
    train = counts.phases("gbm_enum.train", config())
    # sketch 8*4 bytes and min/max of the 2 numeric columns; digitise
    # 8*(4+2) bytes and 7 compares a value of the 2 numeric columns
    assert (train[0]["bytes"], train[0]["flops"]) == (ROWS * 32, ROWS * 4)
    assert (train[1]["bytes"], train[1]["flops"]) == (ROWS * 48, ROWS * 14)
    assert sum(p["bytes"] for p in train) == ROWS * (
        32 + 48 + 10 * (20 + 10 * 36 + 22 + 12) + 12)


def test_the_count_ignores_the_lane_layout():
    a = config()
    b = config()
    b["expect"] = {"W": 512, "lane_layout": "uniform", "lanes": 4096}
    for name in ("gbm_enum.levels", "gbm_enum.train"):
        assert counts.phases(name, a) == counts.phases(name, b)
