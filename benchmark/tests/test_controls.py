"""The controls, kept at a size a test run can hold (4,096 rows, kernels
interpreted): the sound run is correct by the cell's own limits, and the
reference put in the program's place in the precision below, or with half
of the rows left out, is not. On the chip at the cells' own size the same is
read by ``chip_control.py`` (PERF.md has the readings)."""
import pytest

from cellrun import SEED, decide, one_step
from harness import loader


@pytest.fixture(scope="module")
def trained():
    return one_step("h2o_defaults.train")


@pytest.fixture(scope="module")
def scored():
    return one_step("h2o_defaults.score")


def over(cell, numbers):
    limits = cell["check"]["limits"]
    return {n for n, v in numbers.items() if not v <= limits[n]}


def test_sound_runs_are_correct(trained, scored):
    for cell, product, ok in (trained, scored):
        correct, compared = decide(cell, product, ok)
        assert ok and correct, compared


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "node_value_gap"), ("fp8", "leaf_gap"),
    ("half_batch", "cover_gap"), ("half_batch", "edge_gap"),
    ("bin_off_by_one", "split_regret"), ("last_step_dropped", "logloss_gap")])
def test_train_controls_are_not_correct(trained, control, must_fail):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    numbers = check.run(cell, product, SEED, control=control)
    assert must_fail in over(cell, numbers), numbers


def test_a_control_that_does_not_exist_is_an_error(trained):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    with pytest.raises(ValueError):
        check.run(cell, product, SEED, control="fp4")


def test_score_control_is_not_correct(scored):
    cell, product, _ = scored
    check = loader.plugin("checks", cell["check"]["check"])
    numbers = check.run(cell, product, SEED, control="bfloat16")
    assert "p1_gap" in over(cell, numbers), numbers
