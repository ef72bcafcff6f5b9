"""The cell ``xgb_hist.train`` at a size a test run can hold (4,096 rows,
kernels interpreted): its rehearsal line, the sound run by the cell's own
limits, each control of ``checks/xgb_train_follow.py`` making ``correct``
false, and a train that reads ``min_child_weight`` as a row count."""
import json

import pytest

from cellrun import SEED, decide, one_step
from harness import loader, system
from test_rehearse import run

CELL = "xgb_hist.train"


@pytest.fixture(scope="module")
def trained():
    return one_step(CELL)


def over(cell, numbers):
    limits = cell["check"]["limits"]
    return {n for n, v in numbers.items() if not v <= limits[n]}


def test_rehearsal_line_names_the_packed_int16_path():
    p = run("--workload", CELL, "--seed", str(2**31 + 29), "--seconds", "1",
            "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    pc = line["run"]["info"]["packed_codes"]
    assert (pc["enabled"], pc["W"], pc["dtype"], pc["bytes_per_value"],
            pc["n_bins"]) == (True, 256, "int16", 2, 254)
    assert pc["kernel"] == "binned_level_tpu_t" and pc["feature_block"] == 28
    # the train's spans are read; what needs a device trace is left out
    assert {"loop_s", "sketch_s", "digitize_s", "pack_s", "queue_s",
            "compiles_in_window.train"} <= set(line["metrics"])
    assert not {m for m in line["metrics"] if m.endswith(".xgb_hist")}
    assert set(line["compared"]) == set(
        loader.read_json("workloads", CELL + ".json")["limits"])


def test_sound_run_is_correct(trained):
    cell, product, ok = trained
    correct, compared = decide(cell, product, ok)
    assert ok and correct, compared


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "node_value_gap"), ("fp8", "leaf_gap"),
    ("half_batch", "cover_gap"), ("half_batch", "edge_gap"),
    ("bin_off_by_one", "split_regret"), ("last_step_dropped", "logloss_gap")])
def test_controls_are_not_correct(trained, control, must_fail):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    numbers = check.run(cell, product, SEED, control=control)
    assert must_fail in over(cell, numbers), numbers


def test_a_control_that_does_not_exist_is_an_error(trained):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    with pytest.raises(ValueError):
        check.run(cell, product, SEED, control="fp4")


def test_min_child_weight_read_as_a_row_count_is_not_correct(monkeypatch):
    """The bound moved from hessian sums to rows underneath, at a value
    where the two differ: splits the objective forbids are kept."""
    import copy
    real_cell, real_est = loader.load_cell, system.estimator

    def heavy(bench, name):
        cell = copy.deepcopy(real_cell(bench, name))
        cell["config"]["params"]["min_child_weight"] = 50.0
        return cell

    def by_rows(config):
        est = real_est(config)
        est.params.update(min_rows=est.params["min_child_weight"],
                          min_child_weight=0.0)
        return est
    monkeypatch.setattr(loader, "load_cell", heavy)
    monkeypatch.setattr(system, "estimator", by_rows)
    cell, product, ok = one_step(CELL)
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert compared["child_weight_gap"]["value"] > 0.5, compared
