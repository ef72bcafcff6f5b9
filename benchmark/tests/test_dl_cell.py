"""The cell ``dl_defaults.train`` at a size a test run can hold (4,096
rows): its rehearsal line, the sound run by the cell's own limits, each
control of ``checks/dl_train_follow.py`` and each fault planted in the
program making ``correct`` false, and ``counts/dl.py`` against hand
arithmetic."""
import json

import pytest

from cellrun import SEED, decide, one_step
from harness import counts, loader, peaks, system
from test_rehearse import run

CELL = "dl_defaults.train"


@pytest.fixture(scope="module")
def trained():
    return one_step(CELL)


def over(cell, numbers):
    limits = cell["check"]["limits"]
    return {n for n, v in numbers.items() if not v <= limits[n]}


def failed(compared):
    return {n for n, c in compared.items() if not c["value"] <= c["limit"]}


def test_rehearsal_line_names_the_epoch_loop():
    p = run("--workload", CELL, "--seed", str(2**31 + 39), "--seconds", "1",
            "--trace", "1", "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    loop = line["run"]["info"]["train_loop"]
    assert (loop["sizes"], loop["batch"], loop["optimizer"], loop["epochs"],
            loop["n_batches"]) == ([28, 200, 200, 2], 256, "adadelta", 10,
                                   16)
    assert line["run"]["info"]["precision"]["matmul"] == "default"
    # the train's spans and counters are read; what needs a device trace
    # or the chip's peaks is left out; the tree trainers' bin stage is not
    # this cell's
    assert {"loop_s", "init_s", "finalize_s", "queue_s", "spec_s",
            "train_other_s", "compiles_in_window.train",
            "optimizer_step_us.dl_defaults"} <= set(line["metrics"])
    assert not {"bin_s", "sketch_s", "digitize_s", "pack_s",
                "train_mfu_pct.dl_defaults", "epoch_s.dl_defaults",
                "epoch_roofline.dl_defaults"} & set(line["metrics"])
    assert set(line["compared"]) == set(
        loader.read_json("workloads", CELL + ".json")["limits"])


def test_sound_run_is_correct(trained):
    cell, product, ok = trained
    correct, compared = decide(cell, product, ok)
    assert ok and correct, compared


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "p1_gap"), ("fp8", "weight_gap"),
    ("sgd_in_place", "weight_gap"), ("last_epoch_dropped", "epochs_gap"),
    ("init_weights", "learned")])
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


def test_a_decay_rate_changed_underneath_moves_the_steps(monkeypatch):
    """ADADELTA's rho is 0.5 in the program, 0.99 in the configuration."""
    real = system.estimator

    def quick(config):
        est = real(config)
        est.params["rho"] = 0.5
        return est
    monkeypatch.setattr(system, "estimator", quick)
    cell, product, ok = one_step(CELL)
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert "weight_gap" in failed(compared), compared


def test_a_probability_altered_where_it_is_produced(monkeypatch):
    """The probabilities of a band of a hundred rows are moved by 0.05."""
    from h2o3_tpu.models.deeplearning import DeepLearningModel
    real = DeepLearningModel._predict_matrix

    def altered(self, X, offset=None):
        probs = real(self, X, offset=offset)
        return probs.at[100:200, 1].add(0.05).at[100:200, 0].add(-0.05)
    monkeypatch.setattr(DeepLearningModel, "_predict_matrix", altered)
    cell, product, ok = one_step(CELL)
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert "p1_gap" in failed(compared), compared


@pytest.mark.parametrize("param,value,why", [
    ("epochs", 9, "9.0 epochs trained of 10.0"),
    ("mini_batch_size", 128, "train.loop batch 128")])
def test_a_train_off_the_configuration_is_a_failed_step(monkeypatch, param,
                                                        value, why):
    real = system.estimator

    def other(config):
        est = real(config)
        est.params[param] = value
        return est
    monkeypatch.setattr(system, "estimator", other)
    with pytest.raises(RuntimeError, match=why):
        one_step(CELL)


def config():
    return loader.read_json("configs", "dl_h2o_defaults_higgs.json")


def test_a_row_of_an_epoch_is_132_400_multiply_adds():
    from harness.counts import dl
    assert dl.macs_per_row([28, 200, 200, 2]) == {
        "forward": 46_000, "weight_grads": 46_000, "error": 40_400}
    epochs = counts.phases("dl.epochs", config())
    assert len(epochs) == 10
    rows = 10_000_000 // 256 * 256                  # 9,999,872
    assert epochs[0]["flops"] == 264_800 * rows
    # a row's 28 features, label and weight; weights and both
    # accumulators (46,402 each) read and written
    assert epochs[0]["bytes"] == 120 * rows + 2 * 3 * 4 * 46_402


def test_a_train_is_its_epochs_and_two_forward_passes():
    phases = counts.phases("dl.train", config())
    assert len(phases) == 12
    fwd = phases[-1]
    assert fwd["flops"] == 2 * 46_000 * 10_000_000
    assert fwd["bytes"] == 4 * (28 + 2) * 10_000_000
    least, bound = peaks.least_seconds(phases, peaks.of("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(
        (10 * 264_800 * 9_999_872 + 2 * 92_000 * 10_000_000) / 197e12)
