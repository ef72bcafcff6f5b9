"""A run with the timed path broken underneath comes out not correct: once
for each fault a cell can have. The faults are planted in the program (or in
what the harness hands it), the rest of the run is the harness's own."""
import dataclasses

import pytest

from cellrun import decide, one_step
from harness import system


def failed(compared):
    return {n for n, c in compared.items() if not c["value"] <= c["limit"]}


def test_steps_that_leave_the_state_unchanged(monkeypatch):
    """Every boosting step adds nothing: learn_rate 0 underneath."""
    real = system.estimator

    def frozen(config):
        est = real(config)
        est.params["learn_rate"] = 0.0
        return est
    monkeypatch.setattr(system, "estimator", frozen)
    cell, product, ok = one_step("h2o_defaults.train")
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert {"leaf_gap", "node_value_gap"} <= failed(compared), compared


def test_half_of_the_batch_left_out(monkeypatch):
    """The trainer sees every second row only (weight 0 on the others); its
    means are taken over the rest."""
    import jax.numpy as jnp
    from h2o3_tpu.models import model_base
    real = model_base.build_training_spec

    def halved(*a, **k):
        spec = real(*a, **k)
        keep = (jnp.arange(spec.w.shape[0]) % 2 == 0).astype(spec.w.dtype)
        return dataclasses.replace(spec, w=spec.w * keep)
    monkeypatch.setattr(model_base, "build_training_spec", halved)
    cell, product, ok = one_step("h2o_defaults.train")
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert "cover_gap" in failed(compared), compared


def test_a_prediction_altered_where_it_is_produced(monkeypatch):
    """The margins of a band of a hundred rows are shifted by 0.01."""
    from h2o3_tpu.models.gbm import GBMModel
    real = GBMModel._margin_matrix

    def altered(self, X, offset=None):
        margin = real(self, X, offset=offset)
        return margin.at[100:200].add(0.01)
    monkeypatch.setattr(GBMModel, "_margin_matrix", altered)
    cell, product, ok = one_step("h2o_defaults.score")
    correct, compared = decide(cell, product, ok)
    assert not correct
    assert "p1_gap" in failed(compared), compared


def test_a_train_that_leaves_the_packed_path_is_a_failed_step(monkeypatch):
    real = system.estimator

    def unpacked(config):
        est = real(config)
        est.params["packed_codes"] = False
        return est
    monkeypatch.setattr(system, "estimator", unpacked)
    with pytest.raises(RuntimeError, match="packed codes not enabled"):
        one_step("h2o_defaults.train")
