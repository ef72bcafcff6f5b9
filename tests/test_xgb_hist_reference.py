"""``H2OXGBoostEstimator(tree_method="hist")`` held to the plain reference
(``benchmark/harness/reference/xgb.py``) on the CPU: 4,096 rows x 28 with
NaNs, depth 6, 3 trees, the packed int16 W=256 path with its Pallas kernels
interpreted, float32 histograms (what ``histogram_precision='auto'`` gives
under 2**18 rows). Every tree is followed node by node by the check the
benchmark's cell uses (``harness/checks/xgb_train_follow.py``): covers exact,
edges bit-equal, node and leaf values, split regret, the hessian bound on a
split's children, the reported log-loss.
"""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import system  # noqa: E402
from harness.checks import xgb_train_follow as check  # noqa: E402
from harness.generators import higgs_shaped  # noqa: E402
from harness.runners import train as train_runner  # noqa: E402

ROWS, SEED = 4096, 29
# float32 program against the float32 HIGHEST reference; the widest sound
# reading of the four cases is beside each (this file, CPU)
LIMITS = {"cover_gap": 0.0, "edge_gap": 0.0,
          "node_value_gap": 5e-6,      # 3.2e-7
          "leaf_gap": 5e-6,            # 2.9e-7
          "split_regret": 2e-4,        # 1.1e-5: near-ties broken by f32 order
          "child_weight_gap": 1e-5,    # 0
          "logloss_gap": 1e-6}         # 6.0e-8


@pytest.fixture
def interpreted(monkeypatch):
    """The packed path on the CPU: kernels interpreted; NaNs planted in
    the generator both sides draw rows from."""
    monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    real = higgs_shaped.make

    def with_nans(seed, rows, padded, features, part=0):
        X, y = real(seed, rows, padded, features, part=part)
        rng = np.random.default_rng(seed)
        holes = jnp.asarray(rng.random((padded, features)) < 0.03)
        return jnp.where(holes, jnp.nan, X), y
    monkeypatch.setattr(higgs_shaped, "make", with_nans)


def cell_for(lam, alpha, mcw, ntrees=3):
    with open(os.path.join(BENCH, "configs", "xgb_h2o_hist_higgs.json")) as f:
        config = json.load(f)
    config["params"].update(ntrees=ntrees, reg_lambda=lam, reg_alpha=alpha,
                            min_child_weight=mcw)
    config["data"]["rows"] = ROWS
    return {"name": "xgb_hist.train", "config": config,
            "check": {"follow_trees": list(range(ntrees))}}


def train(cell, edit=None):
    import h2o3_tpu as h2o
    h2o.init()
    frame = system.build_frame(cell["config"], SEED)
    est = system.estimator(cell["config"])
    if edit:
        edit(est)
    est.train(y="label", training_frame=frame)
    pc = est.model.output["packed_codes"]
    assert (pc["enabled"], pc["W"], pc["dtype"]) == (True, 256, "int16"), pc
    assert pc["kernel"] == "binned_level_tpu_t" and pc["feature_block"] == 28
    tm = est.model.training_metrics
    return {**train_runner.shape(frame),
            "model": system.model_arrays(est.model),
            "reported": {"logloss": float(tm.logloss), "auc": float(tm.auc)}}


def over(numbers):
    return {n: v for n, v in numbers.items() if not v <= LIMITS[n]}


@pytest.mark.parametrize("lam,alpha,mcw", [(1.0, 0.0, 1.0), (0.0, 0.0, 1.0),
                                           (1.0, 0.5, 1.0), (1.0, 0.0, 50.0)])
def test_program_follows_the_reference(interpreted, lam, alpha, mcw):
    cell = cell_for(lam, alpha, mcw)
    product = train(cell)
    numbers = check.run(cell, product, SEED)
    assert not over(numbers), numbers
    assert product["model"]["is_split"].sum() > 20       # trees that grew


def test_min_child_weight_is_a_hessian_sum_not_a_row_count(interpreted):
    """At 50 the hessian bound (p(1-p) <= 1/4 a row) forbids splits that a
    bound of 50 ROWS keeps: the two readings grow different trees, and the
    reference refuses the row-count reading's."""
    cell = cell_for(1.0, 0.0, 50.0)
    by_hessian = train(cell)

    def row_count_reading(est):      # what the estimator did before PR 29
        est.params.update(min_rows=50.0, min_child_weight=0.0)
    by_rows = train(cell, edit=row_count_reading)
    a, b = by_hessian["model"], by_rows["model"]
    assert (a["is_split"] != b["is_split"]).any() or (a["thr"] != b["thr"]).any()
    assert b["is_split"].sum() > a["is_split"].sum()     # it keeps more splits
    assert not over(check.run(cell, by_hessian, SEED))
    refused = over(check.run(cell, by_rows, SEED))
    assert refused.get("child_weight_gap", 0) > 0.5, refused


def test_bfloat16_sums_are_told_apart(interpreted):
    """The reference in the program's place with its node sums made from
    gradients rounded to bfloat16, the precision below the float32 this
    size states: the tolerances above refuse it."""
    cell = cell_for(1.0, 0.0, 1.0)
    product = train(cell)
    numbers = check.run(cell, product, SEED, control="bf16")
    refused = over(numbers)
    assert {"node_value_gap", "leaf_gap"} <= set(refused), numbers
    assert min(refused["node_value_gap"], refused["leaf_gap"]) \
        > 10 * LIMITS["leaf_gap"]


@pytest.mark.parametrize("control,must_fail", [
    ("half_batch", "cover_gap"), ("bin_off_by_one", "split_regret"),
    ("last_step_dropped", "logloss_gap")])
def test_planted_faults_are_refused(interpreted, control, must_fail):
    cell = cell_for(1.0, 0.0, 1.0)
    numbers = check.run(cell, train(cell), SEED, control=control)
    assert must_fail in over(numbers), numbers
