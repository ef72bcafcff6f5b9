"""The tree trainers' one path decision (ISSUE 31): ``tree.tree_path`` as a
table, one case a branch, and the shared bin stage behind it.

Every "mode" below was read first off the parent's three copies of the
rule (``gbm._train_dense``, ``drf._train_impl``, ``gbm._train_streaming``)
by training tiny frames and reading ``model.output["packed_codes"]`` and
the model's edges; the trains at the end hold GBM, DRF and the streamed
driver to the table through ``prepare_tree_inputs``.
"""
from types import SimpleNamespace

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import memman
from h2o3_tpu.models.drf import H2ORandomForestEstimator
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.tree import (TreeConfig, adaptive_feasible,
                                  packed_bins_upper_bound,
                                  packed_codes_requested, tree_config,
                                  tree_path)


def _spec(F, card=0):
    """What the rule reads of a TrainingSpec: F numeric features, and one
    categorical of ``card`` levels more where asked."""
    names = [f"x{i}" for i in range(F)] + (["c0"] if card else [])
    return SimpleNamespace(
        names=names, n_features=len(names),
        is_cat=[False] * F + ([True] if card else []),
        cat_domains={"c0": [f"k{i}" for i in range(card)]} if card else {})


# id: (params, interpret, random_is_adaptive, F, card, depth,
#      bins the sketch finds, mode before the sketch, mode after it)
TABLE = {
    "auto-on-cpu": ({}, False, True, 6, 0, 3, None, "adaptive", "adaptive"),
    "auto-under-interpret": ({}, True, True, 6, 0, 3, 20, "packed", "packed"),
    "packed-true": ({"packed_codes": True}, False, True, 6, 0, 3, 20,
                    "packed", "packed"),
    "packed-false": ({"packed_codes": False}, True, True, 6, 0, 3, None,
                     "adaptive", "adaptive"),
    "random-gbm": ({"packed_codes": True, "histogram_type": "random"},
                   False, True, 6, 0, 3, None, "adaptive", "adaptive"),
    "random-drf": ({"packed_codes": True, "histogram_type": "random"},
                   False, False, 6, 0, 3, 20, "sketch", "sketch"),
    # 300 levels want 300 identity bins: no lane width holds them, and
    # the domains alone say so before any sketch
    "categorical-past-254": ({"packed_codes": True}, False, True, 6, 300, 3,
                             None, "adaptive", "adaptive"),
    "categorical-grouped-by-nbins-cats": (
        {"packed_codes": True, "nbins_cats": 64}, False, True, 6, 300, 3,
        64, "packed", "packed"),
    # 2 x [3 * 2^10, 28 * 256] f32 = 176 MB: neither kernel's level fits
    "depth-11-f28-w256": ({"packed_codes": True, "nbins": 254}, False, True,
                          28, 0, 11, 254, "sketch", "sketch"),
    "depth-10-f28-w256": ({"packed_codes": True, "nbins": 254}, False, True,
                          28, 0, 10, 254, "packed", "packed"),
    "nbins-300": ({"packed_codes": True, "nbins": 300}, False, True, 6, 0, 3,
                  300, "sketch", "sketch"),
    # a bound that cannot pack and a sketch whose own count can (rows that
    # use fewer levels than the domain lists): packing stays on offer
    # through the sketch, which leaves the int32 operand out for it
    "bound-past-254-sketch-finds-fewer": (
        {"packed_codes": True, "histogram_type": "quantiles_global"}, False,
        True, 6, 300, 3, 199, "sketch", "packed"),
    "quantiles-global-packed": (
        {"packed_codes": True, "histogram_type": "quantiles_global"}, False,
        True, 6, 0, 3, 20, "packed", "packed"),
    "quantiles-global-unpacked": (
        {"packed_codes": False, "histogram_type": "quantiles_global"}, False,
        True, 6, 0, 3, 20, "sketch", "sketch"),
    "round-robin": ({"packed_codes": True, "histogram_type": "round_robin"},
                    False, True, 6, 0, 3, 20, "packed", "packed"),
}


@pytest.mark.parametrize("case", sorted(TABLE))
def test_tree_path_table(monkeypatch, case):
    params, interpret, ria, F, card, depth, found, before, after = TABLE[case]
    if interpret:
        monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("H2O3_PALLAS_INTERPRET", raising=False)
    params = {"nbins": 20, "nbins_cats": 1024, **params}
    spec = _spec(F, card)
    hist = params.get("histogram_type", "uniform_adaptive")

    def path(n_bins):
        return tree_path(hist, packed_codes_requested(params), n_bins,
                         spec.n_features, depth,
                         adaptive_fits=adaptive_feasible(spec, params, depth),
                         random_is_adaptive=ria)

    assert path(packed_bins_upper_bound(spec, params)) == before
    # "adaptive" before the sketch is final: no sketch runs
    assert (before if before == "adaptive" else path(found)) == after


@pytest.mark.parametrize("hist,requested,n_bins,fits,ria,want", [
    # nothing known of the bins yet: packing is still on offer
    ("uniform_adaptive", True, None, True, True, "packed"),
    ("random", True, None, True, True, "adaptive"),
    ("uniform_adaptive", False, None, True, True, "adaptive"),
    # the sketch's count past the lanes after a bound that fitted: the
    # fused adaptive kernel, not the matmul path
    ("uniform_adaptive", True, 300, True, True, "adaptive"),
    ("uniform_adaptive", True, 300, False, True, "sketch"),
    # the streamed driver's call (adaptive_fits=True): what does not pack
    # streams the f32 window
    ("quantiles_global", True, 300, True, True, "sketch"),
    ("random", False, 20, False, True, "sketch"),
    ("random", False, 20, True, False, "sketch"),
])
def test_tree_path_by_hand(hist, requested, n_bins, fits, ria, want):
    assert tree_path(hist, requested, n_bins, 6, 3, adaptive_fits=fits,
                     random_is_adaptive=ria) == want


@pytest.mark.parametrize("depth,lanes,want", [
    # per-feature lane widths (a frame with set features): 300 bins pack,
    # the 254-bin cap is the uniform layout's
    (10, 896, "packed"),
    # 2 x [3 * 2^9, 4096] f32 = 50 MB: a uniform W=512 would fit too
    (10, 4096, "packed"),
    # 2 x [3 * 2^12, 896] f32 = 88 MB fits, depth 14 does not
    (13, 896, "packed"), (14, 896, "adaptive")])
def test_tree_path_with_lane_widths(depth, lanes, want):
    assert tree_path("uniform_adaptive", True, 300, 8, depth,
                     adaptive_fits=True, lanes=lanes) == want


def test_tree_config_reads_every_objective_field_once():
    """The one builder: XGBoost's objective fields reach DRF's and the
    streamed driver's configs as they reach GBM's."""
    params = {"min_rows": 2.0, "min_split_improvement": 1e-4,
              "reg_lambda": 1.0, "reg_alpha": 0.5, "min_child_weight": 3.0,
              "col_sample_rate_change_per_level": 0.9,
              "hist_kernel": "scatter", "histogram_precision": "Float32"}
    assert tree_config(params, 4, 20, 6, mtries=2) == TreeConfig(
        max_depth=4, n_bins=20, n_features=6, min_rows=2.0,
        min_split_improvement=1e-4, reg_lambda=1.0, reg_alpha=0.5,
        min_child_weight=3.0, mtries=2, col_rate_change=0.9,
        hist_method="scatter", random_grid=False,
        histogram_precision="float32")
    bare = tree_config({"min_rows": 1.0, "min_split_improvement": 0.0,
                        "col_sample_rate_change_per_level": None}, 3, 14, 2)
    assert bare == TreeConfig(max_depth=3, n_bins=14, n_features=2,
                              min_rows=1.0, min_split_improvement=0.0)


# ------------------------------------------- the trainers, held to it


def _frame(n=1536, F=4, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(X[:, 0] - X[:, 1] + 0.3 * rng.normal(size=n) > 0,
                            "y", "n")
    return cols


_TINY = dict(ntrees=2, max_depth=2, seed=3, min_rows=1.0,
             score_tree_interval=0, stopping_rounds=0)
_PACKED_KEYS = {"enabled", "dtype", "W", "bytes_per_value", "n_bins",
                "kernel", "feature_block", "row_tile", "leaf_lookup",
                "n_nodes", "lanes", "lane_layout", "set_features",
                "level_hist", "acc_rows",
                # where the edges were made and the mesh the train ran
                # under, with what it all-reduced (PR 35)
                "sketch", "n_data", "n_model", "psum_bytes",
                # the columns the sketch sorted for their ranks (PR 36)
                "ranked_features"}
# the trees of this numeric frame as the commit before category-set splits
# (fe4f801) grew them: feat, na_left, is_split, and thr and value to four
# decimals
_BEFORE_SETS = {"gbm-packed": "36eefd2adffb9421",
                "drf-packed": "27ea00fd5716a892",
                "gbm-auto": "36eefd2adffb9421", "drf-auto": "970f9e7430b7c41e",
                "gbm-random": "ea8e6ebe3a4dde6a",
                "drf-random": "6e33ada796d665a4"}


def _digest(m):
    import hashlib
    h = hashlib.sha256()
    for a in (m._feat, m._na_left, m._is_split):
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    for a in (m._thr, m._value):
        h.update(np.round(np.asarray(a, np.float64), 4).tobytes())
    return h.hexdigest()[:16]


def _mode(model):
    if model.output["packed_codes"]["enabled"]:
        return "packed"
    return "adaptive" if len(model.edges) == 0 else "sketch"


@pytest.mark.parametrize("Est,params,want", [
    (H2OGradientBoostingEstimator, {"packed_codes": True}, "packed"),
    (H2ORandomForestEstimator, {"packed_codes": True}, "packed"),
    (H2OGradientBoostingEstimator, {}, "adaptive"),
    (H2ORandomForestEstimator, {}, "adaptive"),
    # the one semantic difference between the trainers' copies, kept as
    # prepare_tree_inputs' random_is_adaptive
    (H2OGradientBoostingEstimator,
     {"packed_codes": True, "histogram_type": "random"}, "adaptive"),
    (H2ORandomForestEstimator,
     {"packed_codes": True, "histogram_type": "random"}, "sketch"),
], ids=["gbm-packed", "drf-packed", "gbm-auto", "drf-auto", "gbm-random",
        "drf-random"])
def test_dense_trainers_take_the_tables_path(monkeypatch, request, Est,
                                            params, want):
    monkeypatch.delenv("H2O3_PALLAS_INTERPRET", raising=False)
    est = Est(**{**_TINY, **params})
    est.train(y="resp", training_frame=h2o.Frame.from_numpy(_frame()))
    assert _mode(est.model) == want
    pc = est.model.output["packed_codes"]
    # one record for every trainer: the level plan travels with it
    assert set(pc) == (_PACKED_KEYS if want == "packed" else {"enabled"})
    if want == "packed":
        assert (pc["kernel"], pc["feature_block"], pc["n_nodes"]) == (
            "binned_level_xla", 4, 7)
        # depth 2: a call of the deeper level accumulates one child of the
        # root; float32 histograms (auto on a small frame) build both
        assert (pc["level_hist"], pc["acc_rows"]) == ("both_children", 3)
        # a numeric frame keeps the uniform layout and thresholds
        assert (pc["lanes"], pc["lane_layout"], pc["set_features"]) == (
            4 * pc["W"], "uniform", 0)
    # ... and grows the trees it grew before set splits came in
    assert est.model._cat_set is None if Est is H2OGradientBoostingEstimator \
        else not hasattr(est.model, "_cat_set")
    assert _digest(est.model) == _BEFORE_SETS[
        request.node.callspec.id], "a numeric frame's trees changed"


@pytest.mark.parametrize("params,want", [
    ({"packed_codes": True}, "packed"),
    ({"packed_codes": True, "histogram_type": "random"}, "adaptive"),
    ({}, "adaptive")], ids=["packed", "random", "auto"])
def test_streamed_driver_asks_the_same_rule(monkeypatch, params, want):
    monkeypatch.delenv("H2O3_PALLAS_INTERPRET", raising=False)
    cols = _frame(n=6000)
    try:
        memman.reset(budget=int(2.2 * 6000 * 4 * 4))
        est = H2OGradientBoostingEstimator(**{**_TINY, **params})
        est.train(y="resp", training_frame=h2o.Frame.from_numpy(cols))
    finally:
        memman.reset()
    assert est.model.output.get("streamed")
    assert _mode(est.model) == want
