"""H2OXGBoostEstimator — the XGBoost parameter surface over the shared
tree machinery.

Reference: h2o-extensions/xgboost — XGBoostModel.java:124 (parameter
definitions), :253-293 (tree_method/backend selection), BoosterWrapper
JNI into libxgboost's hist/gpu_hist + Rabit allreduce.

TPU re-design: there is no JNI and no Rabit — the booster IS the JAX
histogram tree builder (ops/hist_adaptive.py fused kernel or the
global-sketch path), with the cross-shard psum standing in for the Rabit
ring (SURVEY §2.4). This class maps the XGBoost parameter names onto the
shared TreeConfig/GBM knobs:

  eta                  -> learn_rate          (default 0.3, XGBoost's)
  subsample            -> sample_rate
  colsample_bytree     -> col_sample_rate_per_tree
  colsample_bylevel    -> col_sample_rate
  max_bins             -> nbins = max_bins - 2 real bins. The default 256
                          is 254 real bins and the NA lane in a 256-lane
                          (W=256) int16 code: the packed level kernel's
                          widest shape (ops/hist_adaptive.py)
  min_split_improvement<- gamma
  reg_lambda (1.0)     -> L2 in gain and leaf values: G²/(H+λ), -η·T(G)/(H+λ)
                          (XGBoost default, not 0)
  reg_alpha            -> L1 soft-threshold T on G in gain and leaf values
  min_child_weight     -> TreeConfig.min_child_weight: each child's HESSIAN
                          sum must reach it, as XGBoost defines it (for a
                          bernoulli response a row's hessian is p(1-p) <=
                          1/4, so this is at least 4x a row count).
                          ``min_rows`` is H2O-3's alias for it, as in
                          XGBoostParameters: either spelling sets the
                          hessian bound, the two given with different
                          values raise, and no bound on a child's row
                          count remains (``params["min_rows"]`` is 0)
  tree_method hist/approx -> quantiles_global: one global quantile sketch,
                          features binned once into packed codes (exact
                          sorted quantiles, not XGBoost's weighted sketch)
  tree_method auto/exact  -> uniform_adaptive per-node histograms, 62 bins
  booster              -> gbtree only (dart/gblinear raise)

``f0`` is the GBM prior (the response's log-odds), not XGBoost's
``base_score=0.5``.
"""
from __future__ import annotations

from typing import Dict

from h2o3_tpu.models.gbm import GBM_DEFAULTS, H2OGradientBoostingEstimator

XGB_DEFAULTS: Dict = dict(
    ntrees=50, max_depth=6, eta=0.3, subsample=1.0, colsample_bytree=1.0,
    colsample_bylevel=1.0, max_bins=256, min_child_weight=1.0,
    gamma=0.0, reg_lambda=1.0, reg_alpha=0.0, tree_method="auto",
    booster="gbtree", distribution="auto", seed=-1, stopping_rounds=0,
    stopping_metric="auto", stopping_tolerance=1e-3, score_tree_interval=0,
)

_ALIAS = {
    "learn_rate": "eta",
    "sample_rate": "subsample",
    "col_sample_rate_per_tree": "colsample_bytree",
    "col_sample_rate": "colsample_bylevel",
}


class H2OXGBoostEstimator(H2OGradientBoostingEstimator):
    algo = "xgboost"
    # XGBoost has no category-set split: an enum column is its level index
    # and a threshold on it, which is what this estimator's ``auto`` means
    # (H2O-3's resolves to one-hot, which is not built)
    _SET_ENCODINGS = ()
    _ORDINAL_ENCODINGS = ("auto", "label_encoder", "labelencoder")

    def __init__(self, **params):
        booster = (params.get("booster",
                              XGB_DEFAULTS["booster"]) or "gbtree").lower()
        if booster not in ("gbtree",):
            raise NotImplementedError(
                f"booster='{booster}' is not implemented (gbtree only; "
                f"the reference's dart/gblinear come from libxgboost)")
        tm = (params.get("tree_method",
                         XGB_DEFAULTS["tree_method"]) or "auto").lower()
        hist = ("uniform_adaptive" if tm in ("auto", "exact")
                else "quantiles_global")

        def pick(*names, default):
            # user-supplied value wins under EITHER spelling; the XGBoost
            # default applies only when neither was given
            for nm in names:
                if nm in params:
                    return params[nm]
            return default

        if ("min_rows" in params and "min_child_weight" in params
                and params["min_rows"] != params["min_child_weight"]):
            raise ValueError(
                "min_rows is an alias of min_child_weight: "
                f"{params['min_rows']!r} and {params['min_child_weight']!r} "
                f"cannot both hold")
        max_bins = int(pick("max_bins", "nbins", default=256))
        gbm_params = dict(GBM_DEFAULTS)
        gbm_params.update(dict(
            ntrees=int(pick("ntrees", "n_estimators", default=50)),
            max_depth=int(pick("max_depth", default=6)),
            learn_rate=float(pick("eta", "learn_rate", default=0.3)),
            sample_rate=float(pick("subsample", "sample_rate", default=1.0)),
            col_sample_rate_per_tree=float(
                pick("colsample_bytree", "col_sample_rate_per_tree",
                     default=1.0)),
            col_sample_rate=float(
                pick("colsample_bylevel", "col_sample_rate", default=1.0)),
            # adaptive histograms recover resolution with depth, so
            # tree_method=auto uses 62 bins (W=64); explicit hist keeps
            # the full global-sketch bin budget
            nbins=(min(max_bins - 2, 62) if hist == "uniform_adaptive"
                   else min(max_bins - 2, 1022)),
            min_rows=0.0,
            min_child_weight=float(
                pick("min_child_weight", "min_rows", default=1.0)),
            min_split_improvement=float(
                pick("gamma", "min_split_improvement", default=0.0)),
            reg_lambda=float(pick("reg_lambda", default=1.0)),
            reg_alpha=float(pick("reg_alpha", default=0.0)),
            histogram_type=hist,
            distribution=params.get("distribution", "auto"),
            seed=params.get("seed", -1),
            stopping_rounds=params.get("stopping_rounds", 0),
            stopping_metric=params.get("stopping_metric", "auto"),
            stopping_tolerance=params.get("stopping_tolerance", 1e-3),
            score_tree_interval=params.get("score_tree_interval", 0),
        ))
        handled = (set(_ALIAS) | set(_ALIAS.values()) | set(XGB_DEFAULTS)
                   | {"n_estimators", "nbins", "min_rows",
                      "min_split_improvement"})
        for k, v in params.items():
            if k in gbm_params and k not in handled:
                gbm_params[k] = v
        super(H2OGradientBoostingEstimator, self).__init__(**gbm_params)
