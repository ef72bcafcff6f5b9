"""KMeans / PCA / XGBoost-compat estimator tests — sklearn parity goldens
(VERDICT r3 tasks #5c and #7)."""
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models.kmeans import H2OKMeansEstimator
from h2o3_tpu.models.pca import H2OPrincipalComponentAnalysisEstimator
from h2o3_tpu.models.xgboost import H2OXGBoostEstimator


def test_kmeans_recovers_blobs():
    rng = np.random.default_rng(0)
    n = 3000
    centers = np.array([[0.0, 0.0], [6.0, 6.0], [-6.0, 6.0]])
    yv = rng.integers(0, 3, n)
    X = (centers[yv] + rng.normal(size=(n, 2))).astype(np.float32)
    fr = h2o.Frame.from_numpy({"x1": X[:, 0], "x2": X[:, 1]})
    km = H2OKMeansEstimator(k=3, max_iterations=20, seed=1,
                            standardize=False)
    km.train(training_frame=fr)
    C = np.sort(np.round(km.model.centers()).astype(int), axis=0)
    np.testing.assert_array_equal(C, np.sort(centers, axis=0).astype(int))
    # assignments agree with ground truth up to label permutation
    pred = km.model.predict(fr).vec("predict").to_numpy().astype(int)
    from scipy.optimize import linear_sum_assignment
    cm = np.zeros((3, 3))
    for a, b in zip(pred, yv):
        cm[a, b] += 1
    r, c = linear_sum_assignment(-cm)
    acc = cm[r, c].sum() / n
    assert acc > 0.99, acc


def test_kmeans_vs_sklearn_inertia():
    from sklearn.cluster import KMeans as SKKMeans
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 5)).astype(np.float32) * [1, 2, 3, 1, 1]
    cols = {f"x{i}": X[:, i] for i in range(5)}
    fr = h2o.Frame.from_numpy(cols)
    km = H2OKMeansEstimator(k=8, max_iterations=30, seed=2,
                            standardize=False)
    km.train(training_frame=fr)
    sk = SKKMeans(n_clusters=8, n_init=3, random_state=0).fit(X)
    # within 15% of sklearn's inertia (different init; same objective)
    assert km.model.tot_withinss < sk.inertia_ * 1.15, \
        (km.model.tot_withinss, sk.inertia_)


def test_kmeans_save_load(tmp_path):
    rng = np.random.default_rng(5)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    fr = h2o.Frame.from_numpy({f"x{i}": X[:, i] for i in range(3)})
    km = H2OKMeansEstimator(k=4, seed=1)
    km.train(training_frame=fr)
    p = h2o.save_model(km.model, str(tmp_path), filename="km")
    m2 = h2o.load_model(p)
    np.testing.assert_allclose(m2.centers(), km.model.centers(), rtol=1e-6)
    p1 = km.model.predict(fr).vec("predict").to_numpy()
    p2 = m2.predict(fr).vec("predict").to_numpy()
    np.testing.assert_array_equal(p1, p2)


def test_pca_matches_sklearn():
    from sklearn.decomposition import PCA as SKPCA
    rng = np.random.default_rng(7)
    n = 3000
    Z = rng.normal(size=(n, 2)).astype(np.float32)
    A = np.array([[1.0, 0.5, 0.1, 0.0], [0.0, 1.0, 0.5, 0.2]],
                 dtype=np.float32)
    X = Z @ A + 0.01 * rng.normal(size=(n, 4)).astype(np.float32)
    fr = h2o.Frame.from_numpy({f"x{i}": X[:, i] for i in range(4)})
    pca = H2OPrincipalComponentAnalysisEstimator(k=2, transform="demean")
    pca.train(training_frame=fr)
    sk = SKPCA(n_components=2).fit(X)
    # eigenvalues ≈ sklearn explained variance (ddof differences ~1/n)
    np.testing.assert_allclose(pca.model.eigval, sk.explained_variance_,
                               rtol=2e-2)
    # components match up to sign
    for j in range(2):
        ours = pca.model.eigvec[:, j]
        theirs = sk.components_[j]
        dot = abs(float(np.dot(ours, theirs)))
        assert dot > 0.999, (j, dot)
    # scores frame
    S = pca.model.predict(fr)
    assert S.names == ["PC1", "PC2"]
    sk_scores = sk.transform(X)
    got = np.stack([S.vec("PC1").to_numpy(), S.vec("PC2").to_numpy()], 1)
    for j in range(2):
        corr = np.corrcoef(got[:, j], sk_scores[:, j])[0, 1]
        assert abs(corr) > 0.999


def test_pca_importance_sums_to_one_with_all_components():
    rng = np.random.default_rng(9)
    X = rng.normal(size=(1000, 3)).astype(np.float32)
    fr = h2o.Frame.from_numpy({f"x{i}": X[:, i] for i in range(3)})
    pca = H2OPrincipalComponentAnalysisEstimator(k=3,
                                                 transform="standardize")
    pca.train(training_frame=fr)
    imp = pca.model.importance
    assert abs(imp["cumulative_proportion"][-1] - 1.0) < 1e-3


def test_xgboost_estimator_param_mapping():
    xgb = H2OXGBoostEstimator(ntrees=7, max_depth=4, eta=0.2, subsample=0.8,
                              colsample_bytree=0.7, reg_lambda=2.0,
                              reg_alpha=0.1, min_child_weight=3.0,
                              gamma=0.01, seed=5)
    p = xgb.params
    assert p["learn_rate"] == 0.2
    assert p["sample_rate"] == 0.8
    assert p["col_sample_rate_per_tree"] == 0.7
    assert p["reg_lambda"] == 2.0
    assert p["reg_alpha"] == 0.1
    # XGBoost's bound on a child's hessian sum, not a row count
    assert p["min_child_weight"] == 3.0 and p["min_rows"] == 0.0
    assert p["min_split_improvement"] == 0.01


@pytest.mark.parametrize("given,want", [
    ({}, 1.0), ({"min_rows": 5.0}, 5.0), ({"min_child_weight": 2.0}, 2.0),
    ({"min_rows": 4.0, "min_child_weight": 4.0}, 4.0),
    ({"min_rows": 10.0, "min_child_weight": 1.0}, None)])
def test_xgboost_min_rows_is_an_alias_of_min_child_weight(given, want):
    """Either spelling sets the hessian bound and no row-count bound is
    left; the two given with different values raise."""
    if want is None:
        with pytest.raises(ValueError, match="alias"):
            H2OXGBoostEstimator(**given)
        return
    p = H2OXGBoostEstimator(**given).params
    assert (p["min_child_weight"], p["min_rows"]) == (want, 0.0)
    assert "min_child_weight" not in H2OXGBoostEstimator()._compat_defaults


def test_xgboost_trains_binomial():
    rng = np.random.default_rng(11)
    n = 3000
    X = rng.normal(size=(n, 4)).astype(np.float32)
    logit = 2 * X[:, 0] - X[:, 1]
    yv = (rng.random(n) < 1 / (1 + np.exp(-logit))).astype(int)
    cols = {f"x{i}": X[:, i] for i in range(4)}
    cols["y"] = np.array(["n", "p"], dtype=object)[yv]
    fr = h2o.Frame.from_numpy(cols)
    xgb = H2OXGBoostEstimator(ntrees=30, max_depth=4, eta=0.3, seed=1)
    xgb.train(y="y", training_frame=fr)
    assert xgb.model.training_metrics.auc > 0.9
    # xgboost-style L2 default (reg_lambda=1.0) shrinks leaves vs GBM
    assert xgb.model.params["reg_lambda"] == 1.0


def test_xgboost_dart_raises():
    with pytest.raises(NotImplementedError):
        H2OXGBoostEstimator(booster="dart")


def test_xgboost_gbm_spelled_params_win():
    xgb = H2OXGBoostEstimator(learn_rate=0.05, sample_rate=0.6)
    assert xgb.params["learn_rate"] == 0.05
    assert xgb.params["sample_rate"] == 0.6


def test_pca_use_all_factor_levels():
    rng = np.random.default_rng(13)
    n = 500
    lv = np.array(["a", "b", "c"])
    cat = rng.integers(0, 3, n)
    fr = h2o.Frame.from_numpy({"c": lv[cat],
                               "x": rng.normal(size=n).astype(np.float32)})
    p1 = H2OPrincipalComponentAnalysisEstimator(k=2)
    p1.train(training_frame=fr)
    p2 = H2OPrincipalComponentAnalysisEstimator(k=2,
                                                use_all_factor_levels=True)
    p2.train(training_frame=fr)
    assert len(p1.model.exp_names) == 3   # c.b, c.c, x
    assert len(p2.model.exp_names) == 4   # c.a, c.b, c.c, x
    assert p2.model.predict(fr).nrow == n
