"""Friedman-Popescu H statistic for tree ensembles.

Reference: h2o-algos/src/main/java/hex/tree/FriedmanPopescusH.java —
H (Friedman & Popescu 2008, Ann. Appl. Stat. 2:916-954 s.8.1) tests for
an interaction among a set of variables in a tree ensemble:

  H^2 = sum_u c_u [ sum_{S subseteq V, S != {}} (-1)^{|V|-|S|} F_S(u) ]^2
        / sum_u c_u F_V(u)^2

evaluated over the unique rows u (with counts c_u) of the training
frame's V-columns, where F_S is the CENTERED partial dependence of the
ensemble on the variable subset S (FriedmanPopescusH.computeFValues:
count-weighted mean subtracted). For |V|=2 the inner sum is
F_{12} - F_1 - F_2: zero when the model is additive in the two
variables. H = sqrt(H^2) when numerator < denominator, else NaN (weak
main effects + rounding spoil the ratio — same rule as computeHValue).

Partial dependence is computed directly on the tree structure
(FriedmanPopescusH.partialDependenceTree, Friedman's weighted-traversal
algorithm): splits on a variable in S route the whole weight by the
grid value; splits on complement variables send cover-proportional
weight (node_w children ratio) down BOTH branches. Vectorized here over
all grid rows at once per tree: a [n_u, M] weight matrix walked in heap
order — no per-row stack, one numpy pass per tree.
"""
from itertools import combinations
from typing import List, Sequence

import numpy as np

__all__ = ["friedman_popescu_h"]


def _pd_tree(Vs: np.ndarray, pos_of_feat: dict, feat, thr, na_left,
             is_split, node_w, value, max_depth: int) -> np.ndarray:
    """Partial dependence of ONE tree on the features in `pos_of_feat`
    (model feature id -> column of Vs), evaluated at grid rows Vs."""
    n_u = Vs.shape[0]
    M = feat.shape[0]
    first_bottom = 2 ** max_depth - 1       # depth-D nodes cannot split
    Wt = np.zeros((n_u, M), np.float64)
    Wt[:, 0] = 1.0
    out = np.zeros(n_u, np.float64)
    for m in range(M):
        w = Wt[:, m]
        if not np.any(w):
            continue
        if m >= first_bottom or not is_split[m]:
            out += w * float(value[m])
            continue
        l, r = 2 * m + 1, 2 * m + 2
        f = int(feat[m])
        if f in pos_of_feat:
            x = Vs[:, pos_of_feat[f]]
            # same routing as predict_raw_stacked (models/tree.py):
            # NaN goes by na_left, else right iff x >= thr
            go_right = np.where(np.isnan(x), not bool(na_left[m]),
                                x >= float(thr[m])).astype(np.float64)
            Wt[:, r] += w * go_right
            Wt[:, l] += w * (1.0 - go_right)
        else:
            wl, wr = float(node_w[l]), float(node_w[r])
            tot = wl + wr
            frac = wl / tot if tot > 0 else 1.0
            Wt[:, l] += w * frac
            Wt[:, r] += w * (1.0 - frac)
    return out


def _pd_ensemble(Vs, pos_of_feat, feat, thr, na_left, is_split, node_w,
                 value, max_depth: int, tree_scale) -> np.ndarray:
    T = feat.shape[0]
    out = np.zeros(Vs.shape[0], np.float64)
    for t in range(T):
        out += _pd_tree(Vs, pos_of_feat, feat[t], thr[t], na_left[t],
                        is_split[t], node_w[t], value[t], max_depth)
    if tree_scale is not None:
        out *= float(tree_scale)
    return out


def friedman_popescu_h(model, frame, variables: Sequence[str]) -> float:
    """H statistic of `variables` for a stacked-tree model (GBM/DRF/
    XGBoost-compat). 0 = no interaction; NaN when numer >= denom."""
    from h2o3_tpu.models.model_base import adapt_test_matrix

    names: List[str] = list(model.feature_names)
    variables = list(variables)
    if len(variables) < 2:
        raise ValueError("H statistic needs at least 2 variables")
    missing = [v for v in variables if v not in names]
    if missing:
        raise ValueError(f"variables not in model features: {missing}")
    if getattr(model, "nclasses", 1) > 2:
        raise ValueError("H statistic supports regression and binomial "
                         "models only")
    if getattr(model, "_node_w", None) is None:
        raise ValueError("this model artifact predates contributions "
                         "support (no per-node cover weights); retrain")
    fids = [names.index(v) for v in variables]
    X = np.asarray(adapt_test_matrix(model, frame), np.float64)
    X = X[: frame.nrow]
    V = X[:, fids]                                       # [n, k]
    uniq, counts = np.unique(V, axis=0, return_counts=True)
    n = float(V.shape[0])
    k = len(fids)

    from h2o3_tpu.models.tree import refuse_set_splits
    refuse_set_splits(model, "the H statistic")
    feat = np.asarray(model._feat)
    thr = np.asarray(model._thr)
    na_left = np.asarray(model._na_left)
    is_split = np.asarray(model._is_split)
    node_w = np.asarray(model._node_w)
    value = np.asarray(model._value)
    scale = model._contrib_scale() if hasattr(model, "_contrib_scale") \
        else None

    inner = np.zeros(uniq.shape[0], np.float64)
    f_full = None
    for size in range(k, 0, -1):
        sign = (-1.0) ** (k - size)
        for sub in combinations(range(k), size):
            pos = {fids[j]: j for j in sub}              # feature id -> V col
            f_s = _pd_ensemble(uniq, pos, feat, thr, na_left, is_split,
                               node_w, value, int(model.max_depth), scale)
            f_s = f_s - float(counts @ f_s) / n          # centered
            inner += sign * f_s
            if size == k:
                f_full = f_s
    numer = float(counts @ (inner ** 2))
    denom = float(counts @ (f_full ** 2))
    return float(np.sqrt(numer / denom)) if numer < denom else float("nan")
