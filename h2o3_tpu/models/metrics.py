"""ModelMetrics family — device-computed, host-materialised.

Reference: hex/ModelMetrics.java and subclasses (~30 classes), AUC via
hex/AUC2.java (400-bin threshold sketch), confusion matrices, gains/lift.
TPU design: metrics are one jitted pass over the (sharded) prediction and
actual arrays. The AUC curve is EXACT (device sort + host chord rule)
up to _EXACT_SWEEP_ROWS rows; above that it switches to an
order-preserving 2^17-bucket histogram sketch — 300x finer than AUC2's
400 bins but no longer bit-exact (golden tests at large n should allow
~1e-4 AUC tolerance).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------- regression

@jax.jit
def _regression_kernel(pred, actual, w):
    tot = w.sum()
    err = actual - pred
    mse = (w * err * err).sum() / tot
    mae = (w * jnp.abs(err)).sum() / tot
    both_pos = (actual >= 0) & (pred >= 0)
    sle = jnp.where(both_pos, (jnp.log1p(pred) - jnp.log1p(actual)) ** 2, 0.0)
    rmsle_ok = both_pos.all()
    rmsle = jnp.sqrt((w * sle).sum() / tot)
    mean_a = (w * actual).sum() / tot
    ss_tot = (w * (actual - mean_a) ** 2).sum()
    r2 = 1.0 - (w * err * err).sum() / jnp.maximum(ss_tot, 1e-30)
    return mse, mae, rmsle, rmsle_ok, r2, mean_a


@dataclass
class ModelMetricsHGLMGaussianGaussian:
    """HGLM gaussian/gaussian metrics — field-for-field analog of
    hex/ModelMetricsHGLMGaussianGaussian.java (sefe/sere per-coefficient
    standard errors, varfix/varranef dispersion components, the
    h-likelihood family hlik/pvh/pbvh and conditional AIC, plus the
    Σ(ηᵢ−η₀)²/Σηᵢ² convergence ratio of GLM.java:569)."""
    fixef: list
    ranef: list
    sefe: list
    sere: list
    varfix: float
    varranef: list
    hlik: float
    pvh: float
    pbvh: float
    caic: float
    dfrefe: float
    converge: bool
    convergence: float
    iterations: int
    mse: float
    nobs: int

    def to_dict(self) -> Dict:
        return {"fixef": self.fixef, "ranef": self.ranef,
                "sefe": self.sefe, "sere": self.sere,
                "varfix": self.varfix, "varranef": self.varranef,
                "hlik": self.hlik, "pvh": self.pvh, "pbvh": self.pbvh,
                "caic": self.caic, "dfrefe": self.dfrefe,
                "converge": self.converge,
                "convergence": self.convergence,
                "iterations": self.iterations,
                "MSE": self.mse, "nobs": self.nobs}


@dataclass
class ModelMetricsRegression:
    mse: float
    rmse: float
    mae: float
    rmsle: float
    r2: float
    mean_residual_deviance: float
    nobs: int

    def to_dict(self) -> Dict:
        return {"MSE": self.mse, "RMSE": self.rmse, "mae": self.mae,
                "rmsle": self.rmsle, "r2": self.r2,
                "mean_residual_deviance": self.mean_residual_deviance,
                "nobs": self.nobs}


def make_regression_metrics(pred, actual, weights=None, deviance=None) -> ModelMetricsRegression:
    pred = jnp.asarray(pred, dtype=jnp.float32)
    actual = jnp.asarray(actual, dtype=jnp.float32)
    w = jnp.ones_like(actual) if weights is None else jnp.asarray(weights, jnp.float32)
    mse, mae, rmsle, rmsle_ok, r2, _ = [np.asarray(v) for v in
                                        _regression_kernel(pred, actual, w)]
    mse = float(mse)
    return ModelMetricsRegression(
        mse=mse, rmse=float(np.sqrt(mse)), mae=float(mae),
        rmsle=float(rmsle) if bool(rmsle_ok) else float("nan"), r2=float(r2),
        mean_residual_deviance=float(deviance) if deviance is not None else mse,
        nobs=int(pred.shape[0]))


# ------------------------------------------------------------------ binomial

@jax.jit
def _sorted_sweep_kernel(score, y, w):
    """Device sort + cumulative TP/FP (small-n exact path). Boundary and
    chord-rule logic runs host-side in numpy: every scan-flavoured XLA
    primitive tried here (associative_scan, cummax, searchsorted) costs
    minutes of COMPILE time at 10M elements, while argsort+cumsum
    compile in ~2s — so the device does only those two."""
    order = jnp.argsort(-score)
    s = score[order]
    tp = jnp.cumsum((w * y)[order])
    fp = jnp.cumsum((w * (1.0 - y))[order])
    return s, tp, fp


_AUC_BIN_BITS = 17
_AUC_BINS = 1 << _AUC_BIN_BITS

# above this row count the curve switches from the exact sorted sweep to
# the 2^17-bucket histogram sketch (no O(n) host transfer either way)
_EXACT_SWEEP_ROWS = 200_000


def _bucket_sums_by_product(b, vp, vn):
    """Per-bucket sums of ``vp`` and ``vn`` ([_AUC_BINS] each) with no
    scatter: the bucket id split into a high and a low part (512 x 256),
    the sums one [rows, 512]^T x [rows, 2 * 256] product of two one-hots
    that fuse into the product's operands. The values go in as their
    three exact bfloat16 terms against a 0/1 one-hot, so every product is
    exact and the sums are f32 accumulations, as the scatter's are. On
    the TPU three scatters over the rows were all of a train's
    ``finalize_s`` (0.25 s at 10M rows, 0.84-1.03 s at 40M) and the part
    of it that moved from run to run (PERF.md section 6, PR 33)."""
    n_lo = 1 << (_AUC_BIN_BITS // 2)
    n_hi = _AUC_BINS // n_lo
    hi = (b // n_lo)[:, None] == jnp.arange(n_hi)[None, :]
    col = jnp.arange(2 * n_lo)[None, :]
    right = jnp.where((b % n_lo)[:, None] == col % n_lo,
                      jnp.where(col < n_lo, vp[:, None], vn[:, None]), 0.0)
    tot = jnp.zeros((n_hi, 2 * n_lo), jnp.float32)
    for _ in range(3):
        term = jax.lax.reduce_precision(right, 8, 7)   # not a cast: XLA
        tot = tot + jax.lax.dot_general(               # may elide one
            hi.astype(jnp.bfloat16), term.astype(jnp.bfloat16),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        right = right - term
    return tot[:, :n_lo].reshape(-1), tot[:, n_lo:].reshape(-1)


@jax.jit
def _binned_curve_kernel(score, y, w):
    """Large-n curve summary: order-preserving float32-bit bucketisation
    into 2^17 bins (the AUC2 sketch idea, hex/AUC2.java's 400 bins, at
    300x finer resolution) and per-bucket weighted positives and
    negatives: scatter-adds off the TPU, a factored one-hot product on it
    (:func:`_bucket_sums_by_product`). The third result is each bucket's
    LOWEST score, the threshold at which all of its rows count (computed
    from the bucket id, no pass over the rows). Nothing O(n) ever
    reaches the host."""
    s32 = score.astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(s32, jnp.uint32)
    # standard float radix trick: flip all bits for negatives, set the
    # sign bit for positives → unsigned keys in score order
    key = jnp.where((bits >> 31) == 1, ~bits,
                    bits | jnp.uint32(0x80000000))
    shift = 32 - _AUC_BIN_BITS
    b = (key >> shift).astype(jnp.int32)
    vp, vn = w * y, w * (1.0 - y)
    if jax.default_backend() == "tpu":
        hp, hn = _bucket_sums_by_product(b, vp, vn)
    else:
        hp = jnp.zeros(_AUC_BINS, jnp.float32).at[b].add(vp)
        hn = jnp.zeros(_AUC_BINS, jnp.float32).at[b].add(vn)
    edge_key = jnp.arange(_AUC_BINS, dtype=jnp.uint32) << shift
    edge = jax.lax.bitcast_convert_type(
        jnp.where((edge_key >> 31) == 1, edge_key & jnp.uint32(0x7FFFFFFF),
                  ~edge_key), jnp.float32)
    return hp, hn, edge


@jax.jit
def auc_device(score, y, w):
    """Scalar AUC entirely on device (the 2^17-bucket sketch + chord
    rule; empty buckets contribute zero-width chords so no occupancy
    filtering is needed). Used by the training loop's per-interval
    scoring so only ONE scalar crosses to the host — the previous
    interval-AUC path imported a kernel that no longer existed."""
    hp, hn, _ = _binned_curve_kernel(score, y, w)
    tp = jnp.cumsum(hp[::-1])
    fp = jnp.cumsum(hn[::-1])
    P, N = tp[-1], fp[-1]
    tp_prev = jnp.concatenate([jnp.zeros(1, tp.dtype), tp[:-1]])
    fp_prev = jnp.concatenate([jnp.zeros(1, fp.dtype), fp[:-1]])
    return ((fp - fp_prev) * (tp + tp_prev)).sum() * 0.5 \
        / jnp.maximum(P * N, 1e-30)


def _binary_curve(prob, y, w):
    """(sb, tpb, fpb, P, N, auc, aucpr): score thresholds (descending)
    with cumulative weighted TP/FP at tie-run boundaries, plus the
    chord-rule AUC and step-interpolated PR AUC. Exact for small n;
    quantised to 2^17 order-preserving buckets above _EXACT_SWEEP_ROWS."""
    n = int(prob.shape[0])
    if n <= _EXACT_SWEEP_ROWS:
        s, tp, fp = (np.asarray(v) for v in
                     _sorted_sweep_kernel(prob, y, w))
        is_b = np.concatenate([s[1:] != s[:-1], [True]])
        sb, tpb, fpb = s[is_b], tp[is_b], fp[is_b]
    else:
        hp, hn, edge = (np.asarray(v) for v in
                        _binned_curve_kernel(prob, y, w))
        occ = np.isfinite(edge) & ((hp > 0) | (hn > 0))
        # descending score order
        sb = edge[occ][::-1]
        tpb = np.cumsum(hp[occ][::-1])
        fpb = np.cumsum(hn[occ][::-1])
    P = float(tpb[-1]) if len(tpb) else 0.0
    N = float(fpb[-1]) if len(fpb) else 0.0
    tp_prev = np.concatenate([[0.0], tpb[:-1]])
    fp_prev = np.concatenate([[0.0], fpb[:-1]])
    auc = float(((fpb - fp_prev) * (tpb + tp_prev)).sum()
                * 0.5 / max(P * N, 1e-30))
    prec = tpb / np.maximum(tpb + fpb, 1e-30)
    rec = tpb / max(P, 1e-30)
    rec_prev = tp_prev / max(P, 1e-30)
    aucpr = float(((rec - rec_prev) * prec).sum())
    return sb, tpb, fpb, P, N, auc, aucpr


@jax.jit
def _logloss_kernel(p, y, w):
    eps = 1e-7  # f32-safe: 1-1e-15 rounds to 1.0f -> log1p(-1) = -inf
    p = jnp.clip(p, eps, 1.0 - eps)
    ll = -(w * (y * jnp.log(p) + (1.0 - y) * jnp.log1p(-p))).sum() / w.sum()
    return ll


@dataclass
class ModelMetricsBinomial:
    auc: float
    aucpr: float
    logloss: float
    mse: float
    rmse: float
    gini: float
    mean_per_class_error: float
    r2: float
    f1_threshold: float
    max_f1: float
    confusion_matrix: np.ndarray  # [[tn, fp], [fn, tp]] at max-F1 threshold
    accuracy: float
    nobs: int
    thresholds_and_metric_scores: Optional[dict] = None

    def to_dict(self) -> Dict:
        return {"AUC": self.auc, "pr_auc": self.aucpr, "logloss": self.logloss,
                "MSE": self.mse, "RMSE": self.rmse, "Gini": self.gini,
                "mean_per_class_error": self.mean_per_class_error, "r2": self.r2,
                "max_f1": self.max_f1, "f1_threshold": self.f1_threshold,
                "cm": self.confusion_matrix.tolist(), "accuracy": self.accuracy,
                "nobs": self.nobs}


def _threshold_columns(thr, tp, fp, P, N):
    """Per-threshold metric columns (hex/AUC2.java ThresholdCriterion set).

    tp/fp are cumulative weighted counts predicting positive at score >= thr."""
    fn = P - tp
    tn = N - fp
    tot = max(P + N, 1e-30)
    precision = tp / np.maximum(tp + fp, 1e-30)
    recall = tp / max(P, 1e-30)                       # tpr
    specificity = tn / max(N, 1e-30)                  # tnr
    fpr = fp / max(N, 1e-30)
    fnr = fn / max(P, 1e-30)
    accuracy = (tp + tn) / tot
    f1 = 2 * precision * recall / np.maximum(precision + recall, 1e-30)
    f2 = 5 * precision * recall / np.maximum(4 * precision + recall, 1e-30)
    f0point5 = (1.25 * precision * recall
                / np.maximum(0.25 * precision + recall, 1e-30))
    mcc_den = np.sqrt(np.maximum(
        (tp + fp) * (tp + fn) * (tn + fp) * (tn + fn), 1e-30))
    mcc = (tp * tn - fp * fn) / mcc_den
    min_pca = np.minimum(recall, specificity)
    mean_pca = 0.5 * (recall + specificity)
    return {
        "threshold": thr, "f1": f1, "f2": f2, "f0point5": f0point5,
        "accuracy": accuracy, "precision": precision, "recall": recall,
        "specificity": specificity, "absolute_mcc": np.abs(mcc),
        "min_per_class_accuracy": min_pca,
        "mean_per_class_accuracy": mean_pca,
        "tns": tn, "fns": fn, "fps": fp, "tps": tp,
        "tnr": specificity, "fnr": fnr, "fpr": fpr, "tpr": recall,
    }


_MAX_CRITERIA = ["f1", "f2", "f0point5", "accuracy", "precision", "recall",
                 "specificity", "absolute_mcc", "min_per_class_accuracy",
                 "mean_per_class_accuracy"]


def make_gains_lift(prob, actual, weights=None, groups=16) -> Optional[dict]:
    """Gains/lift table — hex/GainsLift.java semantics: sort by score desc,
    split into `groups` weight-quantile bins, report response rate / lift /
    cumulative capture & gain per bin, plus the Kolmogorov-Smirnov stat."""
    s = np.asarray(prob, dtype=np.float64)
    y = np.asarray(actual, dtype=np.float64)
    w = np.ones_like(y) if weights is None else np.asarray(weights, np.float64)
    order = np.argsort(-s, kind="stable")
    yw = (y * w)[order]
    wo = w[order]
    W = wo.sum()
    P = yw.sum()
    if P <= 0 or P >= W:
        return None  # single-class: table undefined (reference skips it too)
    cw = np.cumsum(wo)
    cy = np.cumsum(yw)
    # bin edges at weight quantiles (last row index with cw <= k*W/groups)
    edges = np.searchsorted(cw, W * np.arange(1, groups + 1) / groups,
                            side="left")
    edges = np.minimum(edges, len(cw) - 1)
    edges = np.unique(edges)
    cum_w = cw[edges]
    cum_y = cy[edges]
    lo_w = np.concatenate([[0.0], cum_w[:-1]])
    lo_y = np.concatenate([[0.0], cum_y[:-1]])
    grp_w = cum_w - lo_w
    grp_y = cum_y - lo_y
    overall_rate = P / W
    response_rate = grp_y / np.maximum(grp_w, 1e-30)
    lift = response_rate / overall_rate
    cum_rate = cum_y / np.maximum(cum_w, 1e-30)
    cum_lift = cum_rate / overall_rate
    capture = grp_y / P
    cum_capture = cum_y / P
    gain = 100.0 * (lift - 1.0)
    cum_gain = 100.0 * (cum_lift - 1.0)
    ks = np.max(np.abs(cy / P - (cw - cy) / (W - P)))
    return {
        "cumulative_data_fraction": (cum_w / W).tolist(),
        "lower_threshold": np.asarray(s[order][edges]).tolist(),
        "lift": lift.tolist(), "cumulative_lift": cum_lift.tolist(),
        "response_rate": response_rate.tolist(),
        "cumulative_response_rate": cum_rate.tolist(),
        "capture_rate": capture.tolist(),
        "cumulative_capture_rate": cum_capture.tolist(),
        "gain": gain.tolist(), "cumulative_gain": cum_gain.tolist(),
        "kolmogorov_smirnov": float(ks),
    }


def make_binomial_metrics(prob, actual, weights=None,
                          nobs: Optional[int] = None) -> ModelMetricsBinomial:
    """prob = P(class 1); actual ∈ {0,1}. ``nobs``: the observations
    among the rows, where zero-weight rows ride along (compute_metrics
    past the exact sweep's size); every row otherwise."""
    prob = jnp.asarray(prob, dtype=jnp.float32)
    y = jnp.asarray(actual, dtype=jnp.float32)
    w = jnp.ones_like(y) if weights is None else jnp.asarray(weights, jnp.float32)
    n = int(prob.shape[0]) if nobs is None else int(nobs)
    sb, tpb, fpb, Pf, Nf, auc, aucpr = _binary_curve(prob, y, w)
    ll = float(np.asarray(_logloss_kernel(prob, y, w)))
    reg = _regression_kernel(prob, y, w)
    mse = float(np.asarray(reg[0]))
    r2 = float(np.asarray(reg[4]))
    fnb = Pf - tpb; tnb = Nf - fpb
    prec = tpb / np.maximum(tpb + fpb, 1e-30)
    rec = tpb / max(Pf, 1e-30)
    f1 = 2 * prec * rec / np.maximum(prec + rec, 1e-30)
    bi = int(np.argmax(f1))
    cm = np.array([[tnb[bi], fpb[bi]], [fnb[bi], tpb[bi]]])
    per_class_err = 0.5 * (fpb[bi] / max(Nf, 1e-30) + fnb[bi] / max(Pf, 1e-30))
    acc = (tpb[bi] + tnb[bi]) / max(Pf + Nf, 1e-30)
    # thresholds_and_metric_scores: AUC2 caps the sweep at ~400 thresholds;
    # subsample boundaries evenly on the sorted-score axis to match.
    n_b = len(sb)
    if n_b > 400:
        keep = np.unique(np.round(np.linspace(0, n_b - 1, 400)).astype(int))
    else:
        keep = np.arange(n_b)
    table = _threshold_columns(sb[keep], tpb[keep], fpb[keep], Pf, Nf)
    table = {k: np.asarray(v).tolist() for k, v in table.items()}
    # max_criteria over the FULL boundary sweep (exact below
    # _EXACT_SWEEP_ROWS, 2^17-bucket resolution above — either way far
    # tighter than AUC2's 400 bins); idx points at the nearest KEPT table
    # row, matching the reference contract that idx indexes the table
    full = _threshold_columns(sb, tpb, fpb, Pf, Nf)
    max_crit = {}
    for c in _MAX_CRITERIA:
        i = int(np.argmax(full[c]))
        ti = int(np.searchsorted(keep, i))
        ti = min(ti, len(keep) - 1)
        max_crit[c] = {"threshold": float(sb[i]), "value": float(full[c][i]),
                       "idx": ti}
    table["max_criteria_and_metric_scores"] = max_crit
    table["gains_lift"] = _gains_lift_from_curve(sb, tpb, fpb, Pf, Nf)
    return ModelMetricsBinomial(
        auc=auc, aucpr=aucpr, logloss=ll, mse=mse, rmse=float(np.sqrt(mse)),
        gini=2 * auc - 1, mean_per_class_error=float(per_class_err), r2=r2,
        f1_threshold=float(sb[bi]), max_f1=float(f1[bi]), confusion_matrix=cm,
        accuracy=float(acc), nobs=n,
        thresholds_and_metric_scores=table)


def _gains_lift_from_curve(sb, tpb, fpb, Pf, Nf, groups: int = 16):
    """Gains/lift from the boundary curve (cum weight = tp+fp): same
    semantics as make_gains_lift without re-sorting the raw scores."""
    W = Pf + Nf
    if not (0.0 < Pf < W) or len(sb) == 0:
        return None
    cum_w = tpb + fpb
    edges = np.searchsorted(cum_w, W * np.arange(1, groups + 1) / groups,
                            side="left")
    edges = np.unique(np.minimum(edges, len(cum_w) - 1))
    cw = cum_w[edges]
    cy = tpb[edges]
    lo_w = np.concatenate([[0.0], cw[:-1]])
    lo_y = np.concatenate([[0.0], cy[:-1]])
    grp_w = np.maximum(cw - lo_w, 1e-30)
    grp_y = cy - lo_y
    rate = Pf / W
    return {
        "cumulative_data_fraction": (cw / W).tolist(),
        "lower_threshold": np.asarray(sb)[edges].tolist(),
        "lift": (grp_y / grp_w / rate).tolist(),
        "cumulative_lift": (cy / np.maximum(cw, 1e-30) / rate).tolist(),
        "response_rate": (grp_y / grp_w).tolist(),
        "cumulative_response_rate": (cy / np.maximum(cw, 1e-30)).tolist(),
        "capture_rate": (grp_y / Pf).tolist(),
        "cumulative_capture_rate": (cy / Pf).tolist(),
        "gain": (100.0 * (grp_y / grp_w / rate - 1.0)).tolist(),
        "cumulative_gain": (100.0 * (cy / np.maximum(cw, 1e-30)
                                     / rate - 1.0)).tolist(),
        "kolmogorov_smirnov": float(np.max(np.abs(
            tpb / max(Pf, 1e-30) - fpb / max(Nf, 1e-30)))),
    }


# --------------------------------------------------------------- multinomial

@jax.jit
def _multinomial_kernel(probs, y, w):
    """Full multinomial aggregate pass ON DEVICE: logloss, argmax error,
    confusion matrix, 1-vs-all MSE and the hit-position histogram all
    reduce to O(K²) outputs here, so finalize does ONE small device_get
    of aggregates and the O(n·K) probability matrix never crosses to the
    host (the old path fetched it three times: py gather, argsort ranks,
    and the OVR AUC table)."""
    eps = 1e-7  # f32-safe: 1-1e-15 rounds to 1.0f -> log1p(-1) = -inf
    rows = probs.shape[0]
    py = probs[jnp.arange(rows), y]
    ll = -(w * jnp.log(jnp.clip(py, eps, 1.0))).sum() / w.sum()
    pred = jnp.argmax(probs, axis=1)
    err = (w * (pred != y)).sum() / w.sum()
    K = probs.shape[1]
    cm = jnp.zeros((K, K), dtype=jnp.float32).at[y, pred].add(w)
    # 1-vs-all MSE (reference semantics: 1 - p_actual)
    mse = (w * (1.0 - py) ** 2).sum() / w.sum()
    # hit ratio @k: position of the true class in the per-row descending
    # sort (same jnp.argsort tie-breaking the host path used), histogram
    # over positions — the cumulative sum happens host-side on [K] floats
    ranks = jnp.argsort(-probs, axis=1)
    pos = jnp.argmax(ranks == y[:, None], axis=1)
    hitpos = jnp.zeros(K, jnp.float32).at[pos].add(1.0) / rows
    return ll, err, cm, mse, hitpos


@jax.jit
def _ovr_auc_kernel(probs, y, w):
    """One-vs-rest AUC/PR-AUC per class, entirely on device: each class
    column runs the 2^17-bucket order-preserving sketch (`auc_device`'s
    curve) and reduces to scalars — the fetch is 3·[K] floats however
    large n is. Empty buckets contribute zero-width chords (AUC) and
    zero-recall steps (PR), so no occupancy filtering is needed."""
    wtot = w.sum()

    def one_class(k):
        yk = (y == k).astype(jnp.float32)
        hp, hn, _ = _binned_curve_kernel(probs[:, k], yk, w)
        tp = jnp.cumsum(hp[::-1])
        fp = jnp.cumsum(hn[::-1])
        P, N = tp[-1], fp[-1]
        tp_prev = jnp.concatenate([jnp.zeros(1, tp.dtype), tp[:-1]])
        fp_prev = jnp.concatenate([jnp.zeros(1, fp.dtype), fp[:-1]])
        auc = ((fp - fp_prev) * (tp + tp_prev)).sum() * 0.5 \
            / jnp.maximum(P * N, 1e-30)
        prec = tp / jnp.maximum(tp + fp, 1e-30)
        rec = tp / jnp.maximum(P, 1e-30)
        rec_prev = tp_prev / jnp.maximum(P, 1e-30)
        aucpr = ((rec - rec_prev) * prec).sum()
        # degenerate-class weight directly, NOT the bucket cumsum: for a
        # single-class input w·yk == w elementwise, so this sum is
        # bit-equal to wtot and the >= guard below cannot be defeated by
        # the scatter-add's different accumulation order
        wk = (w * yk).sum()
        return auc, aucpr, wk

    K = probs.shape[1]
    per_auc, per_pr, prevalence = jax.vmap(one_class)(jnp.arange(K))
    # degenerate classes (no positives / no negatives under the weights)
    # have an undefined OVR AUC — mask to NaN on device like the host
    # path's wk<=0 / wk>=wtot guard
    bad = (prevalence <= 0) | (prevalence >= wtot)
    nan = jnp.float32(jnp.nan)
    return (jnp.where(bad, nan, per_auc), jnp.where(bad, nan, per_pr),
            prevalence)


@dataclass
class ModelMetricsMultinomial:
    logloss: float
    mse: float
    rmse: float
    mean_per_class_error: float
    error: float
    confusion_matrix: np.ndarray
    hit_ratios: np.ndarray
    nobs: int
    auc: Optional[float] = None          # macro one-vs-rest (MultinomialAUC)
    aucpr: Optional[float] = None
    auc_table: Optional[dict] = None     # per-class OVR auc/aucpr + averages

    def to_dict(self) -> Dict:
        return {"logloss": self.logloss, "MSE": self.mse, "RMSE": self.rmse,
                "mean_per_class_error": self.mean_per_class_error,
                "error": self.error, "cm": self.confusion_matrix.tolist(),
                "hit_ratios": self.hit_ratios.tolist(), "nobs": self.nobs,
                "AUC": self.auc, "pr_auc": self.aucpr}


def multinomial_auc_table(probs, y, w, max_classes=20) -> Optional[dict]:
    """One-vs-rest AUC per class + macro/weighted averages.

    Reference: hex/MultinomialAUC.java (default OVR). Skipped above
    `max_classes` (the reference gates this behind auc_type for memory).
    Computed on device via the 2^17-bucket sketch (``_ovr_auc_kernel``)
    so the fetch is 3·[K] scalars regardless of n — the old path pulled
    the whole probability matrix host-side and sorted each class column;
    sketch-vs-exact AUC deviation is bounded by the bucket quantisation
    (~1e-4, same contract as the binomial large-n path)."""
    probs = jnp.asarray(probs, jnp.float32)
    K = int(probs.shape[1])
    if K > max_classes:
        return None
    per_auc_d, per_pr_d, prev_d = _ovr_auc_kernel(
        probs, jnp.asarray(y, jnp.int32), jnp.asarray(w, jnp.float32))
    from h2o3_tpu import telemetry
    pa, pp, pv = telemetry.device_get((per_auc_d, per_pr_d, prev_d),
                                      pipeline="train")
    pa = np.asarray(pa, np.float64)
    pp = np.asarray(pp, np.float64)
    pv = np.asarray(pv, np.float64)
    pv = pv / max(pv.sum(), 1e-30)
    ok = ~np.isnan(pa)
    macro = float(pa[ok].mean()) if ok.any() else float("nan")
    weighted = float((pa[ok] * pv[ok]).sum() / max(pv[ok].sum(), 1e-30)) \
        if ok.any() else float("nan")
    macro_pr = float(pp[ok].mean()) if ok.any() else float("nan")
    weighted_pr = float((pp[ok] * pv[ok]).sum() / max(pv[ok].sum(), 1e-30)) \
        if ok.any() else float("nan")
    return {"per_class_auc": [float(v) for v in pa],
            "per_class_aucpr": [float(v) for v in pp],
            "macro_auc": macro, "weighted_auc": weighted,
            "macro_aucpr": macro_pr, "weighted_aucpr": weighted_pr}


def make_multinomial_metrics(probs, actual, weights=None) -> ModelMetricsMultinomial:
    """All aggregates computed on device; the host sees O(K²) numbers
    (confusion matrix, hit histogram, OVR AUC scalars) in two counted
    fetches — never the [n, K] probability matrix (transfer-budget
    guarded in tests/test_transfer_budget.py)."""
    probs = jnp.asarray(probs, dtype=jnp.float32)
    y = jnp.asarray(actual, dtype=jnp.int32)
    w = (jnp.ones(probs.shape[0], jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    from h2o3_tpu import telemetry
    ll, err, cm, mse, hitpos = telemetry.device_get(
        _multinomial_kernel(probs, y, w), pipeline="train")
    cm = np.asarray(cm)
    K = cm.shape[0]
    row_tot = cm.sum(axis=1)
    per_class = np.where(row_tot > 0, 1.0 - np.diag(cm) / np.maximum(row_tot, 1e-30), 0.0)
    present = row_tot > 0
    mpce = float(per_class[present].mean()) if present.any() else 0.0
    mse = float(mse)
    # hit ratio @k: cumulative share of rows whose true class ranks in
    # the top k (host cumsum over the [K] device histogram)
    hr = np.cumsum(np.asarray(hitpos, np.float64))[: min(K, 10)]
    auct = multinomial_auc_table(probs, y, w)
    return ModelMetricsMultinomial(
        logloss=float(ll), mse=mse, rmse=float(np.sqrt(mse)),
        mean_per_class_error=mpce, error=float(err),
        confusion_matrix=cm, hit_ratios=hr, nobs=int(probs.shape[0]),
        auc=None if auct is None else auct["macro_auc"],
        aucpr=None if auct is None else auct["macro_aucpr"],
        auc_table=auct)


# ------------------------------------------------------------------- anomaly

@dataclass
class ModelMetricsAnomaly:
    """hex/ModelMetricsAnomaly.java — score summary for IsolationForest."""
    mean_score: float
    mean_normalized_score: float
    nobs: int

    def to_dict(self) -> Dict:
        return {"mean_score": self.mean_score,
                "mean_normalized_score": self.mean_normalized_score,
                "nobs": self.nobs}


def make_anomaly_metrics(score, normalized_score) -> ModelMetricsAnomaly:
    s = np.asarray(score, np.float64)
    ns = np.asarray(normalized_score, np.float64)
    return ModelMetricsAnomaly(mean_score=float(s.mean()),
                               mean_normalized_score=float(ns.mean()),
                               nobs=int(s.shape[0]))


# ---------------- uplift (hex/AUUC.java + ModelMetricsBinomialUplift) ---

@dataclass
class ModelMetricsBinomialUplift:
    """hex/ModelMetricsBinomialUplift: the AUUC object with its
    threshold table and the qini/lift/gain flavors
    (hex/AUUC.java AUUCType)."""
    auuc: float                         # default-flavor AUUC (qini)
    auuc_normalized: float
    qini: float                         # Qini coefficient (area - random)
    ate: float                          # average treatment effect
    att: float                          # ATE on the treated
    atc: float                          # ATE on control
    auuc_table: Optional[dict] = None   # per-bin AUUC per flavor
    thresholds_and_metric_scores: Optional[dict] = None
    nobs: int = 0

    @property
    def auuc_normalized_(self):
        return self.auuc_normalized

    def to_dict(self):
        return {"AUUC": self.auuc, "auuc": self.auuc,
                "auuc_normalized": self.auuc_normalized,
                "qini": self.qini, "ate": self.ate, "att": self.att,
                "atc": self.atc, "nobs": self.nobs}


def make_uplift_metrics(uplift, y, treat, weights=None,
                        nbins: int = 1000) -> ModelMetricsBinomialUplift:
    """Full AUUC computation (hex/AUUC.java): rows ranked by predicted
    uplift, cumulative uplift at ``nbins`` thresholds, three flavors:
      qini:  cum_treat_y − cum_ctrl_y · n_t/n_c
      lift:  cum_treat_y/n_t − cum_ctrl_y/n_c
      gain:  lift · (n_t + n_c)
    AUUC = mean over bins of the chosen flavor's curve; normalized
    divides by the curve's final value (AUUC.java normalizedAUUC)."""
    uplift = np.asarray(uplift, np.float64)
    y = np.asarray(y, np.float64)
    treat = np.asarray(treat, np.float64)
    w = (np.ones_like(y) if weights is None
         else np.asarray(weights, np.float64))
    live = w > 0
    uplift, y, treat, w = uplift[live], y[live], treat[live], w[live]
    n = len(y)
    order = np.argsort(-uplift)
    u_s = uplift[order]
    wt = (w * treat)[order]
    wc = (w * (1 - treat))[order]
    wyt = (w * y * treat)[order]
    wyc = (w * y * (1 - treat))[order]
    nt = np.cumsum(wt)
    nc = np.cumsum(wc)
    cyt = np.cumsum(wyt)
    cyc = np.cumsum(wyc)
    qini_c = cyt - cyc * nt / np.maximum(nc, 1e-12)
    lift_c = cyt / np.maximum(nt, 1e-12) - cyc / np.maximum(nc, 1e-12)
    gain_c = lift_c * (nt + nc)
    idx = np.linspace(0, n - 1, min(nbins, n)).astype(int)
    flavors = {"qini": qini_c, "lift": lift_c, "gain": gain_c}
    aucs = {k: float(v[idx].mean()) for k, v in flavors.items()}
    finals = {k: float(v[-1]) if n else 0.0 for k, v in flavors.items()}
    norm = {k: (aucs[k] / finals[k] if abs(finals[k]) > 1e-12 else 0.0)
            for k in flavors}
    # random-targeting baseline for the Qini coefficient
    rand_area = 0.5 * finals["qini"]
    ate = (float(cyt[-1] / max(nt[-1], 1e-12)
                 - cyc[-1] / max(nc[-1], 1e-12)) if n else 0.0)
    # ATT/ATC: the model's PREDICTED uplift averaged over the treated /
    # control subpopulations (distinct estimands from the outcome-based
    # ATE above — hex/ModelMetricsBinomialUplift)
    wt_sum = float((w * treat).sum())
    wc_sum = float((w * (1 - treat)).sum())
    att = (float((w * treat * uplift).sum() / max(wt_sum, 1e-12))
           if n else 0.0)
    atc = (float((w * (1 - treat) * uplift).sum() / max(wc_sum, 1e-12))
           if n else 0.0)
    tbl = {
        "thresholds": [float(u_s[i]) for i in idx],
        "qini": [float(qini_c[i]) for i in idx],
        "lift": [float(lift_c[i]) for i in idx],
        "gain": [float(gain_c[i]) for i in idx],
        "n": [int(i + 1) for i in idx],
    }
    return ModelMetricsBinomialUplift(
        auuc=aucs["qini"], auuc_normalized=norm["qini"],
        qini=aucs["qini"] - rand_area, ate=ate, att=att, atc=atc,
        auuc_table={"flavors": aucs, "normalized": norm},
        thresholds_and_metric_scores=tbl, nobs=n)
