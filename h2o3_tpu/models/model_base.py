"""Model / ModelBuilder — the ML abstraction layer.

Reference: hex/ModelBuilder.java:25 (param validation, trainModel driver,
N-fold CV orchestration at :535-957) and hex/Model.java (Parameters/
Output, adaptTestForTrain categorical remap, BigScore bulk scoring
:1919-2176, per-row score0 contract :2304).

TPU re-design: the Driver/H2OCountedCompleter machinery collapses into a
plain call (optionally wrapped in a Job thread for REST); BigScore's
per-row score0 becomes one jitted batched predict over the sharded
feature matrix; adaptTestForTrain becomes domain remapping host-side when
building the test matrix.
"""
from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import T_ENUM, T_STR, Vec
from h2o3_tpu.jobs import Job
from h2o3_tpu import telemetry as _tel
from h2o3_tpu.models import metrics as metrics_mod


@dataclass
class TrainingSpec:
    """Resolved training inputs: dense device matrix + response/weights.

    The DataInfo analog (h2o-algos/.../hex/DataInfo.java:16) — but trees
    take enum codes directly (no one-hot); GLM/DL expand downstream."""
    X: Any                       # [padded, F] float32, NaN=NA (enum codes as floats)
    y: Any                       # [padded] float32 (reg) / int32 codes (classif)
    w: Any                       # [padded] float32 weights; 0 on pad/NA-response rows
    names: List[str]
    is_cat: List[bool]
    cat_domains: Dict[str, tuple]
    nrow: int
    response: str
    response_domain: Optional[tuple]
    nclasses: int                # 1 = regression
    offset: Any = None
    # memory-pressure mode (memman.fits_device said no): X stays on HOST
    # as float32 numpy and algorithms stream row chunks through training
    # (water/Cleaner.java graceful-degradation analog); X above is None
    X_host: Any = None
    stream: bool = False
    # how the response became an enum (Vec.factor's path; "none" where it
    # was one already or the train is a regression)
    response_factor: str = "none"

    @property
    def n_features(self) -> int:
        return len(self.names)


def build_training_spec(frame: Frame, y: str, x: Optional[Sequence[str]] = None,
                        ignored_columns: Optional[Sequence[str]] = None,
                        weights_column: Optional[str] = None,
                        offset_column: Optional[str] = None,
                        classification: Optional[bool] = None) -> TrainingSpec:
    if y not in frame:
        raise ValueError(f"response column '{y}' not in frame {frame.names}")
    excluded = {y} | set(ignored_columns or ())
    if weights_column:
        excluded.add(weights_column)
    if offset_column:
        excluded.add(offset_column)
    names = list(x) if x else [n for n in frame.names if n not in excluded]
    names = [n for n in names if n != y and frame.vec(n).type != T_STR]
    rvec = frame.vec(y)
    if classification is None:
        classification = rvec.type == T_ENUM
    response_factor = "none"
    if classification:
        # numeric response used as classification → derive domain
        # (Vec.factor: unique finite values → sorted domain, NaN → NA;
        # an enum is its own factor)
        rvec, response_factor = rvec.factor()
    # memory pressure gate (water/MemoryManager.java allocation gate):
    # a design matrix beyond the device budget stays on HOST and the
    # algorithms stream row chunks (X_host/stream mode)
    from h2o3_tpu import memman
    mm = memman.manager()
    est_bytes = (frame.nrow + 256) * max(len(names), 1) * 4
    # account for what's already resident (the frame's own Vec payloads
    # count): as_matrix is a fresh copy ON TOP of them
    stream = not mm.fits_device(est_bytes + mm.stats()
                                ["device_resident_bytes"])
    if not stream:
        mm.request(est_bytes)    # spill LRU peers to make room
    if stream:
        X = None
        X_host = _host_matrix(frame, names)
        # y/w stay device vectors at the VEC padded length
        padded = int(rvec.data.shape[0])
    else:
        X = frame.as_matrix(names)
        X_host = None
        padded = X.shape[0]
    is_cat = [frame.vec(n).type == T_ENUM for n in names]
    cat_domains = {n: frame.vec(n).domain for n in names
                   if frame.vec(n).type == T_ENUM}
    nrow = frame.nrow
    row_ok = jnp.arange(padded) < nrow
    if classification:
        yd = rvec.data.astype(jnp.int32)
        resp_ok = yd >= 0
        y_dev = jnp.maximum(yd, 0)
        nclasses = rvec.cardinality
        response_domain = rvec.domain
    else:
        yf = rvec.as_float()
        resp_ok = ~jnp.isnan(yf)
        y_dev = jnp.where(resp_ok, yf, 0.0)
        nclasses = 1
        response_domain = None
    w = jnp.where(row_ok & resp_ok, 1.0, 0.0).astype(jnp.float32)
    if weights_column:
        wv = frame.vec(weights_column).as_float()
        w = w * jnp.where(jnp.isnan(wv), 0.0, wv)
    offset = None
    if offset_column:
        ov = frame.vec(offset_column).as_float()
        offset = jnp.where(jnp.isnan(ov), 0.0, ov)
    return TrainingSpec(X=X, y=y_dev, w=w, names=names, is_cat=is_cat,
                        cat_domains=cat_domains, nrow=nrow, response=y,
                        response_domain=response_domain, nclasses=nclasses,
                        offset=offset, X_host=X_host, stream=stream,
                        response_factor=response_factor)


def build_parallelism(par: int) -> int:
    """Effective build-thread count for parallel CV/grid building.

    H2O3_MAX_BUILD_THREADS caps every build thread pool: on the
    virtual-device CPU test backend, many threads dispatching jitted
    train steps concurrently across oversubscribed xdist processes can
    abort() inside XLA — the suite pins the cap to 1 (conftest.py) and
    the dedicated concurrency tests raise it back. Unset/0 = no cap
    (TPU path: the device serializes dispatch, threads only overlap
    host orchestration + compiles)."""
    cap = int(os.environ.get("H2O3_MAX_BUILD_THREADS", "0") or 0)
    return min(par, cap) if cap > 0 else par


def _host_matrix(frame: Frame, names) -> np.ndarray:
    """Host-resident float32 design (as_matrix semantics: enum codes as
    floats, NA→NaN, string cols all-NaN) for streaming training."""
    nrow = frame.nrow
    out = np.empty((nrow, len(names)), np.float32)
    for j, n in enumerate(names):
        v = frame.vec(n)
        if v.type == T_STR:
            out[:, j] = np.nan
            continue
        a = v.to_numpy()
        if v.type == T_ENUM:
            a = np.where(np.asarray(a) < 0, np.nan,
                         np.asarray(a, np.float64))
        out[:, j] = np.asarray(a, np.float32)[:nrow]
    return out


def build_unsupervised_spec(frame: Frame, x: Optional[Sequence[str]] = None,
                            ignored_columns: Optional[Sequence[str]] = None,
                            weights_column: Optional[str] = None) -> TrainingSpec:
    """Spec for unsupervised builders (IsolationForest, KMeans, PCA…):
    no response column, y is a dummy zero vector."""
    excluded = set(ignored_columns or ())
    if weights_column:
        excluded.add(weights_column)
    names = list(x) if x else [n for n in frame.names if n not in excluded]
    names = [n for n in names if frame.vec(n).type != T_STR]
    X = frame.as_matrix(names)
    padded = X.shape[0]
    row_ok = jnp.arange(padded) < frame.nrow
    w = jnp.where(row_ok, 1.0, 0.0).astype(jnp.float32)
    if weights_column:
        wv = frame.vec(weights_column).as_float()
        w = w * jnp.where(jnp.isnan(wv), 0.0, wv)
    return TrainingSpec(
        X=X, y=jnp.zeros(padded, jnp.float32), w=w, names=names,
        is_cat=[frame.vec(n).type == T_ENUM for n in names],
        cat_domains={n: frame.vec(n).domain for n in names
                     if frame.vec(n).type == T_ENUM},
        nrow=frame.nrow, response=None, response_domain=None, nclasses=1)


def adapt_test_matrix(model: "Model", frame: Frame):
    """adaptTestForTrain (hex/Model.java): reorder columns to training
    order, remap enum codes through the training domain (unseen → NA),
    missing columns → all-NA."""
    return _adapt_matrix(frame, model.feature_names, model.feature_is_cat,
                         model.cat_domains)


def build_validation_spec(frame: Frame, train_spec: TrainingSpec,
                          weights_column=None, offset_column=None) -> TrainingSpec:
    """Validation/test spec ADAPTED to a training spec: columns in training
    order, enum codes remapped through the TRAINING domains (unseen → NA),
    response codes mapped through the training response domain. Building a
    fresh spec from the validation frame's own domains silently misroutes
    enum splits and class indices (adaptTestForTrain, hex/Model.java)."""
    X = _adapt_matrix(frame, train_spec.names, train_spec.is_cat,
                      train_spec.cat_domains)
    padded = X.shape[0]
    nrow = frame.nrow
    row_ok = np.arange(padded) < nrow
    if train_spec.response is None:
        return TrainingSpec(
            X=X, y=jnp.zeros(padded, jnp.float32),
            w=jnp.asarray(row_ok.astype(np.float32)),
            names=train_spec.names, is_cat=train_spec.is_cat,
            cat_domains=train_spec.cat_domains, nrow=nrow, response=None,
            response_domain=None, nclasses=1)
    if train_spec.nclasses > 1:
        codes, wr = response_codes_in_domain(frame, train_spec.response,
                                             train_spec.response_domain)
        y_dev = jnp.asarray(np.pad(codes, (0, padded - len(codes))))
        w = np.zeros(padded, np.float32)
        w[:nrow] = wr
    else:
        yf = np.asarray(_tel.device_get(
            frame.vec(train_spec.response).as_float(), pipeline="train"))
        resp_ok = np.isfinite(yf) & row_ok
        y_dev = jnp.asarray(np.where(resp_ok, yf, 0.0).astype(np.float32))
        w = resp_ok.astype(np.float32)
    if weights_column:
        if weights_column not in frame:
            raise ValueError(
                f"validation frame lacks weights_column '{weights_column}'")
        wv = np.asarray(_tel.device_get(
            frame.vec(weights_column).as_float(), pipeline="train"))
        w = w * np.where(np.isnan(wv), 0.0, wv)
    w = jnp.asarray(w)
    offset = None
    if offset_column:
        # an offset-trained model requires the offset at validation time —
        # silently dropping it would shift every margin (hex/Model.java
        # adaptTestForTrain raises)
        if offset_column not in frame:
            raise ValueError(
                f"validation frame lacks offset_column '{offset_column}'")
        ov = frame.vec(offset_column).as_float()
        offset = jnp.where(jnp.isnan(ov), 0.0, ov)
    return TrainingSpec(X=X, y=y_dev, w=w, names=train_spec.names,
                        is_cat=train_spec.is_cat,
                        cat_domains=train_spec.cat_domains, nrow=nrow,
                        response=train_spec.response,
                        response_domain=train_spec.response_domain,
                        nclasses=train_spec.nclasses, offset=offset)


def _adapt_matrix(frame: Frame, feature_names, feature_is_cat, cat_domains):
    cols = []
    padded = None
    for n, is_cat in zip(feature_names, feature_is_cat):
        if n not in frame:
            cols.append(None)
            continue
        v = frame.vec(n)
        if is_cat and v.type == T_ENUM:
            train_dom = cat_domains.get(n)
            if train_dom and v.domain != train_dom:
                lut = {lab: i for i, lab in enumerate(train_dom)}
                remap = np.array([lut.get(lab, -1) for lab in v.domain] + [-1],
                                 dtype=np.int32)
                codes = np.asarray(_tel.device_get(v.data, pipeline="score"))
                codes = remap[np.where(codes < 0, len(v.domain), codes)]
                v = Vec.from_numpy(codes[: v.nrow], vtype=T_ENUM, domain=train_dom)
        cols.append(v.as_float())
        padded = cols[-1].shape[0]
    if padded is None:
        raise ValueError("test frame shares no columns with the model")
    cols = [jnp.full(padded, jnp.nan, dtype=jnp.float32) if c is None else c
            for c in cols]
    return jnp.stack(cols, axis=1)


class ScoreKeeper:
    """Scoring history + convergence-based early stopping
    (hex/ScoreKeeper.java stopping_rounds/metric/tolerance semantics:
    stop when the moving average of the last k scores is no better than
    the previous k's by rel. tolerance)."""

    LESS_IS_BETTER = {"logloss", "mse", "rmse", "mae", "deviance",
                      "mean_per_class_error", "rmsle", "anomaly_score"}

    def __init__(self, stopping_rounds=0, stopping_metric="auto",
                 stopping_tolerance=1e-3, task="regression"):
        self.rounds = int(stopping_rounds or 0)
        metric = (stopping_metric or "auto").lower()
        if metric == "auto":
            metric = "logloss" if task in ("binomial", "multinomial") else "deviance"
        self.metric = metric
        self.tol = stopping_tolerance
        self.history: List[Dict] = []

    def record(self, entry: Dict):
        self.history.append(entry)

    def should_stop(self) -> bool:
        if self.rounds <= 0:
            return False
        k = self.rounds
        metric = self.metric
        if self.history and all(e.get(metric) is None for e in self.history):
            metric = "deviance"  # requested metric unavailable for this family
        scores = [e.get(metric) for e in self.history
                  if e.get(metric) is not None]
        if metric == "deviance" and self.metric != "deviance":
            return self._stop_on(scores, k, less_is_better=True)
        return self._stop_on(scores, k,
                             less_is_better=metric in self.LESS_IS_BETTER)

    def _stop_on(self, scores, k, less_is_better):
        if len(scores) < 2 * k:
            return False
        recent = np.mean(scores[-k:])
        prev = np.mean(scores[-2 * k:-k])
        # relative-improvement test with |prev| scaling — robust to metrics
        # that cross zero (the old sign trick inverted the band there)
        margin = self.tol * abs(prev)
        if less_is_better:
            return recent >= prev - margin
        return recent <= prev + margin


# reference param surfaces carrying the class-balancing trio and the
# calibration trio (h2o-py generated estimators; enforced by the
# bindings diff in tests/test_bindings.py) — merged as REAL defaults in
# ModelBuilder.__init__ since both features are implemented generically
_BALANCE_DEFAULTS = dict(balance_classes=False,
                         class_sampling_factors=None,
                         max_after_balance_size=5.0)
_CALIBRATION_DEFAULTS = dict(calibrate_model=False,
                             calibration_frame=None,
                             calibration_method="auto")
_BALANCE_ALGOS = {"gbm", "drf", "deeplearning", "glm", "gam", "anovaglm",
                  "infogram", "modelselection", "naivebayes", "upliftdrf"}
_CALIBRATION_ALGOS = {"gbm", "drf", "xgboost"}


class Model:
    """Trained artifact. Subclasses implement _predict_matrix(X)."""

    algo = "base"

    def __init__(self, key: str, params: Dict, spec: TrainingSpec):
        self.key = key
        self.params = dict(params)
        self.feature_names = list(spec.names)
        self.feature_is_cat = list(spec.is_cat)
        self.cat_domains = dict(spec.cat_domains)
        self.response = spec.response
        self.response_domain = spec.response_domain
        self.nclasses = spec.nclasses
        self.output: Dict[str, Any] = {}
        self.training_metrics = None
        self.validation_metrics = None
        self.cross_validation_metrics = None
        self.scoring_history: List[Dict] = []
        self.run_time: float = 0.0

    # -- scoring --------------------------------------------------------

    def _predict_matrix(self, X, offset=None):
        """Return margin/score array: [padded] for regression,
        [padded, K] class probabilities for classification."""
        raise NotImplementedError

    def _score_attrs(self, X) -> dict:
        """Attrs of predict's ``score.dispatch`` span: what a trace or
        /3/Timeline should say of the program ``_predict_matrix(X)`` runs."""
        return {}

    def _frame_offset(self, frame: Frame):
        """Offset vector for scoring. An offset-trained model requires the
        offset column at scoring time (adaptTestForTrain raises in the
        reference, hex/Model.java) — silently dropping it would shift every
        prediction."""
        oc = self.params.get("offset_column")
        if not oc:
            return None
        if oc not in frame:
            raise ValueError(
                f"model was trained with offset_column='{oc}' but the "
                f"scoring frame does not contain it")
        ov = frame.vec(oc).as_float()
        return jnp.where(jnp.isnan(ov), 0.0, ov)

    def _correct_probabilities(self, probs: np.ndarray) -> np.ndarray:
        """balance_classes probability un-correction (hex/Model
        correctProbabilities): p_k ∝ p̂_k · prior_k / model_dist_k, so
        a model trained on a rebalanced distribution reports
        probabilities calibrated to the ORIGINAL class priors."""
        prior_d = self.output.get("prior_class_dist")
        model_d = self.output.get("model_class_dist")
        if not prior_d or not model_d or probs.ndim != 2 \
                or probs.shape[1] != len(prior_d):
            return probs
        ratio = (np.asarray(prior_d, np.float64)
                 / np.maximum(np.asarray(model_d, np.float64), 1e-12))
        p = probs.astype(np.float64) * ratio[None, :]
        return (p / np.maximum(p.sum(axis=1, keepdims=True),
                               1e-12)).astype(probs.dtype)

    def predict(self, frame: Frame) -> Frame:
        """Bulk scoring → prediction Frame (BigScore analog). Output
        schema mirrors the reference: regression → 'predict'; classif →
        'predict' + one prob column per class.

        Spanned at the boundaries where the work stops: ``score.adapt``
        (host), ``score.dispatch`` (the enqueue of programs JAX has
        cached, and on a shape's first call their trace, lower and load:
        it returns before the device is done; attrs ``_score_attrs``:
        which form the scorer's program has), ``score.fetch`` (the wait
        for the device and the D2H) and ``score.frame`` (host, and the
        result columns' uploads)."""
        with _tel.span("score.predict", rows=frame.nrow, model=self.key):
            with _tel.span("score.adapt"):
                X = adapt_test_matrix(self, frame)
                offset = self._frame_offset(frame)
            with _tel.span("score.dispatch", **self._score_attrs(X)):
                out = self._predict_matrix(X, offset=offset)
            with _tel.span("score.fetch"):
                host = np.asarray(
                    _tel.device_get(out, pipeline="score"))[:frame.nrow]
            with _tel.span("score.frame"):
                return self._prediction_frame(host)

    def _prediction_frame(self, host: np.ndarray) -> Frame:
        """The fetched scores as the prediction Frame."""
        if self.nclasses <= 1:
            return Frame(["predict"], [Vec.from_numpy(host)])
        probs = self._correct_probabilities(host)
        lbl = np.argmax(probs, axis=1).astype(np.int32)
        names = ["predict"] + [f"p{d}" for d in self.response_domain]
        vecs = [Vec.from_numpy(lbl, vtype=T_ENUM, domain=self.response_domain)]
        vecs += [Vec.from_numpy(probs[:, k]) for k in range(self.nclasses)]
        cal = self.output.get("calibration")
        if cal and self.nclasses == 2:
            # calibrated probability columns (CalibrationHelper
            # postProcessPredictions appends cal_p0/cal_p1)
            p1 = np.clip(probs[:, 1].astype(np.float64), 1e-12, 1 - 1e-12)
            if cal["method"] == "platt":
                q1 = 1.0 / (1.0 + np.exp(-(cal["a"] * np.log(
                    p1 / (1 - p1)) + cal["b"])))
            else:
                q1 = np.interp(p1, np.asarray(cal["tx"]),
                               np.asarray(cal["ty"]))
            names += [f"cal_p{self.response_domain[0]}",
                      f"cal_p{self.response_domain[1]}"]
            vecs += [Vec.from_numpy((1.0 - q1).astype(np.float32)),
                     Vec.from_numpy(q1.astype(np.float32))]
        return Frame(names, vecs)

    def deploy(self, **serve_config):
        """Register this model with the serving subsystem
        (h2o3_tpu.serve): pre-encodes the column/domain spec and warms
        compiled predict executables at the batch-size buckets, then
        rows score through the micro-batcher — see
        POST /3/Predictions/models/{key}/rows. Returns the Deployment."""
        from h2o3_tpu import serve
        return serve.deploy(self.key, model=self, **serve_config)

    def predict_rows(self, rows, timeout_ms=None):
        """Score a list of {column: value} dicts through the deployed
        micro-batching path. Deploys with defaults on first use; an
        EXISTING deployment under this key is reused as-is — replacing
        a live (possibly pinned, custom-configured) deployment
        mid-traffic is deploy()'s explicit job, not a scoring
        side-effect."""
        from h2o3_tpu import serve
        dep = serve.deployment(self.key) or self.deploy()
        return dep.predict_rows(rows, timeout_ms=timeout_ms)

    def model_performance(self, frame: Optional[Frame] = None):
        if frame is None:
            return self.training_metrics
        X = adapt_test_matrix(self, frame)
        out = self._predict_matrix(X, offset=self._frame_offset(frame))
        nrow = frame.nrow
        if self.nclasses > 1:
            # remap the test response through the TRAINING domain — a fresh
            # spec would re-derive codes from the test frame's own label set
            # (adaptTestForTrain semantics, hex/Model.java)
            y, w = response_codes_in_domain(frame, self.response,
                                            self.response_domain)
            out_h = self._correct_probabilities(
                np.asarray(_tel.device_get(out, pipeline="score"))[:nrow])
            return compute_metrics(out_h, y, w, self.nclasses, self.response_domain)
        spec_like = build_training_spec(frame, self.response, classification=False)
        return compute_metrics(out, spec_like.y, spec_like.w, 1)

    # -- persistence hooks (persist.save_model/load_model) -------------

    def _save_arrays(self) -> Dict[str, np.ndarray]:
        """Per-algo tensors to persist (trees, coefficients, weights…)."""
        return {}

    def _save_extra_meta(self) -> Dict[str, Any]:
        """Per-algo JSON metadata to persist."""
        return {}

    @classmethod
    def _restore_base(cls, meta) -> "Model":
        """Rebuild the base Model state from artifact metadata (subclass
        _restore() fills algo-specific fields)."""
        m = cls.__new__(cls)
        m.key = meta["key"]
        m.params = dict(meta["params"] or {})
        m.feature_names = list(meta["feature_names"])
        m.feature_is_cat = list(meta["feature_is_cat"])
        m.cat_domains = {k: tuple(v) for k, v in
                         (meta.get("cat_domains") or {}).items()}
        m.response = meta["response"]
        rd = meta.get("response_domain")
        m.response_domain = tuple(rd) if rd else None
        m.nclasses = meta["nclasses"]
        m.output = dict(meta.get("output") or {})
        m.training_metrics = None
        m.validation_metrics = None
        m.cross_validation_metrics = None
        m.scoring_history = []
        m.run_time = 0.0
        return m

    @classmethod
    def _restore(cls, meta, arrays) -> "Model":
        raise NotImplementedError(f"{cls.__name__} does not support load yet")

    # -- convenience accessors (h2o-py parity) -------------------------

    def _metric(self, name, valid=False):
        m = self.validation_metrics if valid else self.training_metrics
        return getattr(m, name, None)

    def download_mojo(self, path: str = ".", get_genmodel_jar: bool = False):
        """Export as an h2o-genmodel-readable MOJO zip (tree models)."""
        import os
        from h2o3_tpu.mojo import export_mojo
        if os.path.isdir(path):
            path = os.path.join(path, f"{self.key}.zip")
        return export_mojo(self, path)

    def auc(self, valid=False):
        return self._metric("auc", valid)

    def logloss(self, valid=False):
        return self._metric("logloss", valid)

    def rmse(self, valid=False):
        return self._metric("rmse", valid)

    def mse(self, valid=False):
        return self._metric("mse", valid)

    def mae(self, valid=False):
        return self._metric("mae", valid)

    def r2(self, valid=False):
        return self._metric("r2", valid)

    def __repr__(self):
        return f"<{type(self).__name__} {self.key} {self.params.get('model_id', '')}>"


def persist_in_training_ckpt(model, algo: str, ckpt_dir,
                             final: bool = False) -> Optional[str]:
    """Persist an in-training checkpoint model to the DKV
    (``<key>_ckpt``) and to ``in_training_checkpoints_dir`` (one
    artifact per committed tree count — hex/tree/SharedTree's
    in_training_checkpoints_* contract). The caller attaches the
    algo-specific resume state (GBM: the f32 training margin; DRF: the
    OOB accumulators) before calling. ``final=True`` (a train that
    COMPLETED) keeps the durable disk artifact but drops the DKV entry
    — the finished model supersedes it, and leaving partial-model
    copies (with dataset-sized resume margins) to accumulate in the
    store would both leak memory and surface phantom models on
    GET /3/Models. Failures are logged, never fatal: a checkpoint
    write must not kill the train it protects."""
    import os as _os

    from h2o3_tpu import dkv, telemetry
    from h2o3_tpu.persist import save_model
    try:
        if final:
            dkv.remove(f"{model.key}_ckpt")
        else:
            dkv.put(f"{model.key}_ckpt", "model", model)
        path = None
        if ckpt_dir:
            _os.makedirs(ckpt_dir, exist_ok=True)
            path = save_model(
                model, ckpt_dir, force=True,
                filename=f"{model.key}_t{model.ntrees_built}.zip")
        telemetry.counter(
            "h2o3_ckpt_written_total", {"algo": algo},
            help="in-training checkpoints written").inc()
        return path
    except Exception as e:   # noqa: BLE001 — advisory only
        from h2o3_tpu.log import warn
        warn("%s: in-training checkpoint write failed: %s", algo, e)
        return None


def pack_impute_means(means) -> Dict[str, np.ndarray]:
    """npz-safe encoding of the {column: imputation mean} dict shared by
    the expanded-design models (GLM/DL/KMeans/PCA)."""
    return {"impute_keys": np.array(list(means.keys())),
            "impute_vals": np.array(list(means.values()), dtype=np.float64)}


def unpack_impute_means(arrays) -> Dict[str, float]:
    return {str(k): float(v) for k, v in
            zip(arrays["impute_keys"], arrays["impute_vals"])}


def response_codes_in_domain(frame: Frame, response: str, domain):
    """Test-frame response codes mapped through a training domain
    (labels unseen in training → NA/zero-weight)."""
    v = frame.vec(response)
    if v.type == T_ENUM:
        labels = v.to_strings()
    else:
        raw = v.to_numpy()
        labels = np.array([None if not np.isfinite(x)
                           else (str(int(x)) if x == int(x) else str(x))
                           for x in raw], dtype=object)
    lut = {lab: i for i, lab in enumerate(domain)}
    codes = np.array([lut.get(l, -1) if l is not None else -1 for l in labels],
                     dtype=np.int32)
    w = (codes >= 0).astype(np.float32)
    return np.maximum(codes, 0), w


def compute_metrics(scores, y, w, nclasses, response_domain=None,
                    deviance=None):
    """Dispatch to the right ModelMetrics maker, masking pad rows by w>0.

    The mask stays ON DEVICE: the old path device_get the full score
    matrix (80MB at 10M×2) just to drop pad rows before re-uploading it
    into the metric kernels — at bench scale that fetch dominated warm
    train time. When every row is live (the common padded==nrow case)
    the arrays pass through untouched. A binomial frame past the exact
    sweep's size passes through too: its kernels (the 2^17-bucket curve
    sketch, log-loss, MSE) weigh every term by ``w``, so a zero-weight
    row adds nothing, and the compaction it replaces is a gather over the
    rows (10 ns a row on a TPU, and on a data-sharded mesh the rows of
    every shard brought to every chip: 123.5M rows with 7 pad rows paid
    it in full). Otherwise one device gather compacts them. Only kernel
    outputs (scalars / 2^17-bin curve summaries) ever cross to the
    host."""
    w_d = jnp.asarray(w)
    live = w_d > 0
    n_live = int(live.sum())
    scores_d = jnp.asarray(scores)
    y_d = jnp.asarray(y)
    weighed = (nclasses == 2
               and scores_d.shape[0] > metrics_mod._EXACT_SWEEP_ROWS)
    if n_live < live.shape[0] and not weighed:
        idx = jnp.nonzero(live)[0]
        scores_d = jnp.take(scores_d, idx, axis=0)
        y_d = jnp.take(y_d, idx, axis=0)
        w_d = jnp.take(w_d, idx, axis=0)
    if nclasses <= 1:
        return metrics_mod.make_regression_metrics(
            scores_d, y_d, w_d, deviance=deviance)
    if nclasses == 2:
        return metrics_mod.make_binomial_metrics(scores_d[:, 1], y_d, w_d,
                                                 nobs=n_live)
    return metrics_mod.make_multinomial_metrics(scores_d, y_d, w_d)


class ModelBuilder:
    """Base trainer with the reference's train/CV orchestration shape."""

    algo = "base"
    supervised = True
    model_count = 0
    # algos with a host-chunked memory-pressure path (spec.stream);
    # others fail fast with guidance instead of crashing on spec.X=None
    supports_streaming = False

    def __init__(self, **params):
        # reference-parity parameters this backend accepts but does not
        # act on (generated by tools/gen_python.py --wire): they keep the
        # generated-bindings/clients' full signatures working; train()
        # warns whenever one is set away from its reference default so
        # nothing is silently ignored
        try:
            from h2o3_tpu.models.compat_params import COMPAT_PARAMS
            compat = COMPAT_PARAMS.get(self.algo, {})
        except ImportError:
            compat = {}
        self._compat_defaults = compat
        merged = {k: v for k, v in compat.items() if k not in params}
        if self.algo in _BALANCE_ALGOS:
            for k, v in _BALANCE_DEFAULTS.items():
                merged.setdefault(k, v)
        if self.algo in _CALIBRATION_ALGOS:
            for k, v in _CALIBRATION_DEFAULTS.items():
                merged.setdefault(k, v)
        merged.update(params)
        self.params = merged
        self.model: Optional[Model] = None

    def _model_key(self) -> str:
        """Key the trained model will carry. ``model_id`` wins when set
        (the reference's Model key naming; the restart-recovery resume
        passes the interrupted train's original key through it so the
        resumed checkpoints land under the same artifact names);
        otherwise the per-builder default."""
        mid = self.params.get("model_id")
        return str(mid) if mid else f"{self.algo}_{id(self) & 0xffffff:x}"

    def _warn_compat_params(self):
        from h2o3_tpu.log import warn
        for k, dflt in self._compat_defaults.items():
            if self.params.get(k) != dflt:
                warn(f"{self.algo}: parameter '{k}' is accepted for "
                     f"reference API compatibility but NOT implemented — "
                     f"value {self.params[k]!r} has no effect")

    # per-algo: build a model from a spec
    def _train_impl(self, spec: TrainingSpec, valid_spec: Optional[TrainingSpec],
                    job: Job) -> Model:
        raise NotImplementedError

    def _validate_calibration(self, spec: TrainingSpec) -> None:
        """Pre-train parameter validation for calibrate_model — all
        checks depend only on params + spec, so a bad combination must
        not cost a full training run (the reference validates in
        ModelBuilder init)."""
        p = self.params
        if self.algo not in _CALIBRATION_ALGOS:
            raise ValueError(
                f"calibrate_model is not supported for {self.algo} "
                f"(hex/tree/CalibrationHelper covers GBM/DRF/XGBoost)")
        if p.get("calibration_frame") is None:
            raise ValueError(
                "calibrate_model requires a calibration_frame")
        if spec.nclasses != 2:
            raise ValueError("model calibration is only supported for "
                             "binomial classification")
        method = str(p.get("calibration_method") or "auto").lower()
        method = method.replace("_scaling", "").replace("scaling", "") \
                       .replace("_regression", "").replace("regression",
                                                           "")
        if method not in ("auto", "", "platt", "isotonic"):
            raise ValueError(
                f"unknown calibration_method "
                f"'{p.get('calibration_method')}' (one of AUTO, "
                f"PlattScaling, IsotonicRegression)")

    def validate_sample_rate_per_class(self, spec: TrainingSpec):
        """Shared GBM/DRF sample_rate_per_class validation
        (hex/tree/SharedTree.java:210-213): one rate per RESPONSE
        class. Returns the normalized tuple or None."""
        srpc = self.params.get("sample_rate_per_class")
        if srpc is None or not len(srpc):
            return None
        if spec.nclasses < 2:
            raise ValueError("sample_rate_per_class requires a "
                             "classification response")
        if len(srpc) != spec.nclasses:
            raise ValueError(
                f"sample_rate_per_class must have {spec.nclasses} "
                f"values (one per class), got {len(srpc)}")
        return tuple(float(v) for v in srpc)

    def _fit_calibration(self, model: "Model") -> None:
        """calibrate_model / calibration_frame / calibration_method
        (hex/tree/CalibrationHelper, used by GBM/DRF): fit Platt scaling
        (Platt 1999, 1-D logistic a·logit(p)+b by Newton) or isotonic
        regression (PAV) of the true labels on the model's predicted
        positive-class probability over the calibration frame; scoring
        then appends cal_p0/cal_p1 columns."""
        p = self.params
        if self.algo not in _CALIBRATION_ALGOS:
            raise ValueError(
                f"calibrate_model is not supported for {self.algo} "
                f"(hex/tree/CalibrationHelper covers GBM/DRF/XGBoost)")
        cf = p.get("calibration_frame")
        if cf is None:
            raise ValueError(
                "calibrate_model requires a calibration_frame")
        if isinstance(cf, str):
            from h2o3_tpu import dkv
            cf = dkv.get(cf, "frame")
        if model.nclasses != 2:
            raise ValueError("model calibration is only supported for "
                             "binomial classification")
        method = str(p.get("calibration_method") or "auto").lower()
        method = method.replace("_scaling", "").replace("scaling", "") \
                       .replace("_regression", "").replace("regression", "")
        if method in ("auto", ""):
            method = "platt"
        X = adapt_test_matrix(model, cf)
        out = model._predict_matrix(X, offset=model._frame_offset(cf))
        probs = model._correct_probabilities(
            np.asarray(_tel.device_get(out, pipeline="train"))[:cf.nrow])
        p1 = np.clip(probs[:, 1].astype(np.float64), 1e-12, 1 - 1e-12)
        yc, w = response_codes_in_domain(cf, model.response,
                                         model.response_domain)
        yv = np.asarray(yc, np.float64)
        wv = np.asarray(w, np.float64)
        if method == "platt":
            z = np.log(p1 / (1.0 - p1))
            a, b = 1.0, 0.0
            for _ in range(50):
                mu = 1.0 / (1.0 + np.exp(-(a * z + b)))
                s = np.maximum(mu * (1 - mu), 1e-12) * wv
                g = np.array([(wv * (yv - mu) * z).sum(),
                              (wv * (yv - mu)).sum()])
                H = np.array([[(s * z * z).sum(), (s * z).sum()],
                              [(s * z).sum(), s.sum()]])
                d = np.linalg.solve(H + 1e-9 * np.eye(2), g)
                a += d[0]
                b += d[1]
                if np.abs(d).max() < 1e-10:
                    break
            model.output["calibration"] = {"method": "platt",
                                           "a": float(a), "b": float(b)}
        elif method == "isotonic":
            from h2o3_tpu.models.isotonic import _pav
            ux, inv = np.unique(p1, return_inverse=True)
            awy = np.bincount(inv, weights=wv * yv)
            aw = np.bincount(inv, weights=wv)
            tx, ty = _pav(ux, awy, aw)
            model.output["calibration"] = {
                "method": "isotonic",
                "tx": [float(v) for v in tx],
                "ty": [float(v) for v in ty]}
        else:
            raise ValueError(
                f"unknown calibration_method "
                f"'{p.get('calibration_method')}' (one of AUTO, "
                f"PlattScaling, IsotonicRegression)")

    def _apply_balance_classes(self, spec: TrainingSpec) -> TrainingSpec:
        """balance_classes / class_sampling_factors /
        max_after_balance_size (hex/ModelBuilder ClassSamplingMethod +
        water/util/MRUtils.sampleFrameStratified): the reference
        physically re-samples rows; the TPU redesign multiplies class
        factors into the row WEIGHTS — identical in expectation for
        every weighted learner here (tree histograms, GLM IRLS, DL
        loss) with no data movement. The prior/model class
        distributions are recorded so scoring can correct predicted
        probabilities back to the prior (hex/Model correctProbabilities
        / _priorClassDist vs _modelClassDist)."""
        from dataclasses import replace as dc_replace
        self._class_dists = None
        if not self.params.get("balance_classes"):
            return spec
        if spec.nclasses < 2:
            return spec
        if self.algo == "upliftdrf":
            raise ValueError(
                "balance_classes is not supported for Uplift DRF "
                "(hex/tree/uplift/UpliftDRF.java rejects it)")
        if spec.stream:
            raise NotImplementedError(
                "balance_classes is not supported in streaming "
                "(memory-pressure) mode")
        K = spec.nclasses
        yc = jnp.clip(spec.y.astype(jnp.int32), 0, K - 1)
        w_eff = spec.w
        mvh = str(self.params.get("missing_values_handling")
                  or "").lower().replace("_", "")
        if mvh == "skip" and spec.X is not None:
            # Skip drops NA rows downstream (GLM _apply_mvh) — class
            # distributions must reflect the data actually trained on
            w_eff = spec.w * (~jnp.isnan(spec.X).any(axis=1))
        counts = jnp.zeros(K, jnp.float32).at[yc].add(w_eff)
        ch = np.asarray(_tel.device_get(counts, pipeline="train"),
                        np.float64)
        total = float(ch.sum())
        if total <= 0:
            return spec
        csf = self.params.get("class_sampling_factors")
        if csf is not None and len(csf):
            fac = np.asarray(csf, np.float64)
            if fac.shape[0] != K:
                raise ValueError(
                    f"class_sampling_factors needs {K} values (one per "
                    f"response class), got {fac.shape[0]}")
        else:
            # auto: uniform target — factor_k = total/(K·n_k)
            fac = total / (K * np.maximum(ch, 1.0))
        mabs = float(self.params.get("max_after_balance_size", 5.0)
                     or 5.0)
        new_total = float((ch * fac).sum())
        if new_total > mabs * total:
            fac *= mabs * total / new_total
            new_total = mabs * total
        w2 = spec.w * jnp.asarray(fac, jnp.float32)[yc]
        self._class_dists = (
            (ch / total).tolist(),
            ((ch * fac) / max(new_total, 1e-12)).tolist())
        return dc_replace(spec, w=w2)

    def train(self, x: Optional[Sequence[str]] = None, y: Optional[str] = None,
              training_frame: Optional[Frame] = None,
              validation_frame: Optional[Frame] = None,
              background: bool = False) -> "ModelBuilder":
        """Train via the cluster scheduler (h2o3_tpu.sched): the
        submission ENQUEUES (surfacing as QUEUED on /3/Jobs) and the
        whole build — spec construction and its device allocations
        included — runs only once admission releases it. Nested builds
        (CV folds, metalearners, calibration trains inside an admitted
        run) and the H2O3_SCHED=0 escape run the pre-scheduler inline/
        daemon-thread path: queueing a child while the parent blocks on
        it would deadlock the parent against its own admission."""
        t_call = time.perf_counter()
        y = y or self.params.get("response_column")
        training_frame = training_frame if training_frame is not None else \
            self.params.get("training_frame")
        if training_frame is None or (y is None and self.supervised):
            raise ValueError("train() needs training_frame"
                             + (" and y" if self.supervised else ""))
        from h2o3_tpu import sched
        # max_runtime_secs rides on the job so the supervision watchdog
        # (jobs.py) enforces it by cancellation — the chunk loops poll
        # cancel_requested and exit cooperatively. Queue wait does NOT
        # count: mark_dispatched restarts the clock.
        job = Job(f"{self.algo} training", work=1.0,
                  max_runtime_secs=float(
                      self.params.get("max_runtime_secs", 0) or 0))
        self.job = job
        # restart recovery (ISSUE 9): is_resuming() is thread-local to
        # the SUBMITTING thread — capture it before the body hops to a
        # scheduler worker
        self._resuming = False
        if os.environ.get("H2O3_RECOVERY_DIR"):
            from h2o3_tpu import recovery
            self._resuming = recovery.is_resuming()
        kwargs = dict(x=x, y=y, training_frame=training_frame,
                      validation_frame=validation_frame)
        if sched.enabled() and not sched.in_scheduled_run():
            try:
                # foreground submissions execute on THIS thread once
                # admission grants them (caller_runs): the caller blocks
                # anyway, and XLA compiles run measurably slower on
                # freshly-spawned worker threads
                entry = sched.scheduler().submit(
                    self, job, kwargs, caller_runs=not background)
            except (sched.SchedulerSaturatedError, ValueError) as e:
                # any submit rejection (queue cap, unknown priority):
                # the job never enters the queue — terminal-fail it so
                # /3/Jobs pollers and join()ers see the rejection
                # instead of a RUNNING zombie that is never evicted
                # (end clocks stamped — a terminal job's msec must not
                # keep growing)
                from h2o3_tpu.jobs import FAILED
                job.status = FAILED
                job._record_failure(e)
                job.end_time = time.time()
                job._end_mono = time.monotonic()
                job._done_evt.set()
                raise
            self._sched_entry = entry
            if not background:
                sched.scheduler().run_to_completion(entry)
                self.model = self._join_typed(job)
                self._close_train_profile(t_call)
            return self
        # inline path (nested build or scheduler disabled)
        if self._resuming:
            from h2o3_tpu import jobs as jobs_mod
            job.status = jobs_mod.RECOVERING
        job.run(lambda j: self._run_build(j, **kwargs),
                background=background)
        if not background:
            self.model = self._join_typed(job)
            self._close_train_profile(t_call)
        return self

    def _close_train_profile(self, t_call: float) -> None:
        """A foreground train's whole call beside its stages: ``total_s``
        from entry to return, and ``other_s``, what the span tree leaves
        unexplained (between the stages, job hand-off, wrap-up)."""
        tp = self.model.output.get("train_profile")
        if tp is None:
            return
        tp["total_s"] = round(time.perf_counter() - t_call, 4)
        tp["other_s"] = round(tp["total_s"] - sum(
            tp.get(k, 0.0) for k in ("queue_s", "spec_s", "bin_s", "init_s",
                                     "loop_s", "finalize_s")), 4)

    def _join_typed(self, job: Job):
        """Foreground-train result: parameter-validation failures (the
        spec phase — bad columns, unsupported modes) re-raise TYPED
        exactly as they did when the spec was built on the calling
        thread; training-phase failures keep join()'s RuntimeError
        wrapping."""
        from h2o3_tpu.jobs import FAILED
        if (job.status == FAILED and job.exception_obj is not None
                and getattr(job.exception_obj, "_h2o3_param_error",
                            False)):
            raise job.exception_obj
        return job.join()

    def _run_build(self, job: Job, x=None, y=None, training_frame=None,
                   validation_frame=None):
        """The whole build — spec (device allocation), train, CV,
        calibration — executed on the dispatching thread (a scheduler
        worker, the caller for inline foreground builds, or a daemon
        thread for inline background ones)."""
        from h2o3_tpu import telemetry
        from h2o3_tpu.log import Profile, info, timeline_record
        t0 = time.monotonic()
        if self._resuming:
            from h2o3_tpu import jobs as jobs_mod
            job.status = jobs_mod.RECOVERING
        # root span for the whole build; handed EXPLICITLY to the Profile
        # because this body may run on a worker thread (thread-local
        # nesting does not carry across threads)
        sp_root = telemetry.open_span(f"train.{self.algo}")
        prof = Profile(parent_span=sp_root)
        # the scheduler's share: submit → admission → dispatch, measured
        # by the job (jobs.mark_dispatched) and over before this body ran
        if job.queue_wait_s:
            prof.add("queue", job.queue_wait_s)
        timeline_record("train_start", f"{self.algo}")
        self._warn_compat_params()
        try:
            with prof.phase("spec") as sp_spec:
                spec = self._make_spec(training_frame, y, x)
                if sp_spec is not None:
                    sp_spec.attrs["response_factor"] = spec.response_factor
                spec = self._apply_balance_classes(spec)
                if self.params.get("calibrate_model"):
                    self._validate_calibration(spec)
                if getattr(spec, "stream", False) \
                        and not self.supports_streaming:
                    raise NotImplementedError(
                        f"{self.algo}: the training frame exceeds the "
                        f"device memory budget and this algorithm has no "
                        f"streaming (memory-pressure) path — raise "
                        f"H2O3_DEVICE_BUDGET_BYTES, reduce the frame, or "
                        f"use GBM/XGBoost/GLM which stream")
                valid_spec = None
                if validation_frame is not None:
                    # ADAPT the validation frame to the training spec
                    # (domain remap), not a fresh spec from its own
                    # domains
                    valid_spec = build_validation_spec(
                        validation_frame, spec,
                        weights_column=self.params.get("weights_column"),
                        offset_column=self.params.get("offset_column"))
        except Exception as e:
            # parameter/spec validation failed: tag so a foreground
            # train() re-raises it TYPED (pre-scheduler, this phase ran
            # on the calling thread and its ValueErrors were never
            # RuntimeError-wrapped)
            e._h2o3_param_error = True
            if sp_root is not None and sp_root.duration_s is None:
                sp_root.finish()
            raise
        # restart recovery (ISSUE 9): a checkpointing train records a
        # durable manifest so a killed PROCESS can rediscover and resume
        # it at the next boot; the env gate keeps the common path one
        # dict lookup (H2O3_TELEMETRY=0 idiom). A train resumed BY the
        # recovery scan surfaces as RECOVERING on /3/Jobs.
        rec_key = None
        if os.environ.get("H2O3_RECOVERY_DIR"):
            from h2o3_tpu import recovery
            if self.params.get("in_training_checkpoints_dir"):
                rec_key = recovery.record_training(self, job,
                                                   training_frame, y, spec)
        info("%s train start: %d rows, %d features", self.algo, spec.nrow,
             spec.n_features)

        def body(job):
            nfolds = int(self.params.get("nfolds", 0) or 0)
            fold_column = self.params.get("fold_column")
            par = build_parallelism(
                int(self.params.get("parallelism", 1) or 1))
            cv_fut = None
            # builders that override _cross_validate opt OUT of the
            # generic fold machinery (TargetEncoder: fold_column selects
            # ENCODING folds, not CV folds) — route through the override,
            # never _cv_fold_pass directly
            custom_cv = (type(self)._cross_validate
                         is not ModelBuilder._cross_validate)
            if (nfolds > 1 or fold_column) and par > 1 and not spec.stream \
                    and not custom_cv:
                # concurrent CV-main (hex/ModelBuilder.java:884
                # cv_buildModels + main build overlap): fold models start
                # on a worker pool while the main model trains here
                import concurrent.futures as cf
                cv_pool = cf.ThreadPoolExecutor(max_workers=1)
                cv_fut = cv_pool.submit(
                    self._cv_fold_pass, training_frame, y, x, spec, job,
                    nfolds, fold_column)
            try:
                with prof.phase("train"):
                    model = self._train_impl(spec, valid_spec, job)
                # PlugValues substitutions must follow the model to
                # scoring time: enum plugs via cat_plugs, numeric plugs
                # MERGED over the computed means so columns the user did
                # not plug keep real mean imputation
                if getattr(self, "_cat_plugs", None):
                    model.cat_plugs = dict(self._cat_plugs)
                if (getattr(self, "_plug_num", None)
                        and hasattr(model, "impute_means")):
                    model.impute_means = {**model.impute_means,
                                          **self._plug_num}
                if getattr(self, "_class_dists", None):
                    prior_d, model_d = self._class_dists
                    model.output["prior_class_dist"] = prior_d
                    model.output["model_class_dist"] = model_d
                if self.params.get("calibrate_model"):
                    self._fit_calibration(model)
            except BaseException:
                if cv_fut is not None:    # don't orphan the fold pass
                    cv_fut.cancel()
                    cv_pool.shutdown(wait=False, cancel_futures=True)
                raise
            model.run_time = time.monotonic() - t0
            # UDF metric (water/udf CMetricFunc analog): a callable
            # (pred, y, w) -> float evaluated on the training data
            cmf = self.params.get("custom_metric_func")
            # unsupervised specs carry a dummy zero y — a metric on it
            # would be meaningless (and wrappers may not even score)
            if callable(cmf) and spec.response is not None:
                pred, yh, wh = (np.asarray(v) for v in _tel.device_get(
                    (model._predict_matrix(spec.X), spec.y, spec.w),
                    pipeline="train"))
                live = wh > 0
                model.output["custom_metric"] = {
                    "name": getattr(cmf, "__name__", "custom"),
                    "value": float(cmf(pred[live], yh[live], wh[live]))}
            if nfolds > 1 or fold_column:
                with prof.phase("cv"):
                    if custom_cv:
                        self._cross_validate(model, training_frame, y, x,
                                             spec, job, nfolds,
                                             fold_column)
                    elif cv_fut is not None:
                        fold_pass = cv_fut.result()
                        cv_pool.shutdown()
                        self._attach_cv(model, training_frame, y, x,
                                        *fold_pass)
                    else:
                        fold_pass = self._cv_fold_pass(
                            training_frame, y, x, spec, job, nfolds,
                            fold_column)
                        self._attach_cv(model, training_frame, y, x,
                                        *fold_pass)
            model.output["profile"] = prof.to_dict()
            if "train_profile" in model.output:
                model.output["train_profile"].update(
                    queue_s=round(prof.phases.get("queue", 0.0), 4),
                    spec_s=round(prof.phases["spec"], 4))
            if rec_key is not None:
                # DELIBERATE completion (DONE or a cooperative cancel
                # that finalized a partial model): the manifest's job is
                # over — only a crash/kill leaves it for boot recovery
                from h2o3_tpu import recovery
                recovery.complete_training(rec_key)
            info("%s train done: %s", self.algo, prof.summary())
            timeline_record("train_done",
                            f"{self.algo} {prof.summary()}")
            if sp_root is not None:
                sp_root.attrs.update(rows=spec.nrow,
                                     features=spec.n_features)
                sp_root.finish()
            return model

        def body_spanned(j):
            try:
                return body(j)
            except BaseException as e:
                # a cooperative cancel that unwound before finalize is
                # still a DELIBERATE end — drop the recovery manifest
                # so the cancelled train does not auto-resume at the
                # next boot (crash/kill paths never reach this handler).
                # A PREEMPTION unwind is NOT terminal: the scheduler
                # requeues the entry, and a crash while it waits must
                # still find the manifest at the next boot
                if rec_key is not None:
                    from h2o3_tpu.jobs import JobCancelled, JobPreempted
                    if isinstance(e, JobCancelled) \
                            and not isinstance(e, JobPreempted):
                        from h2o3_tpu import recovery
                        recovery.complete_training(rec_key)
                raise
            finally:
                # failed/cancelled builds still close their root span
                if sp_root is not None and sp_root.duration_s is None:
                    sp_root.finish()

        return body_spanned(job)

    def _make_spec(self, frame, y, x):
        if not self.supervised:
            return build_unsupervised_spec(
                frame, x,
                ignored_columns=self.params.get("ignored_columns"),
                weights_column=self.params.get("weights_column"))
        classification = None
        dist = (self.params.get("distribution") or "").lower()
        if dist in ("bernoulli", "binomial", "multinomial"):
            classification = True
        elif dist and dist != "auto":
            classification = False
        return build_training_spec(
            frame, y, x,
            ignored_columns=self.params.get("ignored_columns"),
            weights_column=self.params.get("weights_column"),
            offset_column=self.params.get("offset_column"),
            classification=classification)

    def _cross_validate(self, model: Model, frame: Frame, y: str, x, spec,
                        job: Job, nfolds: int, fold_column: Optional[str]):
        """N-fold CV (hex/ModelBuilder.java:535-957): assign folds, train a
        model per fold on the complement, score the holdout, aggregate.
        Holdout predictions are kept for StackedEnsemble."""
        self._attach_cv(model, frame, y, x,
                        *self._cv_fold_pass(frame, y, x, spec, job, nfolds,
                                            fold_column))

    def _cv_fold_pass(self, frame: Frame, y: str, x, spec, job: Job,
                      nfolds: int, fold_column: Optional[str]):
        """Fold assignment + per-fold training/holdout scoring — the part
        that can overlap the MAIN model's build (concurrent CV-main).
        Returns (holdout, fold_models, fold, K)."""
        nrow = frame.nrow
        if fold_column:
            fold = frame.vec(fold_column).to_numpy().astype(int)
            fold_ids = np.unique(fold)
        else:
            assignment = (self.params.get("fold_assignment") or "auto").lower()
            seed = int(self.params.get("seed", -1) or -1)
            rng = np.random.default_rng(None if seed == -1 else seed)
            if assignment == "modulo":
                fold = np.arange(nrow) % nfolds
            else:
                fold = rng.integers(0, nfolds, size=nrow)
            fold_ids = np.arange(nfolds)
        K = spec.nclasses if spec.nclasses > 1 else 1
        holdout = np.full((nrow, K) if K > 1 else (nrow,), np.nan, dtype=np.float32)

        def one_fold(fid):
            # fold builds are NESTED: they ride the parent's scheduler
            # admission. The inline flag is thread-local, so a fold
            # running on a pool thread (parallel CV / concurrent
            # CV-main) must re-enter it explicitly — without this the
            # fold would ENQUEUE while the parent blocks holding its
            # grant, deadlocking under a tight budget
            from h2o3_tpu import sched
            with sched.inline_run():
                mask = fold == fid
                tr = frame.rows(~mask)
                te = frame.rows(mask)
                sub = type(self)(**{k: v for k, v in self.params.items()
                                    if k not in ("nfolds", "fold_column",
                                                 "parallelism")})
                sub.train(x=x, y=y, training_frame=tr)
                fm = sub.model
                X_te = adapt_test_matrix(fm, te)
                out = np.asarray(_tel.device_get(
                    fm._predict_matrix(X_te,
                                       offset=fm._frame_offset(te)),
                    pipeline="train"))[: te.nrow]
                return mask, out, fm

        par = build_parallelism(
            int(self.params.get("parallelism", 1) or 1))
        fold_models = []
        if par > 1:
            # CVModelBuilder parallel fold building (hex/CVModelBuilder,
            # ModelBuilderHelper.trainModelsParallel): threads overlap
            # host orchestration and XLA compiles (GIL released)
            import concurrent.futures as cf
            with cf.ThreadPoolExecutor(max_workers=par) as ex:
                futs = [ex.submit(one_fold, fid) for fid in fold_ids]
                for i, fu in enumerate(futs):
                    mask, out, fm = fu.result()
                    holdout[mask] = out
                    fold_models.append(fm)
                    job.set_progress(0.5 + 0.5 * (i + 1) / len(fold_ids))
        else:
            for i, fid in enumerate(fold_ids):
                mask, out, fm = one_fold(fid)
                holdout[mask] = out
                fold_models.append(fm)
                job.set_progress(0.5 + 0.5 * (i + 1) / len(fold_ids))
        return holdout, fold_models, fold, K

    def _attach_cv(self, model: Model, frame: Frame, y: str, x, holdout,
                   fold_models, fold, K):
        """Aggregate pooled-holdout CV metrics onto the main model."""
        nrow = frame.nrow
        cv_spec = build_training_spec(frame, y, x,
                                      classification=model.nclasses > 1)
        yh, wh = (np.asarray(v)[:nrow] for v in _tel.device_get(
            (cv_spec.y, cv_spec.w), pipeline="train"))
        ok = wh > 0
        if K > 1:
            model.cross_validation_metrics = (
                metrics_mod.make_binomial_metrics(holdout[ok, 1], yh[ok], wh[ok])
                if K == 2 else
                metrics_mod.make_multinomial_metrics(holdout[ok], yh[ok], wh[ok]))
        else:
            model.cross_validation_metrics = metrics_mod.make_regression_metrics(
                holdout[ok], yh[ok], wh[ok])
        model.output["cross_validation_holdout_predictions"] = holdout
        model.output["cross_validation_models"] = fold_models
        model.output["cv_fold_assignment"] = fold

    @staticmethod
    def nclasses_of(model: Model) -> int:
        return model.nclasses

    def __getattr__(self, item):
        # delegate metric accessors to the trained model (h2o-py style)
        if item.startswith("_") or self.__dict__.get("model") is None:
            raise AttributeError(item)
        return getattr(self.model, item)
