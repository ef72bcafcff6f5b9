"""GBM — gradient boosting on the JAX histogram tree builder.

Reference: hex/tree/gbm/GBM.java:32 over the shared machinery in
hex/tree/SharedTree.java:229 (scoreAndBuildTrees :481, per-level
ScoreBuildHistogram2 MRTask, DTree split finding, CompressedTree storage).

The TPU training loop is one jitted per-tree step: compute (g, h) from the
distribution at the current margin, row/column-sample, grow a static-depth
tree from MXU histograms, and fold the tree's leaf values back into the
margin — no host round-trips inside a tree. Multinomial builds K trees per
iteration (one per class), as the reference does per-class DTrees.
"""
from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o3_tpu import telemetry
from h2o3_tpu.jobs import Job
from h2o3_tpu.log import Profile
from h2o3_tpu.models.distributions import get_distribution
from h2o3_tpu.models.model_base import (Model, ModelBuilder, ScoreKeeper,
                                        TrainingSpec, compute_metrics)
from h2o3_tpu.models.tree import (adaptive_setup, chunk_bucket,
                                  collect_chunk_trees, grow_tree,
                                  grow_tree_adaptive, grow_tree_binned,
                                  levels_per_pass, node_lookup,
                                  packed_codes_requested, predict_binned,
                                  predict_raw_stacked, predict_raw_tree,
                                  prepare_tree_inputs, tree_config,
                                  tree_path)
from h2o3_tpu.ops.binning import (CodesView, digitize_with_edges,
                                  make_codes_view, pack_codes_for,
                                  packed_codes_record)
from h2o3_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, current_mesh,
                                    n_data_shards, n_model_shards,
                                    partitioner, spmd_enabled)
from h2o3_tpu.resilience import resilient_device_put, retry_transient

GBM_DEFAULTS: Dict = dict(
    ntrees=50, max_depth=5, min_rows=10.0, learn_rate=0.1,
    learn_rate_annealing=1.0, sample_rate=1.0, sample_rate_per_class=None,
    col_sample_rate=1.0, col_sample_rate_per_tree=1.0,
    col_sample_rate_change_per_level=1.0, nbins=20, nbins_cats=1024,
    distribution="auto", tweedie_power=1.5, quantile_alpha=0.5,
    huber_alpha=0.9, min_split_improvement=1e-5,
    seed=-1, stopping_rounds=0, stopping_metric="auto",
    stopping_tolerance=1e-3, score_tree_interval=0, reg_lambda=0.0,
    # continue-training + in-training checkpoints (hex/Model.java:487
    # _checkpoint, hex/tree/SharedTree in_training_checkpoints_*):
    # REAL params now (formerly compat_params warn entries) — resumed
    # trains are bit-identical to uninterrupted ones via the saved
    # resume margin (tests/test_resilience.py)
    checkpoint=None, in_training_checkpoints_dir=None,
    in_training_checkpoints_tree_interval=1,
    # uniform_adaptive = the reference's default (hex/tree/DHistogram.java
    # UniformAdaptive): per-node re-binned uniform histograms via the fused
    # adaptive kernel; quantiles_global = global-sketch binned codes
    # (XGBoost tree_method=hist semantics)
    max_abs_leafnode_pred=1e30, histogram_type="uniform_adaptive",
    # monotone_constraints: {col: +1/-1} (hex/tree/DTree Constraints);
    # interaction_constraints: [[col,...],...] feature groups allowed to
    # interact on a branch (GlobalInteractionConstraints)
    monotone_constraints=None, interaction_constraints=None,
    # TPU-specific: which histogram kernel ('auto' = matmul on TPU,
    # scatter on CPU); see ops/histogram.py
    hist_kernel="auto",
    # MXU histogram precision: 'auto' (= bfloat16 1-pass; deviation bound
    # in ops/hist_adaptive.py) or 'float32' (exact, ~6x hist cost)
    histogram_precision="auto",
    # packed binned-code hot path (ISSUE 12): 'auto' bins features once
    # into int8/int16 codes and runs the fused binned level kernel
    # wherever compiled pallas runs (TPU / interpret escape) — the
    # XGBoost tree_method=hist shape with 1-2 byte/value hot-loop
    # traffic; True forces it everywhere (scatter reference), False
    # keeps the per-node adaptive f32 kernel. histogram_type='random'
    # always uses the adaptive kernel (its per-tree grid phase needs
    # per-level rebinning, which packing removes by design)
    packed_codes="auto",
    # how enum columns enter the trees (hex/Model.Parameters
    # CategoricalEncodingScheme): 'auto' and 'enum' are one bin a level
    # and a split that sends a SET of levels left (on the packed path;
    # tree.prepare_tree_inputs); 'label_encoder' is a threshold on the
    # level index; the other schemes are not built and are reported in
    # model.output["categorical_encoding"]
    categorical_encoding="auto",
)


from h2o3_tpu.models.treeshap import TreeScoringOptionsMixin  # noqa: E402


def _spec_signature(spec) -> np.ndarray:
    """Cheap fingerprint of the training data a resume state belongs
    to: (nrow, Σy, Σw) as f32 device reductions — identical data gives
    bit-equal sums, different data virtually never does. Guards
    against applying a checkpoint's saved margin/OOB state to a
    different frame that merely has the same shape."""
    sy, sw = telemetry.device_get(
        (spec.y.astype(jnp.float32).sum(),
         spec.w.astype(jnp.float32).sum()), pipeline="train")
    return np.array([float(spec.nrow), float(sy), float(sw)],
                    np.float64)


def _resolve_checkpoint_source(ckpt, model_cls, algo_label):
    """``checkpoint=`` accepts a live model, a DKV key (the in-training
    checkpoints land there as ``<key>_ckpt``) or an artifact path
    (hex/Model.java _checkpoint takes a Key; h2o-py also passes model
    objects)."""
    if isinstance(ckpt, model_cls):
        return ckpt
    if isinstance(ckpt, str):
        from h2o3_tpu import dkv
        ent = dkv.get_opt(ckpt)
        if ent is not None and ent[0] == "model":
            prior = ent[1]
        else:
            from h2o3_tpu.persist import load_model
            prior = load_model(ckpt)
    else:
        raise ValueError(
            f"checkpoint must be a {algo_label} model, DKV key or "
            f"artifact path, got {type(ckpt).__name__}")
    if not isinstance(prior, model_cls):
        raise ValueError(
            f"checkpoint resolves to a {getattr(prior, 'algo', '?')} "
            f"model — {algo_label} can only continue from its own kind")
    return prior


class GBMModel(TreeScoringOptionsMixin, Model):
    algo = "gbm"

    def __init__(self, key, params, spec, dist_name, f0, trees_host, edges,
                 n_bins, max_depth, ntrees_built, nclasses):
        super().__init__(key, params, spec)
        self.dist_name = dist_name
        self.f0 = f0                      # scalar or [K]
        self.edges = edges
        self.n_bins = n_bins
        self.max_depth = max_depth
        self.ntrees_built = ntrees_built
        self._K = max(nclasses, 1) if nclasses > 2 else 1
        # stacked device arrays [T*K, M] in (tree, class) order
        self._feat = jnp.asarray(trees_host["feat"])
        self._thr = jnp.asarray(trees_host["thr"])
        self._na_left = jnp.asarray(trees_host["na_left"])
        self._is_split = jnp.asarray(trees_host["is_split"])
        self._value = jnp.asarray(trees_host["value"])
        nw = trees_host.get("node_w")
        self._node_w = jnp.asarray(nw) if nw is not None else None
        # category-set splits (tree.grow_tree_binned on enum features):
        # [T*K, M, words] uint32 and which nodes hold a set; None where
        # every split is a threshold
        cs = trees_host.get("cat_set")
        self._cat_set = jnp.asarray(cs) if cs is not None else None
        self._is_set = (jnp.asarray(trees_host["is_set"])
                        if cs is not None else None)
        # how many nodes split on a set (``score.dispatch``'s attr),
        # counted once from the host arrays: a predict fetches nothing
        self._set_nodes = (int(np.asarray(trees_host["is_set"]).sum())
                           if cs is not None else None)

    def _contrib_f0(self) -> float:
        return float(np.asarray(self.f0).reshape(-1)[0])

    def _margin_matrix(self, X, offset=None):
        contribs = predict_raw_stacked(X, self._feat, self._thr, self._na_left,
                                       self._is_split, self._value,
                                       self.max_depth,
                                       cat_set=self._cat_set,
                                       is_set=self._is_set)
        K = self._K
        if K == 1:
            margin = jnp.asarray(self.f0) + contribs.sum(axis=1)
            if offset is not None:
                margin = margin + offset
            return margin
        T = self.ntrees_built
        per_class = contribs.reshape(X.shape[0], T, K).sum(axis=1)
        return jnp.asarray(self.f0)[None, :] + per_class

    def _predict_matrix(self, X, offset=None):
        margin = self._margin_matrix(X, offset=offset)
        if self.nclasses <= 1:
            return get_distribution(self.dist_name,
                                    self.params.get("tweedie_power", 1.5)
                                    ).predict(margin)
        if self.nclasses == 2:
            p1 = 1.0 / (1.0 + jnp.exp(-margin))
            return jnp.stack([1.0 - p1, p1], axis=1)
        return jax.nn.softmax(margin, axis=1)

    def varimp(self, use_pandas=False):
        """Relative importance = summed split gain per feature
        (hex/tree/SharedTreeModel varimp semantics)."""
        return self.output.get("variable_importances")

    # -- persistence (persist.save_model/load_model) -------------------

    def _save_arrays(self):
        # ONE counted pytree fetch for the stacked tree arrays (the
        # five raw per-array device_gets were invisible to d2h budgets)
        host = telemetry.device_get(
            {"feat": self._feat, "thr": self._thr,
             "na_left": self._na_left, "is_split": self._is_split,
             "value": self._value})
        d = {k: np.asarray(v) for k, v in host.items()}
        d["f0"] = np.asarray(self.f0)
        if self._node_w is not None:
            d["node_w"] = np.asarray(telemetry.device_get(self._node_w))
        if self._cat_set is not None:
            sets = telemetry.device_get({"cat_set": self._cat_set,
                                         "is_set": self._is_set})
            d.update({k: np.asarray(v) for k, v in sets.items()})
        rm = getattr(self, "_resume_margin", None)
        if rm is not None:
            # in-training checkpoint state: the exact f32 training
            # margin at the committed tree count — resuming from it
            # (instead of re-summing tree contributions) is what makes
            # a resumed train BIT-identical to an uninterrupted one
            d["resume_margin"] = np.asarray(rm)
        sig = getattr(self, "_resume_sig", None)
        if sig is not None:
            d["resume_sig"] = np.asarray(sig)
        for i, e in enumerate(self.edges):
            d[f"edge_{i}"] = np.asarray(e)
        return d

    def _save_extra_meta(self):
        return {"dist_name": self.dist_name, "n_bins": self.n_bins,
                "max_depth": self.max_depth,
                "ntrees_built": self.ntrees_built,
                "n_edges": len(self.edges)}

    @classmethod
    def _restore(cls, meta, arrays):
        m = cls._restore_base(meta)
        ex = meta["extra"]
        m.dist_name = ex["dist_name"]
        m.n_bins = ex["n_bins"]
        m.max_depth = ex["max_depth"]
        m.ntrees_built = ex["ntrees_built"]
        m.f0 = arrays["f0"]
        m.edges = [arrays[f"edge_{i}"] for i in range(ex["n_edges"])]
        m._K = max(m.nclasses, 1) if m.nclasses > 2 else 1
        m._feat = jnp.asarray(arrays["feat"])
        m._thr = jnp.asarray(arrays["thr"])
        m._na_left = jnp.asarray(arrays["na_left"])
        m._is_split = jnp.asarray(arrays["is_split"])
        m._value = jnp.asarray(arrays["value"])
        m._node_w = (jnp.asarray(arrays["node_w"])
                     if "node_w" in arrays else None)
        m._cat_set = (jnp.asarray(arrays["cat_set"])
                      if "cat_set" in arrays else None)
        m._is_set = (jnp.asarray(arrays["is_set"])
                     if "cat_set" in arrays else None)
        m._set_nodes = (int(np.asarray(arrays["is_set"]).sum())
                        if "cat_set" in arrays else None)
        m._resume_margin = (np.asarray(arrays["resume_margin"])
                            if "resume_margin" in arrays else None)
        m._resume_sig = (np.asarray(arrays["resume_sig"])
                         if "resume_sig" in arrays else None)
        return m


def _stack_sets(prior, th: dict) -> dict:
    """``cat_set`` / ``is_set`` of a finished train: the new trees' sets
    (``th``, tree.collect_chunk_trees) after a checkpoint's, either side
    zeros where it split by thresholds alone, words padded to the wider;
    {} where neither has a set."""
    old = getattr(prior, "_cat_set", None) if prior is not None else None
    if "cat_set" not in th and old is None:
        return {}
    parts = []
    for cs, iss, like in (
            (old, getattr(prior, "_is_set", None),
             getattr(prior, "_feat", None)),
            (th.get("cat_set"), th.get("is_set"), th["feat"])):
        if like is None:
            continue
        shape = np.asarray(like).shape
        parts.append((np.zeros(shape + (1,), np.uint32) if cs is None
                      else np.asarray(cs),
                      np.zeros(shape, bool) if cs is None
                      else np.asarray(iss)))
    words = max(c.shape[-1] for c, _ in parts)
    return {"cat_set": np.concatenate(
                [np.pad(c, ((0, 0), (0, 0), (0, words - c.shape[-1])))
                 for c, _ in parts]),
            "is_set": np.concatenate([i for _, i in parts])}


@jax.named_scope("gbm.chunk")     # a stable name in the ops' metadata
def _gbm_chunk_body(codes_rm, codes_t, margin, y, w, vrm, vmargin, base_key,
                    lr0, hdelta, root_lo, root_hi, nb_f, mono, sets,
                    start_idx, n_active, sample_rate, col_rate, anneal,
                    *, cfg, K,
                    dist_name, tweedie_power, quantile_alpha,
                    sample_rate_per_class, na_bin, chunk,
                    has_valid, has_t, adaptive, binned, has_mono, has_sets,
                    axis_name, model_axis=None):
    """One chunk of the boosting loop, per data shard (runs under
    shard_map). ``chunk`` trees are built inside ONE program via lax.scan:
    per-call dispatch overhead amortises and margins/trees stay on device
    between trees. The reference dispatches one MRTask per level per tree
    (SharedTree.java:566-635) — here a whole chunk of trees is a single
    XLA program, and the cross-shard histogram reduction is the psum
    inside the tree grower (the Rabit-allreduce / MRTask-reduce-tree
    analog, hex/tree/xgboost/rabit/RabitTrackerH2O.java,
    water/MRTask.java:871).

    ``chunk`` is a PADDING BUCKET, not the exact tree count: the traced
    ``n_active`` scalar masks trailing trees (their margin contribution
    is zeroed; the driver drops them at finalize), so one compiled
    executable serves every remaining-tree count in the bucket —
    grid/AutoML variants with different ntrees reuse it. Sampling rates
    and learn-rate annealing ride as TRACED scalars for the same reason.

    ``adaptive`` selects the fused per-node-adaptive-bins kernel over raw
    features (codes_rm then carries raw X); ``binned`` the PACKED
    global-sketch path (codes_rm/codes_t carry int8/int16 codes with
    NA = W-1 through the fused binned kernel, split thresholds as bin
    indices); otherwise the matmul/scatter global-sketch path."""
    codes = CodesView(rm=codes_rm, t=codes_t if has_t else None)
    vcodes = vrm
    F = codes_rm.shape[1]
    shard = jax.lax.axis_index(axis_name) if axis_name else 0

    mono_a = mono if has_mono else None
    sets_a = sets if has_sets else None

    def build(gv, hv, wt, col_mask, key=None):
        if adaptive:
            return grow_tree_adaptive(codes_rm, gv, hv, wt, cfg, col_mask,
                                      root_lo, root_hi, axis_name=axis_name,
                                      nb_f=nb_f, mono=mono_a, sets=sets_a,
                                      key=key, model_axis=model_axis)
        if binned:
            return grow_tree_binned(codes_rm, gv, hv, wt, cfg, col_mask,
                                    axis_name=axis_name, mono=mono_a,
                                    sets=sets_a, key=key,
                                    model_axis=model_axis, ct=codes.t)
        return grow_tree(codes, gv, hv, wt, cfg, col_mask,
                         axis_name=axis_name, mono=mono_a, sets=sets_a,
                         key=key, model_axis=model_axis)

    def valid_contrib(tree):
        if adaptive:
            return predict_raw_tree(vrm, tree, cfg.max_depth)[0]
        # binned + global-sketch: bin-space walk (na_bin = W-1 packed)
        return predict_binned(vcodes, tree, cfg.max_depth, na_bin)[0]

    def one_tree(carry, i):
        margin, vmargin, lr = carry
        # padding-bucket mask: trees at i >= n_active are built but their
        # margin contribution is zeroed (finalize drops them host-side)
        lr_t = jnp.where(i < n_active, lr, 0.0)
        key = jax.random.fold_in(base_key, start_idx + i)
        key_r, key_c = jax.random.split(key)
        if axis_name is not None:
            # decorrelate row sampling across shards (same base key would
            # repeat the identical draw pattern on every shard); the column
            # key stays common so col_mask is identical everywhere
            key_r = jax.random.fold_in(key_r, shard)
        if sample_rate_per_class is not None:
            # hex/tree/SharedTree.java:210: per-class rates override
            # sample_rate (one rate per RESPONSE class — binomial runs
            # with internal K=1, so index by the tuple length)
            srpc = jnp.asarray(sample_rate_per_class, jnp.float32)
            thr = srpc[jnp.clip(y.astype(jnp.int32), 0,
                                len(sample_rate_per_class) - 1)]
            wt = w * (jax.random.uniform(key_r, w.shape) < thr)
        else:
            # always draw against the TRACED rate: uniform() < 1.0 is
            # identically True (draws live in [0, 1)), so rate=1.0 keeps
            # the exact unsampled weights while the executable is shared
            # across every sample_rate value
            wt = w * (jax.random.uniform(key_r, w.shape) < sample_rate)
        col_mask = jax.random.uniform(key_c, (F,)) < col_rate
        trees = []
        if K == 1:
            # hdelta rides as a traced scalar so data-derived huber deltas
            # don't fragment the compile cache
            dist = get_distribution(dist_name, tweedie_power, quantile_alpha,
                                    hdelta)
            g, h = dist.grad_hess(margin, y)
            tree, nid = build(g * wt, h * wt, wt, col_mask, key=key)
            # the grower already routed every row to its leaf — reuse
            # nid instead of re-walking the tree (saves ~250ms/tree@1M);
            # the leaf's value is selected, not gathered, while the
            # tree is small (tree.node_lookup: 74 -> 0.4 ms a tree at
            # 10M rows and 127 nodes, 30 -> 0.25 ms at 63)
            margin = margin + lr_t * node_lookup(tree["value"], nid)
            if has_valid:
                vmargin = vmargin + lr_t * valid_contrib(tree)
            trees.append(tree)
        else:
            p = jax.nn.softmax(margin, axis=1)
            for k in range(K):
                yk = (y == k).astype(jnp.float32)
                gk = (p[:, k] - yk)
                hk = jnp.maximum(p[:, k] * (1.0 - p[:, k]), 1e-9)
                tree, nid = build(gk * wt, hk * wt, wt, col_mask, key=key)
                margin = margin.at[:, k].add(
                    lr_t * node_lookup(tree["value"], nid))
                if has_valid:
                    vmargin = vmargin.at[:, k].add(lr_t * valid_contrib(tree))
                trees.append(tree)
        stacked = {kk: jnp.stack([t[kk] for t in trees])
                   for kk in trees[0]}
        return (margin, vmargin, lr * anneal), stacked

    (margin, vmargin, _), chunk_trees = jax.lax.scan(
        one_tree, (margin, vmargin, lr0), jnp.arange(chunk))
    return margin, vmargin, chunk_trees


@lru_cache(maxsize=128)
def _compiled_chunk(mesh, cfg, K, dist_name, tweedie_power, quantile_alpha,
                    sample_rate_per_class, na_bin, chunk, has_valid, has_t,
                    adaptive, binned=False, has_mono=False, has_sets=False,
                    donate=False):
    """Build + cache the sharded jitted chunk step for a given mesh/config.

    Rows ride the mesh 'data' axis; tree arrays come back replicated (every
    shard computes identical splits from the psum'd histograms — the same
    redundancy the reference's per-node DTree split scan has).

    ``donate=True`` donates the margin/vmargin operands: each chunk's
    margins are dead the moment the next chunk's outputs exist, so XLA
    reuses their HBM instead of holding two generations live. The driver
    only donates when early stopping is off (a stop rollback needs the
    committed chunk's buffers intact)."""
    # split search shards over the model axis whenever the mesh HAS one
    # (feature blocks per shard, all_gather'd winners — tree.py
    # _find_splits_sharded); H2O3_SPMD=0 keeps it off everywhere
    model_axis = (MODEL_AXIS
                  if mesh.shape[MODEL_AXIS] > 1 and spmd_enabled()
                  else None)
    body = partial(_gbm_chunk_body, cfg=cfg, K=K, dist_name=dist_name,
                   tweedie_power=tweedie_power, quantile_alpha=quantile_alpha,
                   sample_rate_per_class=sample_rate_per_class,
                   na_bin=na_bin, chunk=chunk,
                   has_valid=has_valid, has_t=has_t,
                   adaptive=adaptive, binned=binned, has_mono=has_mono,
                   has_sets=has_sets,
                   axis_name=DATA_AXIS, model_axis=model_axis)
    in_specs = (P(DATA_AXIS),                              # codes_rm / raw X
                P(None, DATA_AXIS) if has_t else P(DATA_AXIS),  # codes_t/dummy
                P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),  # margin, y, w
                P(DATA_AXIS), P(DATA_AXIS),                # vrm, vmargin
                P(), P(), P(), P(), P(), P(),       # key, lr0, hdelta, lo/hi, nb_f
                P(), P(), P(),                      # mono, sets, start
                P(), P(), P(), P())                 # n_active, rates, anneal
    out_specs = (P(DATA_AXIS), P(DATA_AXIS), P())
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return jax.jit(f, donate_argnums=(2, 6) if donate else ())


class H2OGradientBoostingEstimator(ModelBuilder):
    algo = "gbm"
    supports_streaming = True

    def __init__(self, **params):
        merged = dict(GBM_DEFAULTS)
        merged.update(params)
        super().__init__(**merged)

    # -- driver ---------------------------------------------------------

    def _resolve_distribution(self, spec: TrainingSpec) -> str:
        d = (self.params.get("distribution") or "auto").lower()
        if d in ("auto", ""):
            if spec.nclasses == 2:
                return "bernoulli"
            if spec.nclasses > 2:
                return "multinomial"
            return "gaussian"
        return d

    def _train_impl(self, spec: TrainingSpec, valid_spec, job: Job) -> GBMModel:
        dist_name = self._resolve_distribution(spec)
        if spec.stream:
            return self._train_streaming(spec, valid_spec, dist_name, job)
        try:
            return self._train_dense(spec, valid_spec, dist_name, job)
        except Exception as e:   # noqa: BLE001 — classified below
            from h2o3_tpu.resilience import is_oom
            if not is_oom(e):
                raise
            return self._degrade_to_streaming(spec, valid_spec, dist_name,
                                              job, e)

    def _degrade_to_streaming(self, spec: TrainingSpec, valid_spec,
                              dist_name, job: Job,
                              cause: BaseException) -> GBMModel:
        """Device OOM mid-train: degrade from the dense grower to the
        resident-window streamed path (water/Cleaner.java graceful
        degradation) instead of crashing the job — slower, but the
        train COMPLETES. The design matrix is pulled back to host and
        the streamed pipeline re-uploads only what its memman window
        allows resident."""
        from h2o3_tpu.log import warn
        warn("%s: device OOM during dense training (%s: %s) — degrading "
             "to the streamed resident-window path", self.algo,
             type(cause).__name__, cause)
        telemetry.counter(
            "h2o3_degrade_total", {"algo": self.algo},
            help="dense→streamed graceful degradations on device OOM"
        ).inc()
        from dataclasses import replace as dc_replace
        X_host = np.asarray(telemetry.device_get(spec.X,
                                                 pipeline="train"),
                            np.float32)
        host_spec = dc_replace(spec, X=None, X_host=X_host, stream=True)
        try:
            return self._train_streaming(host_spec, valid_spec, dist_name,
                                         job)
        except NotImplementedError as e2:
            # this configuration has no streamed fallback (multinomial,
            # huber, constraints, …): surface the ORIGINAL OOM — it is
            # the actionable failure — with the degrade refusal chained
            warn("%s: streamed fallback unavailable (%s) — re-raising "
                 "the device OOM", self.algo, e2)
            raise cause from e2

    def _train_dense(self, spec: TrainingSpec, valid_spec, dist_name,
                     job: Job) -> GBMModel:
        p = self.params
        K = spec.nclasses if spec.nclasses > 2 else 1
        task = ("binomial" if spec.nclasses == 2
                else "multinomial" if K > 1 else "regression")
        # the stages' clock: each phase is a live span under the thread's
        # train.train span, and train_profile is their durations
        prof = Profile()
        # the bin stage every dense tree trainer shares (models/tree.py):
        # which grower, then the sketch, digitise and pack it calls for
        inputs = prepare_tree_inputs(spec, p, int(p["max_depth"]), prof=prof,
                                     random_is_adaptive=True,
                                     set_splits=self._set_splits_wanted())
        adaptive, packed = inputs.adaptive, inputs.packed
        cfg, bm, pc = inputs.cfg, inputs.bm, inputs.pc
        root_lo, root_hi, nb_f = inputs.root_lo, inputs.root_hi, inputs.nb_f
        # the stage before the loop: prior or checkpoint, f0, the margins,
        # the chunk's other operands, their placement over the mesh and
        # the loop-entry fence
        with prof.phase("init"):
            y, w = spec.y, spec.w
            padded = spec.X.shape[0]
            if spec.offset is not None and K > 1:
                raise NotImplementedError(
                    "offset_column is not supported for multinomial GBM "
                    "(matching hex/tree/gbm/GBM.java offset restrictions)")
            prior = self._resolve_checkpoint(dist_name, spec)
            huber_delta = jnp.float32(1.0)
            if K == 1 and dist_name == "huber":
                # transition point = huber_alpha w-quantile of |resid - init|
                # on the OFFSET-ADJUSTED scale (the reference re-estimates per
                # scoring round; computed once here; w-weighted so pad/NA/
                # zero-weight rows can't skew it). The quantile STAYS a device
                # scalar: it feeds the chunk step as a traced operand and the
                # distribution's jnp ops, so the old mid-train device_get was
                # a pure pipeline stall
                from h2o3_tpu.models.distributions import (weighted_median,
                                                           weighted_quantile)
                yf0 = y.astype(jnp.float32)
                if spec.offset is not None:
                    yf0 = yf0 - spec.offset
                med = weighted_median(yf0, w)
                huber_delta = jnp.maximum(weighted_quantile(
                    jnp.abs(yf0 - med), w,
                    float(p.get("huber_alpha", 0.9))).astype(jnp.float32),
                    jnp.float32(1e-10))
            dist = (self._dist(dist_name, huber_delta) if K == 1 else None)
            if K == 1:
                yf = y.astype(jnp.float32)
                if prior is not None:
                    f0 = jnp.asarray(prior.f0)
                    margin, prior_has_offset = self._prior_margin(
                        prior, spec, padded, K)
                else:
                    if spec.offset is not None:
                        # initial value on the offset-adjusted scale, not the
                        # marginal init — early trees shouldn't spend capacity
                        # correcting a biased intercept
                        from h2o3_tpu.models.distributions import offset_adjusted_f0
                        f0 = offset_adjusted_f0(dist, yf, w, spec.offset)
                    else:
                        f0 = dist.init_f0(yf, w)
                    margin = jnp.full(padded, f0, jnp.float32)
                    prior_has_offset = False
                if spec.offset is not None and not prior_has_offset:
                    # offset enters the margin, not the trees: f = f0 + offset + Σ lr·tree
                    # (reference GBM honors offsets in every distribution's
                    # margin); a resumed margin already carries it
                    margin = margin + spec.offset
            else:
                if prior is not None:
                    f0 = jnp.asarray(prior.f0)
                    margin, _ = self._prior_margin(prior, spec, padded, K)
                else:
                    pri = jnp.maximum(
                        jnp.zeros(K, jnp.float32).at[y].add(w) / w.sum(), 1e-9)
                    f0 = jnp.log(pri)
                    margin = jnp.broadcast_to(f0, (padded, K)).astype(jnp.float32)
                yf = y
            seed = int(p.get("seed", -1) or -1)
            key = jax.random.PRNGKey(seed if seed != -1 else int(time.time() * 1e3) % (2**31))
            ntrees = int(p["ntrees"])
            start_trees = prior.ntrees_built if prior is not None else 0
            ntrees_new = ntrees - start_trees
            lr = float(p["learn_rate"])
            anneal = float(p["learn_rate_annealing"])
            lr *= anneal ** start_trees
            col_rate = float(p["col_sample_rate"]) * float(p["col_sample_rate_per_tree"])
            srpc = self.validate_sample_rate_per_class(spec)
            if srpc is not None and float(p.get("sample_rate", 1.0)) < 1.0:
                from h2o3_tpu.log import warn as _warn
                _warn("sample_rate is ignored when sample_rate_per_class "
                      "is specified (hex/tree/SharedTree.java:210)")
            keeper = ScoreKeeper(p.get("stopping_rounds", 0), p.get("stopping_metric"),
                                 p.get("stopping_tolerance", 1e-3), task)
            interval = max(int(p.get("score_tree_interval", 5) or 5), 1)
            # validation margin tracked with train edges
            mesh = current_mesh()
            nd = n_data_shards(mesh)
            # chunk operands; na_bin is the packed codes' reserved lane W-1
            Xtr, codes_t_arg, has_t, na_bin = inputs.operands(spec.X)
            if Xtr.shape[0] % nd != 0:
                raise ValueError(
                    f"padded row count {Xtr.shape[0]} is not divisible by "
                    f"the {nd}-shard data axis — the training frame was built "
                    f"under a different mesh; rebuild it after h2o3_tpu.init()")
            has_valid = valid_spec is not None
            if has_valid:
                if valid_spec.X.shape[0] % nd != 0:
                    raise ValueError(
                        f"validation frame padded rows {valid_spec.X.shape[0]} "
                        f"not divisible by the {nd}-shard data axis — rebuild it "
                        f"after h2o3_tpu.init()")
                if adaptive:
                    vtrain = valid_spec.X
                elif packed:
                    # validation codes share the training sketch AND the
                    # packed NA = W-1 convention (predict_binned walk)
                    vtrain = pack_codes_for(valid_spec.X, bm, pc.W, pc.widths)
                else:
                    vtrain = make_codes_view(digitize_with_edges(
                        valid_spec.X, bm.edges, bm.n_bins)).rm
                if prior is not None:
                    vmargin = prior._margin_matrix(valid_spec.X).astype(jnp.float32)
                else:
                    vmargin = (jnp.full(valid_spec.X.shape[0], f0, jnp.float32) if K == 1
                               else jnp.broadcast_to(f0, (valid_spec.X.shape[0], K)).astype(jnp.float32))
                if K == 1 and valid_spec.offset is not None:
                    vmargin = vmargin + valid_spec.offset
            else:  # small dummies (untraced branches, but args need shapes)
                vtrain = jnp.zeros((8 * nd, cfg.n_features), Xtr.dtype)
                vmargin = (jnp.zeros(8 * nd, jnp.float32) if K == 1
                           else jnp.zeros((8 * nd, K), jnp.float32))

            # scoring cadence: early stopping OR an explicit
            # score_tree_interval both record ScoreKeeper history (the
            # reference scores every interval regardless of stopping —
            # learning_curve_plot reads this)
            # reference default score_tree_interval=0 (score only at the
            # stopping cadence); ANY positive value is an explicit request
            sti = int(p.get("score_tree_interval", 0) or 0)
            score_each = keeper.rounds > 0 or sti > 0
            chunk = interval if score_each else min(ntrees_new, 50)
            # in-training checkpoints: align chunk commits to the checkpoint
            # cadence so every `tree_interval` committed trees persist a
            # resumable state (hex/tree/SharedTree in_training_checkpoints_*)
            ckpt_dir = p.get("in_training_checkpoints_dir")
            ckpt_interval = max(int(
                p.get("in_training_checkpoints_tree_interval", 1) or 1), 1)
            ckpt_on = bool(ckpt_dir)
            if ckpt_on and not score_each:
                # align chunk commits to the checkpoint cadence — but NEVER
                # when interval scoring is on: shrinking the chunk there
                # would change the early-stopping score cadence (a silent
                # model change); checkpoints then land at the scoring
                # chunk's commit boundaries instead
                chunk = max(min(chunk, ckpt_interval), 1)
            if ckpt_on and ntrees_new / ckpt_interval > 50:
                # each commit re-fetches every committed tree + writes a
                # full artifact (O(T²) across the train) — loud, not silent
                from h2o3_tpu.log import warn as _warn
                _warn("gbm: in_training_checkpoints_tree_interval=%d means "
                      "~%d checkpoint commits, each fetching all committed "
                      "trees and writing a full artifact — consider a "
                      "larger interval", ckpt_interval,
                      int(ntrees_new / ckpt_interval))
            trees_since_ckpt = 0
            # monotone constraints ({col: ±1}, hex/tree/DTree Constraints) and
            # interaction constraints ([[col,...],...], per-branch feature
            # allowance) ride as traced arrays through the chunk step
            mc = p.get("monotone_constraints") or {}
            has_mono = bool(mc)
            mono_arr = jnp.zeros(cfg.n_features, jnp.int32)
            if has_mono:
                mono_host = np.zeros(cfg.n_features, np.int32)
                for cname, direction in dict(mc).items():
                    if cname not in spec.names:
                        raise ValueError(
                            f"monotone_constraints column '{cname}' is not a "
                            f"training feature {list(spec.names)}")
                    if spec.is_cat[spec.names.index(cname)]:
                        raise ValueError(
                            f"monotone constraint on categorical column "
                            f"'{cname}' is not supported (reference restricts "
                            f"constraints to numeric columns)")
                    mono_host[spec.names.index(cname)] = int(direction)
                mono_arr = jnp.asarray(mono_host)
            ic = p.get("interaction_constraints") or None
            has_sets = bool(ic)
            sets_arr = jnp.ones((1, cfg.n_features), bool)
            if has_sets:
                sets_host = np.zeros((len(ic), cfg.n_features), bool)
                for si, group in enumerate(ic):
                    for cname in group:
                        if cname not in spec.names:
                            raise ValueError(
                                f"interaction_constraints column '{cname}' is "
                                f"not a training feature")
                        sets_host[si, spec.names.index(cname)] = True
                sets_arr = jnp.asarray(sets_host)
            # pin the margins to the data sharding BEFORE the first dispatch:
            # freshly-built margins (jnp.full of a traced f0) are replicated,
            # while every chunk OUTPUT is data-sharded — without this the
            # first call of each bucket compiles a second, replicated-operand
            # executable (visible as one stray recompile per new ntrees)
            from jax.sharding import NamedSharding
            rows_sh = NamedSharding(mesh, P(DATA_AXIS))
            margin = resilient_device_put(margin, rows_sh, pipeline="train")
            vmargin = resilient_device_put(vmargin, rows_sh,
                                           pipeline="train")
            # buffer donation is only safe when (a) an early stop can never
            # force a rollback to the previous chunk's margins and (b) no
            # in-training checkpoint will device_get a margin after it has
            # been donated to the next dispatch
            donate = (keeper.rounds == 0 and not ckpt_on
                      and jax.default_backend() == "tpu")
            sc_spec = valid_spec if has_valid else spec
            want_auc = keeper.metric == "auc"
            rate_t = jnp.float32(float(p["sample_rate"]))
            col_rate_t = jnp.float32(col_rate)
            anneal_t = jnp.float32(anneal)
            all_trees = []          # [(device chunk trees, n_active)]
            built = 0               # committed trees
            disp = 0                # dispatched trees (committed + in flight)
            inflight = None         # last dispatched, not yet committed chunk
            stopped = False
            # per-shard collective/straggler observations (ISSUE 8): the
            # commit point sits one chunk behind the dispatch frontier, so
            # watching the committed chunk's output shards there costs the
            # pipeline nothing the score fetch wasn't already paying
            shard_obs = []
            partn = partitioner(mesh)
            # performance accounting (ISSUE 11): per-executable cost capture
            # at this jit seam + the measured loop wall -> the train's
            # roofline point (None when telemetry is off — checked no-op)
            perf_acc = telemetry.costmodel.accumulator(
                "train.loop", n_devices=mesh.size)
            jax.block_until_ready(margin)  # h2o3-lint: allow[transfer-seam] loop-entry fence: resume-margin upload must land before the tree-loop clock starts

        def commit_ckpt(cur_margin):
            """Write an in-training checkpoint at the COMMITTED tree
            count (``built`` trees; ``cur_margin`` is their margin).
            The WHOLE commit — finalize's tree device_get included — is
            advisory: a transient fetch failure here must neither kill
            a healthy train nor mask the original error on the
            failure-path commit."""
            try:
                m = self._finalize(spec, None, dist_name, f0, all_trees,
                                   bm, cfg, K, built, cur_margin, None,
                                   keeper, tree_offset=start_trees,
                                   prior=prior, dist=dist,
                                   with_metrics=False)
                self._write_in_training_checkpoint(m, cur_margin,
                                                   ckpt_dir, spec=spec)
                from h2o3_tpu.telemetry import blackbox
                blackbox.record("ckpt_commit",
                                member=str(self.params.get("model_id")
                                           or self.algo),
                                payload=f"trees={built} algo={self.algo}")
            except Exception as e:  # noqa: BLE001 — advisory only
                from h2o3_tpu.log import warn
                warn("%s: in-training checkpoint commit failed: %s",
                     self.algo, e)

        chunks = 0              # dispatched chunks
        with prof.phase("loop") as sp_loop:
            # pipelined boosting: dispatch chunk k+1 BEFORE blocking on chunk
            # k's score scalars, so the metric fetch overlaps device compute.
            # With early stopping on, chunk k+1 is SPECULATIVE: a stop verdict
            # discards it (margins roll back to chunk k's outputs), keeping
            # the built-tree count identical to the serial loop.
            while disp < ntrees_new and not stopped:
                c = min(chunk, ntrees_new - disp)
                if score_each and c == chunk:
                    # full score intervals compile at their EXACT length: an
                    # off-bucket interval (say 6) repeats every chunk, and
                    # rounding it up would pay masked trees on EVERY chunk —
                    # one compile per interval value instead
                    bucket = c
                else:
                    # single-shot lengths (the non-scoring whole-train chunk,
                    # any final partial interval) round up to a shared bucket
                    # so grid/AutoML ntrees variants reuse the executable;
                    # masked waste is bounded by ONE chunk per train
                    bucket = chunk_bucket(c)
                # ONE spelling of the executable cache key, shared by the
                # dispatch and the cost capture below — the two must
                # describe the SAME executable or the accounting drifts
                lru_key = (mesh, cfg, K, dist_name,
                           float(p["tweedie_power"]),
                           float(p.get("quantile_alpha", 0.5)),
                           srpc, na_bin, bucket, has_valid, has_t,
                           adaptive, packed, has_mono, has_sets, donate)
                def _dispatch(lru_key=lru_key, c=c):
                    # compile + execute behind the fault seam: both the
                    # executable build and the chunk dispatch may fail
                    # transiently (the injected faults reproduce that)
                    from h2o3_tpu import faults
                    if faults.ACTIVE:
                        faults.check("compile", pipeline="train")
                    step = _compiled_chunk(*lru_key)
                    if faults.ACTIVE:
                        faults.check("execute", pipeline="train")
                        if nd > 1:
                            # ICI collective seam: the per-level histogram
                            # psum rides inside this dispatch on a multi-
                            # shard mesh — a transient interconnect failure
                            # surfaces here and retries like any other
                            # transient execute error
                            faults.check("collective", pipeline="train")
                    operands = (
                        Xtr, codes_t_arg, margin, yf, w, vtrain, vmargin,
                        key, jnp.float32(lr), huber_delta,
                        root_lo, root_hi, nb_f, mono_arr, sets_arr,
                        jnp.int32(start_trees + disp), jnp.int32(c),
                        rate_t, col_rate_t, anneal_t)
                    # AOT handle on this chunk executable, by shape only: it
                    # pins no device buffer and never reads a margin the
                    # dispatch below has donated. Only a committed operand
                    # keeps its sharding — the scalars and dummies follow
                    # the mesh as they do in the call. The cost capture
                    # lowers through it; chip_smoke.py reads the compiled HLO
                    self.chunk_lowering = partial(step.lower, *(
                        jax.ShapeDtypeStruct(
                            a.shape, a.dtype,
                            sharding=a.sharding if a.committed else None)
                        for a in operands))
                    return step(*operands)
                try:
                    # transient device failures retry with backoff; donated
                    # operand buffers cannot be replayed, so donation (TPU,
                    # no early stopping) disables the retry path
                    nm, nv, chunk_trees = retry_transient(
                        _dispatch, site="train.execute",
                        attempts=1 if donate else 3)
                    # dispatch is async — this clock starts when the chunk
                    # is enqueued, not when it completes, so THIS chunk's
                    # cold-bucket compile stays out of its own step numbers;
                    # a later chunk's compile delaying the observation is
                    # caught by shardstats' staleness check instead
                    t_disp = time.perf_counter()
                except BaseException:
                    # commit the already-computed in-flight chunk and leave
                    # a resumable checkpoint before the error propagates —
                    # a mid-train kill then resumes from the committed
                    # prefix instead of tree 0 (`margin` still holds that
                    # chunk's outputs; it is only rebound after dispatch)
                    if inflight is not None:
                        all_trees.append((inflight["trees"], inflight["c"]))
                        built += inflight["c"]
                        inflight = None
                        if ckpt_on:
                            commit_ckpt(margin)
                    raise
                if perf_acc is not None:
                    # per-executable FLOP/byte attribution: ONE trace+lower
                    # per (config, bucket) key for the process lifetime (NO
                    # backend compile — the zero-recompile guards never see
                    # it, and JAX serves the lowering from its own cache of
                    # the dispatch above: 2 ms on a v5e, PR 37); warm
                    # dispatches pay a dict lookup. scale=bucket:
                    # HLO cost analysis counts the tree-scan body once, and
                    # the executable runs it `bucket` times (masked trees
                    # included — they compute). The capture wall is noted
                    # so a cold key's trace+lower (host work inside the
                    # measured loop) is excluded from device seconds.
                    t_cap0 = time.perf_counter()
                    perf_acc.add(telemetry.costmodel.executable_cost(
                        ("gbm.chunk",) + lru_key, self.chunk_lowering,
                        scale=bucket))
                    perf_acc.note_capture_seconds(
                        time.perf_counter() - t_cap0)
                pend = None
                if score_each:
                    pend = self._score_entry_dev(nv if has_valid else nm,
                                                 sc_spec, dist, K,
                                                 start_trees + disp + c,
                                                 want_auc=want_auc)
                if inflight is not None:
                    # commit the previous chunk; its metric scalars land
                    # while the device crunches the chunk just dispatched
                    all_trees.append((inflight["trees"], inflight["c"]))
                    built += inflight["c"]
                    trees_since_ckpt += inflight["c"]
                    if nd > 1 and telemetry.enabled():
                        shard_obs.append(partn.observe_step(
                            inflight["trees"], inflight["t_disp"],
                            algo=self.algo))
                    if score_each:
                        with prof.phase("score"):
                            keeper.record(
                                self._score_entry_fetch(inflight["pend"]))
                        if keeper.rounds > 0 and keeper.should_stop():
                            # discard the speculative dispatch: the margin/
                            # vmargin locals still hold the COMMITTED chunk's
                            # outputs (they are only rebound to the new
                            # dispatch below), so breaking here is the
                            # rollback — nm/nv are simply never used
                            stopped = True
                            break
                    if ckpt_on and trees_since_ckpt >= ckpt_interval:
                        commit_ckpt(margin)   # margin = committed chunk's
                        trees_since_ckpt = 0
                inflight = {"trees": chunk_trees, "c": c, "pend": pend,
                            "t_disp": t_disp}
                margin, vmargin = nm, nv
                disp += c
                chunks += 1
                lr *= anneal ** c
                # progress by DISPATCHED trees: the committed count lags one
                # chunk behind and would sit at 0 through a one-chunk train
                job.set_progress(0.5 * disp / ntrees_new)
                if job.cancel_requested or job.preempt_requested:
                    break
            # checkpoint-based preemption (ISSUE 15): the scheduler asked
            # this train to yield — commit the prefix as a DKV checkpoint
            # (below) and unwind; user cancel wins and keeps its semantics.
            # A preempt that raced the last chunk (every tree dispatched) is
            # moot: the train just finishes.
            preempting = (job.preempt_requested and not job.cancel_requested
                          and not stopped and disp < ntrees_new)
            if not stopped and inflight is not None:
                all_trees.append((inflight["trees"], inflight["c"]))
                built += inflight["c"]
                trees_since_ckpt += inflight["c"]
                if nd > 1 and telemetry.enabled():
                    shard_obs.append(partn.observe_step(
                        inflight["trees"], inflight["t_disp"],
                        algo=self.algo))
                if score_each:
                    with prof.phase("score"):
                        keeper.record(
                            self._score_entry_fetch(inflight["pend"]))
                if (ckpt_on and trees_since_ckpt > 0) \
                        or (preempting and built > 0):
                    # final commit covers cancellation too: a cancelled job
                    # leaves a checkpoint at its committed tree count. A
                    # PREEMPTED train commits even without a checkpoint dir
                    # (DKV-only artifact) — that checkpoint's exact f32
                    # margin is what makes the scheduler's resume
                    # bit-identical
                    commit_ckpt(margin)
            if preempting:
                from h2o3_tpu.jobs import JobPreempted
                raise JobPreempted(
                    f"gbm train preempted at {built} committed trees"
                    + (f": {job.preempt_reason}" if job.preempt_reason
                       else ""))

            jax.block_until_ready(margin)  # h2o3-lint: allow[transfer-seam] train-loop timing fence: the loop span must cover device completion, not dispatch
            # the mesh layout and what the train all-reduced over it, and
            # the shards' collective/straggler observations
            mesh_attrs = inputs.mesh_attrs(mesh, built * K)
            from h2o3_tpu.parallel.shardstats import merge_observations
            collective = merge_observations(shard_obs)
            if sp_loop is not None:
                sp_loop.attrs.update(trees=built, chunks=chunks,
                                     **mesh_attrs, **inputs.loop_attrs())
                if collective and "straggler_ratio" in collective:
                    sp_loop.attrs["straggler_ratio"] = collective[
                        "straggler_ratio"]
        with prof.phase("finalize"):
            model = self._finalize(spec, valid_spec, dist_name, f0,
                                   all_trees, bm, cfg, K, built, margin,
                                   vmargin if has_valid else None, keeper,
                                   tree_offset=start_trees, prior=prior,
                                   dist=dist)
            if ckpt_on:
                # the finished model supersedes the in-training DKV
                # entry — leaving it would accumulate partial-model
                # copies (with dataset-sized resume margins) across
                # trains and surface phantom models on GET /3/Models;
                # disk artifacts remain
                from h2o3_tpu import dkv
                dkv.remove(f"{model.key}_ckpt")
        t_loop = prof.phases["loop"]
        model.output["training_loop_seconds"] = t_loop
        # the stage split that travels with the model: the phases' span
        # durations (model_base adds spec_s, queue_s, total_s, other_s)
        model.output["train_profile"] = {
            f"{key}_s": round(prof.phases.get(phase, 0.0), 4)
            for key, phase in (
                ("bin", "bin"), ("sketch", "bin.sketch"),
                ("digitize", "bin.digitize"), ("pack", "bin.pack"),
                ("init", "init"), ("loop", "loop"), ("score", "score"),
                ("finalize", "finalize"))}
        if perf_acc is not None:
            # measured device time = the loop wall (dispatches pipeline;
            # the block_until_ready fence above makes it device-
            # saturated) paired with the dispatched executables' cost
            perf_acc.add_device_seconds(t_loop)
            rp = perf_acc.finish()
            if rp is not None:
                model.output["perf"] = {"train": rp,
                                        "phases": {"loop": rp}}
        # hot-loop representation record (ISSUE 12): what the level
        # kernel actually streamed — bench.py and profile_train.py read
        # this for the bytes/row attribution
        model.output["packed_codes"] = inputs.record(mesh_attrs)
        if mesh_attrs.get("psum_bytes"):
            telemetry.counter(
                "h2o3_collective_bytes_total",
                {"algo": self.algo, "op": "psum"},
                help="bytes finished tree trains all-reduced over the "
                     "data axis (level histograms, leaf totals), from "
                     "shapes").inc(mesh_attrs["psum_bytes"])
        model.output["categorical_encoding"] = self._encoding_record(
            spec, inputs.set_features)
        # the dense chunk body traces its whole level loop into ONE
        # executable — every level rides a single dispatch (the fused
        # shape the streamed driver's L-level windows approximate)
        model.output["levels_per_dispatch"] = int(cfg.max_depth)
        # mesh layout this train actually ran under — the bench scaling
        # round and the SPMD parity tests assert against it instead of
        # inferring from env
        model.output["spmd"] = {
            "n_data": nd, "n_model": n_model_shards(mesh),
            "model_axis_split_search": bool(
                n_model_shards(mesh) > 1 and spmd_enabled())}
        # collective/straggler attribution for the scaling verdict
        # (tools/multichip_bench.py reads this per point)
        if collective is not None:
            model.output["spmd"]["collective"] = collective
        return model

    def _train_streaming(self, spec: TrainingSpec, valid_spec, dist_name,
                         job: Job) -> GBMModel:
        """Memory-pressure path: the frame exceeded the device budget, so
        X stays on host and every tree streams row chunks through the
        adaptive level kernels (models/tree.py
        grow_tree_adaptive_streamed over a models/streaming.py
        StreamedChunks pipeline: budget-sized resident window uploaded
        once per train, overflow chunks double-buffered per level;
        water/Cleaner.java graceful degradation — slower, but any frame
        that fits host RAM trains)."""
        from h2o3_tpu import memman
        from h2o3_tpu.models.streaming import StreamedChunks
        from h2o3_tpu.models.tree import grow_tree_adaptive_streamed
        p = self.params
        if spec.nclasses > 2:
            raise NotImplementedError(
                "multinomial GBM is not supported in streaming "
                "(memory-pressure) mode; raise H2O3_DEVICE_BUDGET_BYTES "
                "or reduce the frame")
        if valid_spec is not None:
            raise NotImplementedError(
                "validation_frame is not supported in streaming mode")
        # options the dense path honors but this path does not: fail
        # fast rather than silently train a different model
        if spec.offset is not None:
            raise NotImplementedError(
                "offset_column is not supported in streaming mode")
        if p.get("sample_rate_per_class"):
            raise NotImplementedError(
                "sample_rate_per_class is not supported in streaming "
                "mode")
        if float(p.get("col_sample_rate_change_per_level", 1.0)
                 or 1.0) != 1.0:
            raise NotImplementedError(
                "col_sample_rate_change_per_level is not supported in "
                "streaming mode")
        if dist_name == "huber":
            raise NotImplementedError(
                "huber distribution is not supported in streaming mode "
                "(its delta re-estimation needs the dense path)")
        if p.get("monotone_constraints") or p.get("interaction_constraints"):
            raise NotImplementedError(
                "monotone/interaction constraints are not supported in "
                "streaming mode")
        K = 1
        dist = self._dist(dist_name)
        X_host = spec.X_host
        rows = spec.nrow
        X_host = X_host[:rows]
        yw_host = telemetry.device_get((spec.y, spec.w),
                                       pipeline="train")
        y_host = np.asarray(yw_host[0])[:rows].astype(np.float32)
        w_host = np.asarray(yw_host[1])[:rows].astype(np.float32)
        budget = memman.manager().budget
        # packed binned-code streaming (ISSUE 12): bin once on host,
        # stream 1-2 byte codes — the compressed resident window fits
        # ~4x more rows under the same budget and overflow H2D moves
        # codes, not f32. histogram_type='random' keeps the adaptive
        # kernel (per-tree grid phase needs per-level rebinning).
        from h2o3_tpu.ops.binning import _edges_host, digitize_codes_host
        hist_type = (p.get("histogram_type") or "uniform_adaptive").lower()
        depth = int(p["max_depth"])
        # the dense trainers' rule (models/tree.py); whatever does not
        # pack streams the f32 window through the adaptive kernels
        path = partial(tree_path, hist_type, packed_codes_requested(p),
                       n_features=spec.n_features, max_depth=depth,
                       adaptive_fits=True)
        packed = path(None) == "packed"
        bin_edges = None
        W = None
        if packed:
            # feasibility from the (cheap) edge sketch BEFORE paying
            # the O(rows·F) host digitise — an infeasible bin count
            # must not build a throwaway code matrix on the
            # memory-pressure path
            try:
                bin_edges, n_bins_eff = _edges_host(
                    X_host, rows, spec.is_cat, max(int(p["nbins"]), 2),
                    int(p.get("nbins_cats", 1024)), hist_type)
                packed = path(n_bins_eff) == "packed"
            except ValueError:
                packed = False      # bin count past the routing cap
            if packed:
                codes_host, W = digitize_codes_host(X_host, bin_edges,
                                                    n_bins_eff)
        if packed:
            cfg = tree_config(p, depth, n_bins_eff, spec.n_features)
            root_lo = root_hi = nb_f = None
            x_stream = codes_host
            x_itemsize = int(codes_host.dtype.itemsize)
        else:
            cfg, root_lo, root_hi, nb_f = adaptive_setup(spec, p, depth)
            x_stream = X_host
            x_itemsize = 4
        chunk_rows = int(max(min(
            budget // max(spec.n_features * x_itemsize * 4, 1), rows),
            16384))
        padded = int(spec.y.shape[0])
        # checkpoint continuation (formerly a streamed-path fail-fast,
        # ISSUE 9 satellite): the dense resolver's full compatibility
        # contract applies; the resume state is the saved f32 margin
        # plus the tree cursor (start_trees), so a resumed streamed
        # train is bit-identical to an uninterrupted one — and to the
        # DENSE resume on fully-resident data
        prior = self._resolve_checkpoint(dist_name, spec)
        start_trees = prior.ntrees_built if prior is not None else 0
        margin0 = None
        if prior is not None:
            f0 = float(np.asarray(prior.f0).reshape(-1)[0])
            rm = getattr(prior, "_resume_margin", None)
            sig = getattr(prior, "_resume_sig", None)
            sig_ok = (sig is None
                      or np.array_equal(np.asarray(sig),
                                        _spec_signature(spec)))
            if rm is not None and sig_ok \
                    and np.asarray(rm).shape == (padded,):
                margin0 = np.asarray(rm, np.float32)
            else:
                from h2o3_tpu.log import warn as _warn
                if rm is not None and not sig_ok:
                    _warn("checkpoint resume margin belongs to "
                          "different training data — recomputing from "
                          "trees")
                # recompute chunk-wise: the whole host matrix must
                # never upload at once on this memory-pressure path
                margin0 = np.empty(rows, np.float32)
                for s in range(0, rows, chunk_rows):
                    e = min(s + chunk_rows, rows)
                    margin0[s:e] = np.asarray(jax.device_get(  # h2o3-lint: allow[transfer-seam,host-sync-hot-loop] once-per-RESUME chunked recompute on the memory-pressure path, not the tree loop
                        prior._margin_matrix(jnp.asarray(X_host[s:e]))
                        .astype(jnp.float32)))
        else:
            f0 = float(telemetry.device_get(
                dist.init_f0(jnp.asarray(y_host), jnp.asarray(w_host)),
                pipeline="train"))
        ntrees = int(p["ntrees"])
        ntrees_new = ntrees - start_trees
        anneal = float(p.get("learn_rate_annealing", 1.0) or 1.0)
        lr = float(p["learn_rate"]) * anneal ** start_trees
        col_rate = (float(p.get("col_sample_rate", 1.0))
                    * float(p.get("col_sample_rate_per_tree", 1.0)))
        seed = int(p.get("seed", -1) or -1)
        key = jax.random.PRNGKey(seed if seed != -1 else 0)
        chunks = StreamedChunks(x_stream, y_host, w_host, f0, chunk_rows,
                                padded_rows=padded, margin0=margin0,
                                packed_W=W if packed else None)
        # cancel propagation into the streamed pipeline: the level
        # passes poll this BETWEEN levels (never mid leaf-apply), so a
        # REST cancel / watchdog max_runtime kill lands promptly even
        # inside a deep tree's chunk uploads
        chunks.cancel_check = lambda: job.cancel_requested
        # fused-window clamp (ISSUE 17): a pending preempt OR cancel
        # shrinks the next L-level window to one level so the
        # cooperative yield lands at the next boundary — the PR-15
        # chunk-commit contract survives multi-level fusion
        chunks.interrupt_check = lambda: job.preempt_requested
        # performance accounting (ISSUE 11): the streamed level passes
        # feed this through chunks.perf_acc (tree.py captures each level
        # kernel's cost once per shape); coverage noted — the routing/
        # leaf-apply passes are not costed
        perf_acc = telemetry.costmodel.accumulator(
            "train.stream", note="level-histogram kernels only")
        chunks.perf_acc = perf_acc
        from h2o3_tpu.jobs import JobCancelled
        trees = []

        def build_model(trees_list):
            """Partial/final GBMModel from the committed streamed trees
            (prior trees prepended, dense-_finalize shape) — shared by
            the in-training checkpoint commits and the train tail."""
            T = len(trees_list)
            th = {k: np.stack([tr[k] for tr in trees_list]) for k in
                  ("feat", "thr", "na_left", "is_split", "value",
                   "node_w")}
            # a checkpoint trained on the packed path may hold sets; the
            # streamed growers split by threshold
            sets = _stack_sets(prior, th)
            if prior is not None:
                th = {
                    "feat": np.concatenate(
                        [np.asarray(prior._feat), th["feat"]]),
                    "thr": np.concatenate(
                        [np.asarray(prior._thr), th["thr"]]),
                    "na_left": np.concatenate(
                        [np.asarray(prior._na_left), th["na_left"]]),
                    "is_split": np.concatenate(
                        [np.asarray(prior._is_split), th["is_split"]]),
                    "value": np.concatenate(
                        [np.asarray(prior._value), th["value"]]),
                    "node_w": (np.concatenate(
                        [np.asarray(prior._node_w), th["node_w"]])
                        if getattr(prior, "_node_w", None) is not None
                        else None),
                }
            th.update(sets)
            m = GBMModel(self._model_key(), p, spec,
                         dist_name, np.float32(f0), th, [],
                         cfg.n_bins, cfg.max_depth, start_trees + T,
                         spec.nclasses)
            gains = np.stack([tr["gain"] for tr in trees_list])
            feat = np.stack([tr["feat"] for tr in trees_list])
            vi = np.zeros(len(spec.names))
            live = feat >= 0
            np.add.at(vi, feat[live], gains[live])
            if prior is not None:
                pv = prior.output.get("variable_importances")
                if pv:
                    lut = {nn: i for i, nn in enumerate(spec.names)}
                    for nn, g in zip(pv["variable"],
                                     pv["relative_importance"]):
                        if nn in lut:
                            vi[lut[nn]] += g
            order = np.argsort(-vi)
            rel = vi / vi.max() if vi.max() > 0 else vi
            m.output["variable_importances"] = {
                "variable": [spec.names[i] for i in order],
                "relative_importance": vi[order].tolist(),
                "scaled_importance": rel[order].tolist(),
                "percentage": (vi[order] / vi.sum() if vi.sum() > 0
                               else vi[order]).tolist()}
            return m

        def attach_resume_state(m):
            """The streamed resume state: the exact f32 margin at the
            committed tree count (window-cursor = ntrees_built) + the
            PR-6 data signature, so resumes are bit-identical and
            never applied to a different frame."""
            mfull = chunks.gather_margin()
            mpad = np.full(padded, np.float32(f0), np.float32)
            mpad[:rows] = mfull      # pad rows carry w=0 everywhere
            m._resume_margin = mpad
            m._resume_sig = _spec_signature(spec)

        # in-training checkpoints on the resident-window path (formerly
        # a warn-and-drop): every tree_interval committed trees persist
        # a resumable artifact, same contract as the dense path
        ckpt_dir = p.get("in_training_checkpoints_dir")
        ckpt_interval = max(int(
            p.get("in_training_checkpoints_tree_interval", 1) or 1), 1)
        ckpt_on = bool(ckpt_dir)
        trees_since_ckpt = 0

        def commit_ckpt():
            # advisory end to end (dense commit_ckpt contract): a
            # checkpoint write must neither kill a healthy train nor
            # mask the original error on the failure-path commit
            try:
                from h2o3_tpu.models.model_base import \
                    persist_in_training_ckpt
                m = build_model(trees)
                attach_resume_state(m)
                persist_in_training_ckpt(m, self.algo, ckpt_dir)
                from h2o3_tpu.telemetry import blackbox
                blackbox.record("ckpt_commit",
                                member=str(p.get("model_id")
                                           or self.algo),
                                payload=f"trees={len(trees)} "
                                        f"algo={self.algo} streamed=1")
            except Exception as ce:  # noqa: BLE001 — advisory only
                from h2o3_tpu.log import warn as _warn
                _warn("%s: streamed in-training checkpoint commit "
                      "failed: %s", self.algo, ce)

        t0 = time.monotonic()
        for t in range(ntrees_new):
            # global tree index keys the RNG (dense start_idx contract)
            # so a resumed train draws the same samples the
            # uninterrupted one would have
            tkey = jax.random.fold_in(key, start_trees + t)
            col_mask = None
            if col_rate < 1.0:
                col_mask = (jax.random.uniform(
                    jax.random.fold_in(tkey, 1), (spec.n_features,))
                    < col_rate)
            try:
                if packed:
                    from h2o3_tpu.models.tree import \
                        grow_tree_binned_streamed
                    tree = grow_tree_binned_streamed(
                        chunks, dist, lr, cfg, bin_edges, key=tkey,
                        sample_rate=float(p.get("sample_rate", 1.0)),
                        col_mask=col_mask)
                else:
                    tree = grow_tree_adaptive_streamed(
                        chunks, dist, lr, cfg, root_lo, root_hi, nb_f,
                        key=tkey,
                        sample_rate=float(p.get("sample_rate", 1.0)),
                        col_mask=col_mask)
            except JobCancelled:
                # the partial tree applied no margin updates (cancel
                # only fires between level passes, before leaf apply) —
                # drop it and finalize the committed trees
                break
            except BaseException:
                # NO failure-path commit here (unlike the dense path,
                # whose per-chunk margin is an immutable device array
                # rebound only at commit points): the streamed grower
                # mutates margin_host chunk-by-chunk DURING leaf apply,
                # so a mid-tree error leaves margins that partially
                # include the failed tree — committing them would
                # silently break resume bit-identity. The last interval
                # commit is the resumable prefix.
                raise
            # lr-scale values like the dense finalize does (float64
            # product rounded once at model construction — bit-matching
            # `val * lrs[:, None]` in _finalize)
            tree = dict(tree)
            tree["value"] = tree["value"].astype(np.float64) * lr
            trees.append(tree)
            trees_since_ckpt += 1
            lr *= anneal
            if ckpt_on and trees_since_ckpt >= ckpt_interval \
                    and len(trees) < ntrees_new:
                commit_ckpt()
                trees_since_ckpt = 0
            job.set_progress((t + 1) / ntrees_new)
            if job.cancel_requested or job.preempt_requested:
                break
        preempting = (job.preempt_requested and not job.cancel_requested
                      and len(trees) < ntrees_new)
        if preempting:
            # checkpoint-based preemption (ISSUE 15): commit the built
            # prefix (DKV-only when no checkpoint dir is set) and unwind
            # so the scheduler can requeue + resume bit-identically —
            # margin_host holds exactly the committed trees' updates.
            # Zero trees built → no checkpoint; the requeue reruns clean.
            if trees:
                commit_ckpt()
            from h2o3_tpu.jobs import JobPreempted
            raise JobPreempted(
                f"gbm streamed train preempted at {len(trees)} trees"
                + (f": {job.preempt_reason}" if job.preempt_reason
                   else ""))
        if not trees:
            raise JobCancelled(
                "cancelled before the first streamed tree completed")
        margin_host = chunks.gather_margin()
        t_loop = time.monotonic() - t0
        T = len(trees)
        model = build_model(trees)
        if ckpt_on:
            # final commit: durable artifact kept, DKV `<key>_ckpt`
            # dropped — the finished model supersedes it (dense/DRF
            # final=True contract); resume state rides the artifact so
            # continue-training stays bit-identical. The state is
            # attached to a COPY (the dense commit_ckpt contract): the
            # RETURNED model must not pin a dataset-sized margin in the
            # DKV or serialize it into every later save_model
            try:
                import copy as _copy

                from h2o3_tpu.models.model_base import \
                    persist_in_training_ckpt
                mfinal = _copy.copy(model)   # shares the tree arrays
                attach_resume_state(mfinal)
                persist_in_training_ckpt(mfinal, self.algo, ckpt_dir,
                                         final=True)
            except Exception as ce:  # noqa: BLE001 — advisory only
                from h2o3_tpu.log import warn as _warn
                _warn("%s: final streamed checkpoint failed: %s",
                      self.algo, ce)
        model.output["training_loop_seconds"] = t_loop
        model.output["streamed"] = True
        model.output["packed_codes"] = packed_codes_record(
            packed, dtype=x_stream.dtype, W=W,
            bytes_per_value=x_itemsize, n_bins=cfg.n_bins)
        model.output["categorical_encoding"] = self._encoding_record(spec, 0)
        # multi-level fusion record (ISSUE 17): the resolved
        # H2O3_LEVELS_PER_PASS window, and how many levels each device
        # dispatch actually covered — fused only on the packed
        # single-chunk path (a multi-chunk window still batches its
        # host syncs but keeps per-level dispatches for the cross-chunk
        # histogram reduction)
        lpp = (levels_per_pass(cfg.max_depth, cfg.n_features, W)
               if packed else 1)
        model.output["levels_per_dispatch"] = int(
            lpp if (packed and chunks.C == 1) else 1)
        if perf_acc is not None:
            perf_acc.add_device_seconds(t_loop)
            rp = perf_acc.finish()
            if rp is not None:
                model.output["perf"] = {"train": rp,
                                        "phases": {"levels": rp}}
        # transfer accounting for the bench guard: h2d bytes per tree vs
        # the dataset's device footprint (once-per-tree contract). The
        # count is the pipeline's OWN tally (chunks.h2d_bytes), not a
        # process-global counter delta — concurrent serve/parse traffic
        # must not be attributed to this train
        sp = chunks.profile()
        sp["trees"] = T
        sp["levels_per_pass"] = int(lpp)
        # steady-state per-tree traffic: the once-per-train resident
        # window upload is reported separately, not amortized — at
        # ntrees=1 amortization would read ~1.6x footprint and false-
        # fail the once-per-tree guard even though each chunk crossed
        # the bus exactly once
        sp["h2d_bytes_per_tree"] = (
            (sp["h2d_bytes"] - sp["h2d_resident_bytes"]) / T) if T else 0
        model.output["stream_profile"] = sp
        mpad = np.full(padded, f0, np.float32)
        mpad[:rows] = margin_host       # pad rows carry w=0 in metrics
        model.training_metrics = self._metrics_from_margin(
            jnp.asarray(mpad), spec, dist_name, K, dist=dist)
        return model

    # categorical_encoding values (hex/Model.Parameters.CategoricalEncoding
    # Scheme) and what this trainer does with each: auto and enum are one
    # bin a level and a split on a SET of levels; label_encoder is the
    # level index as a number, a threshold; the rest are not built
    _SET_ENCODINGS = ("auto", "enum")
    _ORDINAL_ENCODINGS = ("label_encoder", "labelencoder")

    def _encoding(self) -> str:
        return str(self.params.get("categorical_encoding")
                   or "auto").lower()

    def _set_splits_wanted(self) -> bool:
        return self._encoding() not in self._ORDINAL_ENCODINGS

    def _encoding_record(self, spec, set_features: int) -> dict:
        """``model.output["categorical_encoding"]``: what was asked, what
        the trees hold, and once in the log where the two differ."""
        asked, enums = self._encoding(), int(sum(map(bool, spec.is_cat)))
        applied = ("none" if not enums else "enum" if set_features
                   else "ordinal")
        rec = {"requested": asked, "applied": applied,
               "enum_features": enums, "set_features": int(set_features)}
        if asked not in self._SET_ENCODINGS + self._ORDINAL_ENCODINGS:
            rec["honoured"] = False
            rec["note"] = (f"categorical_encoding={asked!r} is not "
                           f"implemented; enum columns were trained as "
                           f"{applied!r}")
        elif enums and asked in self._SET_ENCODINGS and not set_features:
            rec["honoured"] = False
            rec["note"] = ("category-set splits need the packed level "
                           "kernel (packed_codes; dense, not histogram_type"
                           "='random', levels within nbins_cats and VMEM): "
                           "enum columns were split by threshold on the "
                           "level index")
        else:
            rec["honoured"] = True
        if not rec["honoured"] and enums:
            from h2o3_tpu.log import warn
            warn("%s: %s", self.algo, rec["note"])
        return rec

    def _dist(self, dist_name: str, huber_delta: float = 1.0):
        if str(dist_name).lower().startswith("custom"):
            # UDF family (water/udf CDistributionFunc): an instance on
            # custom_distribution_func wins over the registry lookup
            cdf = self.params.get("custom_distribution_func")
            if cdf is not None and not isinstance(cdf, str):
                return get_distribution(cdf)
        return get_distribution(dist_name,
                                float(self.params.get("tweedie_power", 1.5)),
                                float(self.params.get("quantile_alpha", 0.5)),
                                huber_delta)

    def _resolve_checkpoint(self, dist_name: str, spec: TrainingSpec):
        """Continue-training support (hex/Model.java:487 _checkpoint): the
        checkpoint model's trees seed the margin; ntrees is the TOTAL tree
        count, so training builds ntrees - prior.ntrees_built new trees."""
        ckpt = self.params.get("checkpoint")
        if not ckpt:
            return None
        prior = _resolve_checkpoint_source(ckpt, GBMModel, "GBM")
        if prior.dist_name != dist_name:
            raise ValueError(
                f"checkpoint distribution '{prior.dist_name}' != "
                f"'{dist_name}' (checkpoint params must match — "
                f"hex/ModelBuilder checkpoint contract)")
        if prior.max_depth != int(self.params["max_depth"]):
            raise ValueError("checkpoint max_depth differs")
        if int(self.params["ntrees"]) <= prior.ntrees_built:
            raise ValueError(
                f"ntrees ({self.params['ntrees']}) must exceed the "
                f"checkpoint's ntrees_built ({prior.ntrees_built})")
        if list(prior.feature_names) != list(spec.names):
            raise ValueError(
                f"checkpoint feature set {prior.feature_names} differs from "
                f"the training spec's {spec.names} — the prior trees' feature "
                f"indices would address the wrong columns")
        # response/domain compatibility (SharedTree/ModelBuilder checkpoint
        # contract): a different class count would silently corrupt the
        # margin columns under jit's clamped indexing; different categorical
        # domains would misroute the prior trees' enum-code thresholds
        if prior.nclasses != spec.nclasses:
            raise ValueError(
                f"checkpoint has {prior.nclasses} response classes but the "
                f"training frame has {spec.nclasses}")
        prd = tuple(prior.response_domain) if prior.response_domain else None
        srd = tuple(spec.response_domain) if spec.response_domain else None
        if prd != srd:
            raise ValueError(
                f"checkpoint response domain {prior.response_domain} differs "
                f"from the training frame's {spec.response_domain}")
        # normalize to tuples: domains loaded from disk round-trip as lists
        pcd = {k: tuple(v) for k, v in prior.cat_domains.items()}
        scd = {k: tuple(v) for k, v in spec.cat_domains.items()}
        if pcd != scd:
            raise ValueError(
                "checkpoint categorical domains differ from the training "
                "frame's — prior trees' enum-code splits would misroute")
        return prior

    def _prior_margin(self, prior, spec, padded, K):
        """Training margin to resume from. An in-training checkpoint
        carries the EXACT f32 margin at its committed tree count
        (``resume_margin``) — resuming from it reproduces the
        uninterrupted train bit-for-bit. A plain saved model recomputes
        the margin from its trees (correct to f32 summation order, not
        bit-guaranteed). Returns (margin, includes_offset)."""
        rm = getattr(prior, "_resume_margin", None)
        if rm is not None:
            rm = np.asarray(rm)
            want = (padded,) if K == 1 else (padded, K)
            sig = getattr(prior, "_resume_sig", None)
            sig_ok = (sig is None
                      or np.array_equal(np.asarray(sig),
                                        _spec_signature(spec)))
            if rm.shape == tuple(want) and sig_ok:
                # a checkpointed margin already includes any offset the
                # train carried — the caller must not add it again
                return jnp.asarray(rm, jnp.float32), True
            from h2o3_tpu.log import warn
            if not sig_ok:
                # continue-on-new-data: the saved margin belongs to a
                # DIFFERENT frame — applying it would silently train
                # against stale state; recompute from trees instead
                warn("checkpoint resume margin belongs to different "
                     "training data — recomputing from trees")
            else:
                warn("checkpoint resume margin shape %s != expected %s "
                     "— recomputing from trees", rm.shape, want)
        # recomputed from trees WITHOUT the offset — the caller must
        # still add spec.offset (f = f0 + offset + Σ lr·tree)
        return prior._margin_matrix(spec.X).astype(jnp.float32), False

    def _write_in_training_checkpoint(self, model, margin, ckpt_dir,
                                      spec=None):
        """Persist an in-training checkpoint: the partial model + its
        exact f32 training margin (the resume state that makes a
        resumed train bit-identical) + a cheap data fingerprint so the
        margin is never applied to a DIFFERENT training frame."""
        from h2o3_tpu.models.model_base import persist_in_training_ckpt
        model._resume_margin = np.asarray(
            telemetry.device_get(margin, pipeline="train"), np.float32)
        if spec is not None:
            model._resume_sig = _spec_signature(spec)
        return persist_in_training_ckpt(model, self.algo, ckpt_dir)

    def _score_entry_dev(self, margin, sc_spec, dist, K, built,
                         want_auc: bool = False):
        """Dispatch the interval-score reduction ON DEVICE and return a
        pending entry of device scalars — the driver fetches them with
        ``_score_entry_fetch`` only after the next chunk is in flight, so
        the metric transfer never stalls the boosting pipeline."""
        w = sc_spec.w
        y = sc_spec.y
        if K == 1:
            mu = dist.predict(margin)
            yf = y.astype(jnp.float32)
            vals = {"deviance": dist.deviance(w, yf, mu)}
            if dist.name == "bernoulli" and want_auc:
                from h2o3_tpu.models.metrics import auc_device
                vals["auc"] = auc_device(mu, yf, w)
            return ("k1", dist.name, built, vals)
        probs = jax.nn.softmax(margin, axis=1)
        eps = 1e-7  # f32-safe: 1-1e-15 rounds to 1.0f -> log1p(-1) = -inf
        py = jnp.clip(probs[jnp.arange(probs.shape[0]), y], eps, 1.0)
        return ("multi", None, built,
                {"logloss": -(w * jnp.log(py)).sum() / w.sum()})

    def _score_entry_fetch(self, pend) -> Dict:
        """Materialize a pending score entry: ONE device_get for all of
        the interval's scalars."""
        kind, dname, built, vals = pend
        h = telemetry.device_get(vals, pipeline="train")
        if kind != "k1":
            ll = float(h["logloss"])
            return {"ntrees": built, "logloss": ll, "deviance": ll}
        dev = float(h["deviance"])
        entry = {"ntrees": built, "deviance": dev}
        if dname == "gaussian":
            entry["mse"] = dev
            entry["rmse"] = float(np.sqrt(max(dev, 0)))
        if dname == "bernoulli":
            entry["logloss"] = dev / 2.0
            if "auc" in h:
                entry["auc"] = float(h["auc"])
        return entry

    def _finalize(self, spec, valid_spec, dist_name, f0, all_trees, bm, cfg,
                  K, built, margin, vmargin, keeper, tree_offset=0,
                  prior=None, dist=None, with_metrics=True) -> GBMModel:
        M = cfg.n_nodes
        # ONE pytree device_get for every chunk's trees, deferred to here
        # — nothing tree-shaped crosses to the host inside the boosting
        # loop (collect_chunk_trees slices off the padding-bucket tails)
        th = collect_chunk_trees(all_trees, M,
                                 bm.edges if bm is not None else [])
        feat = th["feat"]
        nal = th["na_left"]
        spl = th["is_split"]
        val = th["value"]
        gains = th["gain"]
        node_w = th["node_w"]
        thr = th["thr"]
        lr0 = float(self.params["learn_rate"])
        anneal = float(self.params["learn_rate_annealing"])
        lrs = lr0 * anneal ** np.repeat(
            np.arange(tree_offset, tree_offset + built), max(K, 1))
        val_scaled = val * lrs[:, None]
        trees_host = {"feat": feat, "thr": thr, "na_left": nal,
                      "is_split": spl, "value": val_scaled, "node_w": node_w}
        sets = _stack_sets(prior, th)
        if prior is not None:
            # checkpoint continuation: prepend the prior model's trees
            # (already lr-scaled) in (tree, class) order
            trees_host = {
                "feat": np.concatenate([np.asarray(prior._feat), feat]),
                "thr": np.concatenate([np.asarray(prior._thr), thr]),
                "na_left": np.concatenate([np.asarray(prior._na_left), nal]),
                "is_split": np.concatenate([np.asarray(prior._is_split), spl]),
                "value": np.concatenate([np.asarray(prior._value), val_scaled]),
                "node_w": (np.concatenate([np.asarray(prior._node_w), node_w])
                           if getattr(prior, "_node_w", None) is not None
                           else None),
            }
        trees_host.update(sets)
        f0_host = np.asarray(telemetry.device_get(f0, pipeline="train"))
        model = GBMModel(self._model_key(), self.params,
                         spec, dist_name, f0_host, trees_host,
                         bm.edges if bm is not None else [],
                         bm.n_bins if bm is not None else cfg.n_bins,
                         cfg.max_depth, tree_offset + built,
                         spec.nclasses)
        # variable importances from split gains (merged with the prior's on
        # checkpoint continuation)
        vi = np.zeros(len(spec.names))
        live = feat >= 0
        np.add.at(vi, feat[live], gains[live])
        if prior is not None:
            pv = prior.output.get("variable_importances")
            if pv:
                lut = {n: i for i, n in enumerate(spec.names)}
                for n, g in zip(pv["variable"], pv["relative_importance"]):
                    if n in lut:
                        vi[lut[n]] += g
        order = np.argsort(-vi)
        rel = vi / vi.max() if vi.max() > 0 else vi
        model.output["variable_importances"] = {
            "variable": [spec.names[i] for i in order],
            "relative_importance": vi[order].tolist(),
            "scaled_importance": rel[order].tolist(),
            "percentage": (vi[order] / vi.sum() if vi.sum() > 0 else vi[order]).tolist(),
        }
        model.scoring_history = keeper.history
        if with_metrics:
            # how many splits the trees hold and how many are sets: host
            # counts of arrays already fetched
            for name, n, what in (
                    ("h2o3_tree_splits_total", int(spl.sum()),
                     "splits in the trees of finished tree trains"),
                    ("h2o3_tree_set_splits_total",
                     int(th["is_set"].sum()) if "is_set" in th else 0,
                     "of them splits on a set of an enum's levels")):
                telemetry.counter(name, {"algo": self.algo}, help=what).inc(n)
            # final metrics from the training margin (exact, no
            # re-predict); in-training checkpoints skip this — they are
            # resume state, not reporting artifacts
            model.training_metrics = self._metrics_from_margin(
                margin, spec, dist_name, K, dist=dist)
            if vmargin is not None:
                model.validation_metrics = self._metrics_from_margin(
                    vmargin, valid_spec, dist_name, K, dist=dist)
        return model

    def _metrics_from_margin(self, margin, spec, dist_name, K, dist=None):
        if spec.nclasses == 2:
            p1 = 1.0 / (1.0 + jnp.exp(-margin))
            probs = jnp.stack([1.0 - p1, p1], axis=1)
            return compute_metrics(probs, spec.y, spec.w, 2, spec.response_domain)
        if K > 1:
            probs = jax.nn.softmax(margin, axis=1)
            return compute_metrics(probs, spec.y, spec.w, K, spec.response_domain)
        dist = dist if dist is not None else self._dist(dist_name)
        mu = dist.predict(margin)
        dev = float(telemetry.device_get(
            dist.deviance(spec.w, spec.y.astype(jnp.float32), mu),
            pipeline="train"))
        return compute_metrics(mu, spec.y, spec.w, 1, deviance=dev)


from h2o3_tpu.persist import register_model_class  # noqa: E402

register_model_class("gbm", GBMModel)
