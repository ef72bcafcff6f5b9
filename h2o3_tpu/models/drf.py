"""DRF — distributed random forest on the shared histogram tree machinery.

Reference: hex/tree/drf/DRF.java:30 over hex/tree/SharedTree.java — per-node
mtries feature subsets, row sampling per tree (default 0.632), OOB
("out-of-bag") scoring reported as the training metrics, class-probability
leaves (each tree's leaf stores the weighted class fraction / mean
response, not a boosting step).

TPU re-design: trees are independent, so a whole chunk builds inside one
shard_mapped lax.scan (like GBM's chunk step, models/gbm.py) with the
histogram psum over the 'data' mesh axis; mtries is a per-node random
feature mask drawn inside grow_tree (models/tree.py). Leaf values come
from the same Newton formula with (g, h) = (-y·w, w) ⇒ leaf = weighted
mean of the (indicator) response — the variance-reduction criterion.
Static-shape note: trees are complete binary arrays, so max_depth is
capped at 16 (the reference default is 20, practically limited by
min_rows; histograms at depth d need 2^(d-1)·F·(B+1)·3 floats).
"""
from __future__ import annotations

import time
from functools import lru_cache, partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from h2o3_tpu import telemetry
from h2o3_tpu.jobs import Job
from h2o3_tpu.models.model_base import (Model, ModelBuilder, ScoreKeeper,
                                        TrainingSpec, compute_metrics)
from h2o3_tpu.log import Profile
from h2o3_tpu.models.tree import (chunk_bucket, collect_chunk_trees,
                                  grow_tree, grow_tree_adaptive,
                                  grow_tree_binned, node_lookup,
                                  predict_raw_stacked, prepare_tree_inputs)
from h2o3_tpu.ops.binning import CodesView
from h2o3_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS, current_mesh,
                                    n_data_shards, n_model_shards,
                                    spmd_enabled)
from h2o3_tpu.persist import register_model_class
from h2o3_tpu.resilience import resilient_device_put, retry_transient

MAX_DEPTH_CAP = 16

DRF_DEFAULTS: Dict = dict(
    # default depth 10, not the reference's 20: trees are complete binary
    # arrays (static shapes for XLA), so depth-d histograms/compile cost
    # scale with 2^d; the reference's deep default relies on dynamic node
    # allocation (hex/tree/DTree.java) and min_rows pruning
    ntrees=50, max_depth=10, min_rows=1.0, nbins=20, nbins_cats=1024,
    mtries=-1, sample_rate=0.632, sample_rate_per_class=None,
    col_sample_rate_per_tree=1.0, col_sample_rate_change_per_level=1.0,
    min_split_improvement=1e-5, seed=-1, histogram_type="uniform_adaptive",
    score_tree_interval=0, stopping_rounds=0, stopping_metric="auto",
    stopping_tolerance=1e-3, hist_kernel="auto", reg_lambda=0.0,
    # continue-training + in-training checkpoints (formerly a
    # compat_params warn entry): forest trees are independent, so a
    # resumed train with the same seed rebuilds the remaining trees
    # bit-identically; OOB accumulators ride the checkpoint as resume
    # state so training metrics match the uninterrupted run
    checkpoint=None, in_training_checkpoints_dir=None,
    in_training_checkpoints_tree_interval=1,
    # MXU histogram precision + packed binned-code hot path — same
    # semantics as the GBM params (models/gbm.py GBM_DEFAULTS)
    histogram_precision="auto", packed_codes="auto",
)


from h2o3_tpu.models.treeshap import TreeScoringOptionsMixin  # noqa: E402


class DRFModel(TreeScoringOptionsMixin, Model):
    algo = "drf"

    def __init__(self, key, params, spec, trees_host, edges, n_bins,
                 max_depth, ntrees_built, nclasses):
        super().__init__(key, params, spec)
        self.edges = edges
        self.n_bins = n_bins
        self.max_depth = max_depth
        self.ntrees_built = ntrees_built
        self._K = max(nclasses, 1) if nclasses > 2 else 1
        self._feat = jnp.asarray(trees_host["feat"])
        self._thr = jnp.asarray(trees_host["thr"])
        self._na_left = jnp.asarray(trees_host["na_left"])
        self._is_split = jnp.asarray(trees_host["is_split"])
        self._value = jnp.asarray(trees_host["value"])
        nw = trees_host.get("node_w")
        self._node_w = jnp.asarray(nw) if nw is not None else None

    def _contrib_scale(self):
        # forest prediction = MEAN over trees, so each tree's SHAP values
        # scale by 1/T (contributions live in probability/response space)
        return 1.0 / max(self.ntrees_built, 1)

    def staged_predict_proba(self, frame):
        # cumulative margins are a boosting concept; DRF trees are
        # independent probability votes (reference restricts this to GBM)
        raise ValueError("staged_predict_proba is not supported for DRF "
                         "(GBM/XGBoost only, hex/Model.java)")

    def _predict_matrix(self, X, offset=None):
        contribs = predict_raw_stacked(X, self._feat, self._thr, self._na_left,
                                       self._is_split, self._value,
                                       self.max_depth)
        T = self.ntrees_built
        if self.nclasses <= 1:
            return contribs.mean(axis=1)
        if self.nclasses == 2:
            p1 = jnp.clip(contribs.mean(axis=1), 0.0, 1.0)
            return jnp.stack([1.0 - p1, p1], axis=1)
        per_class = jnp.clip(
            contribs.reshape(X.shape[0], T, self._K).mean(axis=1), 0.0, 1.0)
        return per_class / jnp.maximum(per_class.sum(axis=1, keepdims=True),
                                       1e-12)

    def varimp(self, use_pandas=False):
        return self.output.get("variable_importances")

    # -- persistence ----------------------------------------------------

    def _save_arrays(self):
        # ONE counted pytree fetch (the raw per-array device_gets were
        # invisible to d2h budgets — PR-11 transfer-seam burn-down)
        host = telemetry.device_get(
            {"feat": self._feat, "thr": self._thr,
             "na_left": self._na_left, "is_split": self._is_split,
             "value": self._value})
        d = {k: np.asarray(v) for k, v in host.items()}
        if self._node_w is not None:
            d["node_w"] = np.asarray(telemetry.device_get(self._node_w))
        # in-training checkpoint resume state: the OOB accumulators at
        # the committed tree count, so resumed training metrics equal
        # the uninterrupted run's
        for attr, name in (("_resume_oob_num", "resume_oob_num"),
                           ("_resume_oob_cnt", "resume_oob_cnt"),
                           ("_resume_sig", "resume_sig")):
            v = getattr(self, attr, None)
            if v is not None:
                d[name] = np.asarray(v)
        for i, e in enumerate(self.edges):
            d[f"edge_{i}"] = np.asarray(e)
        return d

    def _save_extra_meta(self):
        return {"n_bins": self.n_bins, "max_depth": self.max_depth,
                "ntrees_built": self.ntrees_built,
                "n_edges": len(self.edges)}

    @classmethod
    def _restore(cls, meta, arrays):
        m = cls._restore_base(meta)
        ex = meta["extra"]
        m.n_bins = ex["n_bins"]
        m.max_depth = ex["max_depth"]
        m.ntrees_built = ex["ntrees_built"]
        m.edges = [arrays[f"edge_{i}"] for i in range(ex["n_edges"])]
        m._K = max(m.nclasses, 1) if m.nclasses > 2 else 1
        m._feat = jnp.asarray(arrays["feat"])
        m._thr = jnp.asarray(arrays["thr"])
        m._na_left = jnp.asarray(arrays["na_left"])
        m._is_split = jnp.asarray(arrays["is_split"])
        m._value = jnp.asarray(arrays["value"])
        m._node_w = (jnp.asarray(arrays["node_w"])
                     if "node_w" in arrays else None)
        m._resume_oob_num = (np.asarray(arrays["resume_oob_num"])
                             if "resume_oob_num" in arrays else None)
        m._resume_oob_cnt = (np.asarray(arrays["resume_oob_cnt"])
                             if "resume_oob_cnt" in arrays else None)
        m._resume_sig = (np.asarray(arrays["resume_sig"])
                         if "resume_sig" in arrays else None)
        return m


def _drf_chunk_body(codes_rm, codes_t, y, w, oob_num, oob_cnt, base_key,
                    root_lo, root_hi, nb_f, start_idx, n_active, sample_rate,
                    col_rate, *, cfg, K,
                    sample_rate_per_class, chunk, has_t, adaptive, binned,
                    axis_name, model_axis=None):
    """A chunk of independent forest trees per data shard; OOB sums ride
    the scan carry (reference: DRF's OOB rows are scored by the trees that
    did not sample them — hex/tree/drf/DRF.java OOB machinery).

    ``chunk`` is a padding bucket (see gbm._gbm_chunk_body): the traced
    ``n_active`` masks trailing trees out of the OOB sums and the driver
    drops them at finalize; sample/col rates ride as traced scalars so
    grid variants share one executable."""
    codes = CodesView(rm=codes_rm, t=codes_t if has_t else None)
    F = codes_rm.shape[1]
    shard = jax.lax.axis_index(axis_name) if axis_name else 0

    def build(gv, hv, wt, col_mask, key_m):
        if adaptive:
            return grow_tree_adaptive(codes_rm, gv, hv, wt, cfg, col_mask,
                                      root_lo, root_hi, axis_name=axis_name,
                                      key=key_m, nb_f=nb_f,
                                      model_axis=model_axis)
        if binned:
            return grow_tree_binned(codes_rm, gv, hv, wt, cfg, col_mask,
                                    axis_name=axis_name, key=key_m,
                                    model_axis=model_axis, ct=codes.t)
        return grow_tree(codes, gv, hv, wt, cfg, col_mask,
                         axis_name=axis_name, key=key_m,
                         model_axis=model_axis)

    def one_tree(carry, i):
        oob_num, oob_cnt = carry
        key = jax.random.fold_in(base_key, start_idx + i)
        key_r, key_c, key_m = jax.random.split(key, 3)
        key_r = jax.random.fold_in(key_r, shard)
        if sample_rate_per_class is not None:
            # per-class bootstrap rates (hex/tree/SharedTree.java:210)
            srpc = jnp.asarray(sample_rate_per_class, jnp.float32)
            thr = srpc[jnp.clip(y.astype(jnp.int32), 0,
                                len(sample_rate_per_class) - 1)]
            sampled = jax.random.uniform(key_r, w.shape) < thr
        else:
            sampled = jax.random.uniform(key_r, w.shape) < sample_rate
        wt = w * sampled
        col_mask = jax.random.uniform(key_c, (F,)) < col_rate
        live_oob = (w > 0) & ~sampled & (i < n_active)
        trees = []
        if K == 1:
            yf = y.astype(jnp.float32)
            tree, nid = build(-(yf * wt), wt, wt, col_mask, key_m)
            pred = node_lookup(tree["value"], nid)
            oob_num = oob_num + jnp.where(live_oob, pred, 0.0)
            oob_cnt = oob_cnt + live_oob.astype(jnp.float32)
            trees.append(tree)
        else:
            preds = []
            for k in range(K):
                yk = (y == k).astype(jnp.float32)
                tree, nid = build(-(yk * wt), wt, wt, col_mask,
                                  jax.random.fold_in(key_m, k))
                preds.append(node_lookup(tree["value"], nid))
                trees.append(tree)
            pk = jnp.stack(preds, axis=1)
            oob_num = oob_num + jnp.where(live_oob[:, None], pk, 0.0)
            oob_cnt = oob_cnt + live_oob.astype(jnp.float32)
        stacked = {kk: jnp.stack([t[kk] for t in trees]) for kk in trees[0]}
        return (oob_num, oob_cnt), stacked

    (oob_num, oob_cnt), chunk_trees = jax.lax.scan(
        one_tree, (oob_num, oob_cnt), jnp.arange(chunk))
    return oob_num, oob_cnt, chunk_trees


@lru_cache(maxsize=128)
def _compiled_drf_chunk(mesh, cfg, K, sample_rate_per_class, chunk, has_t,
                        adaptive, binned=False, donate=False):
    model_axis = (MODEL_AXIS
                  if mesh.shape[MODEL_AXIS] > 1 and spmd_enabled()
                  else None)
    body = partial(_drf_chunk_body, cfg=cfg, K=K,
                   sample_rate_per_class=sample_rate_per_class,
                   chunk=chunk, has_t=has_t,
                   adaptive=adaptive, binned=binned, axis_name=DATA_AXIS,
                   model_axis=model_axis)
    in_specs = (P(DATA_AXIS),
                P(None, DATA_AXIS) if has_t else P(DATA_AXIS),
                P(DATA_AXIS), P(DATA_AXIS),
                P(DATA_AXIS), P(DATA_AXIS),
                P(), P(), P(), P(), P(), P(), P(), P())
    out_specs = (P(DATA_AXIS), P(DATA_AXIS), P())
    f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    # the OOB accumulators are write-once-per-chunk carries: donate them
    # so the device updates in place instead of double-buffering
    return jax.jit(f, donate_argnums=(4, 5) if donate else ())


class H2ORandomForestEstimator(ModelBuilder):
    algo = "drf"

    def __init__(self, **params):
        merged = dict(DRF_DEFAULTS)
        merged.update(params)
        super().__init__(**merged)

    def _train_impl(self, spec: TrainingSpec, valid_spec, job: Job) -> DRFModel:
        p = self.params
        if spec.offset is not None:
            raise NotImplementedError("DRF does not support offset_column "
                                      "(matching hex/tree/drf/DRF.java)")
        K = spec.nclasses if spec.nclasses > 2 else 1
        depth = int(p["max_depth"])
        if depth > MAX_DEPTH_CAP:
            raise ValueError(
                f"max_depth {depth} exceeds the static-tree cap "
                f"{MAX_DEPTH_CAP} (complete-binary-array trees; the "
                f"reference's default 20 relies on dynamic node allocation)")
        mtries = int(p.get("mtries", -1) or -1)
        F = spec.n_features
        if mtries <= 0:
            # reference defaults: sqrt(p) classification, p/3 regression
            mtries = (max(1, int(np.sqrt(F))) if spec.nclasses > 1
                      else max(1, F // 3))
        # the bin stage every dense tree trainer shares (models/tree.py).
        # 'random' is binned by the global sketch here: the forest's
        # randomness is its rows and mtries, not the grid's phase
        inputs = prepare_tree_inputs(spec, p, depth, prof=Profile(),
                                     mtries=mtries, random_is_adaptive=False)
        adaptive, packed = inputs.adaptive, inputs.packed
        cfg, bm = inputs.cfg, inputs.bm
        root_lo, root_hi, nb_f = inputs.root_lo, inputs.root_hi, inputs.nb_f
        mesh = current_mesh()
        nd = n_data_shards(mesh)
        padded = spec.X.shape[0]
        if padded % nd != 0:
            raise ValueError(f"padded rows {padded} not divisible by the "
                             f"{nd}-shard data axis")
        seed = int(p.get("seed", -1) or -1)
        key = jax.random.PRNGKey(seed if seed != -1
                                 else int(time.time() * 1e3) % (2 ** 31))
        srpc = self.validate_sample_rate_per_class(spec)
        ntrees = int(p["ntrees"])
        prior = self._resolve_checkpoint(spec)
        start_trees = prior.ntrees_built if prior is not None else 0
        ntrees_new = ntrees - start_trees
        sample_rate = float(p["sample_rate"])
        col_rate = float(p.get("col_sample_rate_per_tree", 1.0))
        Xtr, codes_t_arg, has_t, _ = inputs.operands(spec.X)
        # data-sharded from the start so every chunk (not just the 2nd+)
        # sees identically-sharded carry operands — one executable per
        # bucket (see the margin pinning note in models/gbm.py)
        from jax.sharding import NamedSharding
        rows_sh = NamedSharding(mesh, P(DATA_AXIS))
        # checkpoint continuation resumes the OOB accumulators saved
        # with the prior (else new trees' OOB would be averaged from a
        # zeroed state and training metrics would drift from the
        # uninterrupted run)
        from h2o3_tpu.models.gbm import _spec_signature
        rn = getattr(prior, "_resume_oob_num", None) \
            if prior is not None else None
        rc = getattr(prior, "_resume_oob_cnt", None) \
            if prior is not None else None
        psig = getattr(prior, "_resume_sig", None) \
            if prior is not None else None
        # the saved OOB state belongs to a specific training frame —
        # applying it to different data would silently skew metrics
        sig_ok = psig is None or np.array_equal(np.asarray(psig),
                                                _spec_signature(spec))
        want = (padded,) if K == 1 else (padded, K)
        if rn is not None and rc is not None and sig_ok \
                and np.asarray(rn).shape == tuple(want):
            oob_num = resilient_device_put(jnp.asarray(rn, jnp.float32),
                                           rows_sh, pipeline="train")
            oob_cnt = resilient_device_put(jnp.asarray(rc, jnp.float32),
                                           rows_sh, pipeline="train")
        else:
            if prior is not None:
                from h2o3_tpu.log import warn
                warn("drf checkpoint carries no OOB resume state — "
                     "training metrics will reflect only the new trees")
            oob_num = resilient_device_put(
                jnp.zeros(padded if K == 1 else (padded, K), jnp.float32),
                rows_sh, pipeline="train")
            oob_cnt = resilient_device_put(
                jnp.zeros(padded, jnp.float32), rows_sh,
                pipeline="train")
        y = spec.y
        all_trees = []          # [(device chunk trees, n_active)]
        built = 0
        chunk = min(ntrees_new, 25)
        ckpt_dir = p.get("in_training_checkpoints_dir")
        ckpt_interval = max(int(
            p.get("in_training_checkpoints_tree_interval", 1) or 1), 1)
        ckpt_on = bool(ckpt_dir)
        if ckpt_on:
            chunk = max(min(chunk, ckpt_interval), 1)
        trees_since_ckpt = 0
        # donation is unsafe with checkpoints on: commit_ckpt
        # device_gets the OOB accumulators, which a donated dispatch
        # would already have consumed
        donate = jax.default_backend() == "tpu" and not ckpt_on
        rate_t = jnp.float32(sample_rate)
        col_rate_t = jnp.float32(col_rate)

        def commit_ckpt():
            # advisory end to end: a transient fetch failure in the
            # finalize/OOB device_gets must neither kill a healthy
            # train nor mask the original error on the failure path
            try:
                m = self._finalize(spec, bm, cfg, K, built, all_trees,
                                   prior=prior, tree_offset=start_trees)
                on, oc = telemetry.device_get((oob_num, oob_cnt),
                                              pipeline="train")
                m._resume_oob_num = np.asarray(on, np.float32)
                m._resume_oob_cnt = np.asarray(oc, np.float32)
                m._resume_sig = _spec_signature(spec)
                from h2o3_tpu.models.model_base import \
                    persist_in_training_ckpt
                persist_in_training_ckpt(m, self.algo, ckpt_dir)
            except Exception as e:  # noqa: BLE001 — advisory only
                from h2o3_tpu.log import warn
                warn("drf: in-training checkpoint commit failed: %s", e)

        # per-shard collective/straggler observation (ISSUE 8): chunk
        # k's output shards are watched AFTER chunk k+1 is dispatched,
        # so the host block lands where the device is already busy
        from h2o3_tpu.parallel.mesh import partitioner
        from h2o3_tpu.parallel.shardstats import merge_observations
        partn = partitioner(mesh)
        shard_obs = []
        pending_obs = None            # (prev chunk_trees, t_disp)
        # performance accounting (ISSUE 11): executable cost capture at
        # this jit seam + loop wall -> roofline point (None = no-op)
        perf_acc = telemetry.costmodel.accumulator(
            "train.loop", n_devices=mesh.size)
        t0 = time.monotonic()
        while built < ntrees_new:
            # bucket-rounded chunk lengths (models/gbm.py): ntrees
            # variants landing in one bucket reuse the executable
            c = min(chunk, ntrees_new - built)
            # ONE spelling of the executable cache key, shared by the
            # dispatch and the cost capture below (see models/gbm.py)
            bucket = chunk_bucket(c)
            lru_key = (mesh, cfg, K, srpc, bucket, has_t,
                       adaptive, packed, donate)

            def _dispatch(lru_key=lru_key, c=c):
                from h2o3_tpu import faults
                if faults.ACTIVE:
                    faults.check("compile", pipeline="train")
                step = _compiled_drf_chunk(*lru_key)
                if faults.ACTIVE:
                    faults.check("execute", pipeline="train")
                    if nd > 1:
                        # ICI collective seam (see models/gbm.py)
                        faults.check("collective", pipeline="train")
                return step(
                    Xtr, codes_t_arg, y, spec.w, oob_num, oob_cnt, key,
                    root_lo, root_hi, nb_f,
                    jnp.int32(start_trees + built), jnp.int32(c),
                    rate_t, col_rate_t)
            try:
                # transient failures retry with backoff; donated OOB
                # accumulators cannot be replayed (TPU path), so
                # donation disables retry
                oob_num, oob_cnt, chunk_trees = retry_transient(
                    _dispatch, site="train.execute",
                    attempts=1 if donate else 3)
                t_disp = time.perf_counter()
            except BaseException:
                if ckpt_on and built > 0:
                    # leave a resumable checkpoint at the committed
                    # prefix before the failure propagates
                    commit_ckpt()
                raise
            if perf_acc is not None:
                # one trace+lower per (config, bucket); scale=bucket —
                # the HLO analysis counts the tree-scan body once and
                # the executable runs it `bucket` times (see gbm.py)
                t_cap0 = time.perf_counter()
                step = _compiled_drf_chunk(*lru_key)   # lru cache hit
                perf_acc.add(telemetry.costmodel.executable_cost(
                    ("drf.chunk",) + lru_key,
                    lambda s=step, b=built, cc=c: s.lower(
                        Xtr, codes_t_arg, y, spec.w, oob_num, oob_cnt,
                        key, root_lo, root_hi, nb_f,
                        jnp.int32(start_trees + b), jnp.int32(cc),
                        rate_t, col_rate_t),
                    scale=bucket))
                perf_acc.note_capture_seconds(
                    time.perf_counter() - t_cap0)
            if pending_obs is not None:
                shard_obs.append(partn.observe_step(
                    pending_obs[0], pending_obs[1], algo=self.algo))
                pending_obs = None
            if nd > 1 and telemetry.enabled():
                pending_obs = (chunk_trees, t_disp)
            all_trees.append((chunk_trees, c))
            built += c
            trees_since_ckpt += c
            if ckpt_on and trees_since_ckpt >= ckpt_interval \
                    and built < ntrees_new:
                commit_ckpt()
                trees_since_ckpt = 0
            job.set_progress(built / ntrees_new)
            if job.cancel_requested or job.preempt_requested:
                break
        # checkpoint-based preemption (ISSUE 15): commit the built
        # prefix (DKV-only when no checkpoint dir is set — commit_ckpt
        # handles ckpt_dir=None) and unwind so the scheduler requeues
        # and resumes bit-identically from the saved OOB accumulators.
        # User cancel wins; a preempt racing the final chunk is moot.
        if (job.preempt_requested and not job.cancel_requested
                and built < ntrees_new):
            if built > 0:
                commit_ckpt()
            from h2o3_tpu.jobs import JobPreempted
            raise JobPreempted(
                f"drf train preempted at {built} committed trees"
                + (f": {job.preempt_reason}" if job.preempt_reason
                   else ""))
        if pending_obs is not None:
            # the final chunk: the loop has nothing left to overlap, so
            # this is the block_until_ready below, observed per shard
            shard_obs.append(partn.observe_step(
                pending_obs[0], pending_obs[1], algo=self.algo))
        jax.block_until_ready(oob_cnt)  # h2o3-lint: allow[transfer-seam] tree-loop timing fence + final-chunk shard observation point
        t_loop = time.monotonic() - t0

        model = self._finalize(spec, bm, cfg, K, built, all_trees,
                               prior=prior, tree_offset=start_trees)
        if ckpt_on:
            try:
                on, oc = telemetry.device_get((oob_num, oob_cnt),
                                              pipeline="train")
                model._resume_oob_num = np.asarray(on, np.float32)
                model._resume_oob_cnt = np.asarray(oc, np.float32)
                model._resume_sig = _spec_signature(spec)
                from h2o3_tpu.models.model_base import \
                    persist_in_training_ckpt
                # final=True: the durable artifact is written but the
                # DKV '<key>_ckpt' entry is dropped — the finished
                # model supersedes it
                persist_in_training_ckpt(model, self.algo, ckpt_dir,
                                         final=True)
            except Exception as e:  # noqa: BLE001 — advisory only
                from h2o3_tpu.log import warn
                warn("drf: final in-training checkpoint failed: %s", e)
        model.output["training_loop_seconds"] = t_loop
        model.output["packed_codes"] = inputs.record(
            inputs.mesh_attrs(mesh, built * K))
        # the DRF chunk body (like GBM dense) traces its whole level
        # loop into one executable — all levels per dispatch
        model.output["levels_per_dispatch"] = int(cfg.max_depth)
        if perf_acc is not None:
            perf_acc.add_device_seconds(t_loop)
            rp = perf_acc.finish()
            if rp is not None:
                model.output["perf"] = {"train": rp,
                                        "phases": {"loop": rp}}
        model.output["spmd"] = {
            "n_data": nd, "n_model": n_model_shards(mesh),
            "model_axis_split_search": bool(
                n_model_shards(mesh) > 1 and spmd_enabled())}
        collective = merge_observations(shard_obs)
        if collective is not None:
            model.output["spmd"]["collective"] = collective
        # OOB metrics as training metrics (reference DRF semantics:
        # "training" numbers are out-of-bag when sample_rate < 1)
        self._oob_metrics(model, spec, K, oob_num, oob_cnt)
        if valid_spec is not None:
            # valid_spec is already adapted to the training domains
            # (build_validation_spec in ModelBuilder.train)
            out = model._predict_matrix(valid_spec.X)
            model.validation_metrics = compute_metrics(
                out, valid_spec.y, valid_spec.w, spec.nclasses,
                spec.response_domain)
        return model

    def _oob_metrics(self, model, spec, K, oob_num, oob_cnt):
        # ONE counted fetch for the OOB finalize (transfer-seam
        # burn-down: these were four raw uncounted device_gets)
        host = telemetry.device_get((oob_cnt, oob_num, spec.w, spec.y),
                                    pipeline="train")
        cnt, num, w, y = (np.asarray(v) for v in host)
        live = (cnt > 0) & (w > 0)
        if not live.any():
            # no OOB rows (sample_rate == 1.0): fall back to in-bag scoring
            # so training_metrics is never silently None (the reference
            # still reports training metrics when OOB is unavailable)
            out = model._predict_matrix(spec.X)
            model.training_metrics = compute_metrics(
                out, spec.y, spec.w, spec.nclasses, spec.response_domain)
            model.output["oob_metrics"] = False
            return
        if K == 1:
            pred = num[live] / cnt[live]
            if spec.nclasses == 2:
                p1 = np.clip(pred, 0.0, 1.0)
                probs = np.stack([1 - p1, p1], axis=1)
                model.training_metrics = compute_metrics(
                    probs, y[live], w[live], 2, spec.response_domain)
            else:
                model.training_metrics = compute_metrics(
                    pred, y[live], w[live], 1)
        else:
            pk = np.clip(num[live] / cnt[live][:, None], 0.0, 1.0)
            pk = pk / np.maximum(pk.sum(axis=1, keepdims=True), 1e-12)
            model.training_metrics = compute_metrics(
                pk, y[live], w[live], K, spec.response_domain)
        model.output["oob_metrics"] = True

    def _resolve_checkpoint(self, spec):
        """Continue-training support (hex/Model.java:487 _checkpoint):
        same compatibility contract as GBM's — the prior trees' feature
        indices and enum-code thresholds must address the same columns
        and domains."""
        ckpt = self.params.get("checkpoint")
        if not ckpt:
            return None
        from h2o3_tpu.models.gbm import _resolve_checkpoint_source
        prior = _resolve_checkpoint_source(ckpt, DRFModel, "DRF")
        if prior.max_depth != int(self.params["max_depth"]):
            raise ValueError("checkpoint max_depth differs")
        if int(self.params["ntrees"]) <= prior.ntrees_built:
            raise ValueError(
                f"ntrees ({self.params['ntrees']}) must exceed the "
                f"checkpoint's ntrees_built ({prior.ntrees_built})")
        if list(prior.feature_names) != list(spec.names):
            raise ValueError(
                f"checkpoint feature set {prior.feature_names} differs "
                f"from the training spec's {spec.names}")
        if prior.nclasses != spec.nclasses:
            raise ValueError(
                f"checkpoint has {prior.nclasses} response classes but "
                f"the training frame has {spec.nclasses}")
        prd = tuple(prior.response_domain) if prior.response_domain else None
        srd = tuple(spec.response_domain) if spec.response_domain else None
        if prd != srd:
            raise ValueError(
                f"checkpoint response domain {prior.response_domain} "
                f"differs from the training frame's "
                f"{spec.response_domain}")
        pcd = {k: tuple(v) for k, v in prior.cat_domains.items()}
        scd = {k: tuple(v) for k, v in spec.cat_domains.items()}
        if pcd != scd:
            raise ValueError(
                "checkpoint categorical domains differ from the "
                "training frame's")
        return prior

    def _finalize(self, spec, bm, cfg, K, built, all_trees, prior=None,
                  tree_offset=0) -> DRFModel:
        M = cfg.n_nodes
        # one pytree device_get; padding-bucket tails sliced off in the
        # shared helper (models/tree.py collect_chunk_trees)
        th = collect_chunk_trees(all_trees, M,
                                 bm.edges if bm is not None else [])
        feat = th["feat"]
        gains = th["gain"]
        trees_host = {"feat": feat, "thr": th["thr"],
                      "na_left": th["na_left"], "is_split": th["is_split"],
                      "value": th["value"], "node_w": th["node_w"]}
        if prior is not None:
            # checkpoint continuation: prepend the prior model's trees
            trees_host = {
                "feat": np.concatenate([np.asarray(prior._feat), feat]),
                "thr": np.concatenate([np.asarray(prior._thr),
                                       th["thr"]]),
                "na_left": np.concatenate([np.asarray(prior._na_left),
                                           th["na_left"]]),
                "is_split": np.concatenate([np.asarray(prior._is_split),
                                            th["is_split"]]),
                "value": np.concatenate([np.asarray(prior._value),
                                         th["value"]]),
                "node_w": (np.concatenate([np.asarray(prior._node_w),
                                           th["node_w"]])
                           if getattr(prior, "_node_w", None) is not None
                           else None),
            }
        model = DRFModel(self._model_key(), self.params,
                         spec, trees_host,
                         bm.edges if bm is not None else [],
                         bm.n_bins if bm is not None else cfg.n_bins,
                         cfg.max_depth, tree_offset + built, spec.nclasses)
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
            "percentage": (vi[order] / vi.sum() if vi.sum() > 0
                           else vi[order]).tolist(),
        }
        return model


register_model_class("drf", DRFModel)
