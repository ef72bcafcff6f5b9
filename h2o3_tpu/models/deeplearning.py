"""DeepLearning — feed-forward MLP (the reference's deepest NN).

Reference: hex/deeplearning/DeepLearning.java:35, Neurons.java (Rectifier/
Tanh/Maxout layers + dropout variants), DeepLearningModelInfo (flat weight
arrays), DeepLearningTask.java:17 — per-row fprop/bprop on thread-shared
weights (Hogwild!) with per-iteration model averaging across nodes
(:101,:180) and optional elastic averaging.

TPU re-design (SURVEY §2.5): Hogwild + averaging is an artifact of JVM
threads — synchronous data-parallel minibatch SGD is strictly better on
TPU: one jitted train step computes batched fwd/bwd on the MXU; under a
mesh the batch shards over 'data' and gradients psum over ICI. A whole
epoch runs as one lax.scan over contiguous batches of a device-resident,
per-epoch-permuted design matrix — zero host round-trips inside an epoch.

Optimizers match the reference's: ADADELTA (adaptive_rate=true default,
rho/epsilon) or momentum SGD with rate annealing + ramp-up
(rate/momentum_start/ramp/stable). Dropout (input + per-layer hidden),
L1/L2, UniformAdaptive init.
"""
from __future__ import annotations

import time
from functools import lru_cache
from typing import Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu import telemetry
from h2o3_tpu.jobs import Job
from h2o3_tpu.models.glm import expand_design
from h2o3_tpu.models.model_base import (Model, ModelBuilder, ScoreKeeper,
                                        TrainingSpec, compute_metrics,
                                        pack_impute_means,
                                        unpack_impute_means)
from h2o3_tpu.persist import register_model_class

DL_DEFAULTS: Dict = dict(
    hidden=(200, 200), epochs=10.0, activation="rectifier",
    checkpoint=None, initial_weights=None, initial_biases=None,
    adaptive_rate=True, rho=0.99, epsilon=1e-8,
    rate=0.005, rate_annealing=1e-6, rate_decay=1.0,
    momentum_start=0.0, momentum_ramp=1e6, momentum_stable=0.0,
    input_dropout_ratio=0.0, hidden_dropout_ratios=None,
    l1=0.0, l2=0.0, max_w2=1e30,
    loss="auto", distribution="auto", standardize=True,
    # per-epoch reshuffling costs a full gather of the design matrix each
    # epoch; the reference's Hogwild pass doesn't shuffle at all
    # (DeepLearningTask streams rows in storage order), so default to one
    # up-front permutation
    shuffle_training_data=False,
    # TPU batch size: the reference's mini_batch_size default 1 feeds the
    # per-row Hogwild loop; a batched MXU step wants hundreds of rows
    mini_batch_size=256,
    autoencoder=False,
    seed=-1, stopping_rounds=0, stopping_metric="auto",
    stopping_tolerance=1e-3, score_interval=1,
)

_ACTS = {
    "rectifier": jax.nn.relu,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "rectifier_with_dropout": jax.nn.relu,
    "tanh_with_dropout": jnp.tanh,
}


def _init_params(key, sizes):
    """UniformAdaptive init (hex/deeplearning Neurons: ±√(6/(fan_in+out)))."""
    params = []
    for i in range(len(sizes) - 1):
        key, k = jax.random.split(key)
        lim = float(np.sqrt(6.0 / (sizes[i] + sizes[i + 1])))
        Wm = jax.random.uniform(k, (sizes[i], sizes[i + 1]), jnp.float32,
                                -lim, lim)
        params.append({"W": Wm, "b": jnp.zeros(sizes[i + 1], jnp.float32)})
    return params


def _forward(params, x, act, drop_key=None, in_drop=0.0, hid_drops=None):
    """Batched fprop; dropout only when drop_key is given (training)."""
    h = x
    if drop_key is not None and in_drop > 0:
        drop_key, k = jax.random.split(drop_key)
        h = h * (jax.random.uniform(k, h.shape) >= in_drop) / (1 - in_drop)
    n = len(params)
    for i, layer in enumerate(params):
        h = h @ layer["W"] + layer["b"]
        if i < n - 1:
            h = act(h)
            if drop_key is not None and hid_drops and hid_drops[i] > 0:
                drop_key, k = jax.random.split(drop_key)
                keep = 1.0 - hid_drops[i]
                h = h * (jax.random.uniform(k, h.shape) < keep) / keep
    return h


# rows a forward pass over a frame takes at a time: one hidden layer's
# activations over 10M rows are 8 GB ([rows, 200] float32), a block's 0.84
_FORWARD_BLOCK = 1 << 20


def _forward_rows(params, X, act):
    """``_forward`` over every row of a frame, ``_FORWARD_BLOCK`` rows at a
    time: the same products row by row, without any layer's activations
    for all the rows at once."""
    if X.shape[0] <= _FORWARD_BLOCK:
        return _forward(params, X, act)
    return jnp.concatenate([_forward(params, X[lo:lo + _FORWARD_BLOCK], act)
                            for lo in range(0, X.shape[0], _FORWARD_BLOCK)])


def _loss_fn(out, y, w, task, dist_name):
    if task == "autoencoder":
        # reconstruction MSE over the standardized inputs (y = Xs batch)
        per = 0.5 * ((out - y) ** 2).sum(axis=1)
        return (w * per).sum() / jnp.maximum(w.sum(), 1e-12)
    if task == "classification":
        logp = jax.nn.log_softmax(out, axis=1)
        ll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
        return (w * ll).sum() / jnp.maximum(w.sum(), 1e-12)
    mu = out[:, 0]
    if dist_name == "laplace":
        per = jnp.abs(mu - y)
    elif dist_name == "poisson":
        per = jnp.exp(mu) - y * mu
    else:  # gaussian
        per = 0.5 * (mu - y) ** 2
    return (w * per).sum() / jnp.maximum(w.sum(), 1e-12)


def _init_opt(net, adaptive: bool):
    def zeros_like_params(params):
        return [{k: jnp.zeros_like(v) for k, v in layer.items()}
                for layer in params]
    return ((zeros_like_params(net), zeros_like_params(net)) if adaptive
            else (zeros_like_params(net),))


class _StepConfig(NamedTuple):
    """What one optimizer step computes, from the parameters alone: the
    network's sizes, activation, loss, penalties, dropout and optimizer.
    Hashable: it keys the compiled step and epoch programs."""
    sizes: tuple
    act_name: str
    task: str
    dist_name: str
    l1: float
    l2: float
    in_drop: float
    hid_drops: tuple
    use_dropout: bool
    adaptive: bool
    rho: float
    eps: float
    rate0: float
    annealing: float
    mom_start: float
    mom_ramp: float
    mom_stable: float


def _step_config(p: Dict, sizes, act_name: str, task: str,
                 dist_name: str) -> _StepConfig:
    hidden = list(sizes[1:-1])
    in_drop = float(p.get("input_dropout_ratio", 0.0))
    hid_drops = p.get("hidden_dropout_ratios")
    if hid_drops is None:
        hid_drops = ([0.5] * len(hidden) if act_name.endswith("_dropout")
                     else [0.0] * len(hidden))
    hid_drops = tuple(float(d) for d in hid_drops)
    return _StepConfig(
        tuple(int(s) for s in sizes), act_name, task, dist_name,
        float(p.get("l1", 0.0)), float(p.get("l2", 0.0)), in_drop,
        hid_drops, in_drop > 0 or any(d > 0 for d in hid_drops),
        bool(p.get("adaptive_rate", True)), float(p.get("rho", 0.99)),
        float(p.get("epsilon", 1e-8)), float(p.get("rate", 0.005)),
        float(p.get("rate_annealing", 1e-6)),
        float(p.get("momentum_start", 0.0)),
        max(float(p.get("momentum_ramp", 1e6)), 1.0),
        float(p.get("momentum_stable", 0.0)))


@lru_cache(maxsize=64)
def _step_body(cfg: _StepConfig):
    """One optimizer step on one batch: ``(params, opt, samples, xb, yb,
    wb, key) -> (params, opt, samples + rows, loss)``. The epoch's scan
    runs it as its body; ``compiled_step`` hands it out alone."""
    act = _ACTS[cfg.act_name]

    def loss(params, xb, yb, wb, dkey):
        out = _forward(params, xb, act,
                       drop_key=dkey if cfg.use_dropout else None,
                       in_drop=cfg.in_drop, hid_drops=list(cfg.hid_drops))
        l = _loss_fn(out, yb, wb, cfg.task, cfg.dist_name)
        if cfg.l2 > 0:
            l = l + cfg.l2 * sum((layer["W"] ** 2).sum() for layer in params)
        if cfg.l1 > 0:
            l = l + cfg.l1 * sum(jnp.abs(layer["W"]).sum()
                                 for layer in params)
        return l

    grad_fn = jax.value_and_grad(loss)
    rho, eps = cfg.rho, cfg.eps

    def sgd_update(params, opt, grads, samples):
        if cfg.adaptive:
            # ADADELTA (hex/deeplearning adaptive_rate default)
            Eg, Ed = opt
            new_p, nEg, nEd = [], [], []
            for layer, g, eg, ed in zip(params, grads, Eg, Ed):
                upd, neg, ned = {}, {}, {}
                for k in ("W", "b"):
                    eg2 = rho * eg[k] + (1 - rho) * g[k] ** 2
                    delta = (-jnp.sqrt(ed[k] + eps)
                             / jnp.sqrt(eg2 + eps) * g[k])
                    ned[k] = rho * ed[k] + (1 - rho) * delta ** 2
                    neg[k] = eg2
                    upd[k] = layer[k] + delta
                new_p.append(upd)
                nEg.append(neg)
                nEd.append(ned)
            return new_p, (nEg, nEd)
        # momentum SGD with annealing + ramp
        vel, = opt
        lr = cfg.rate0 / (1.0 + cfg.annealing * samples)
        mom = jnp.where(samples < cfg.mom_ramp,
                        cfg.mom_start + (cfg.mom_stable - cfg.mom_start)
                        * samples / cfg.mom_ramp, cfg.mom_stable)
        new_p, nv = [], []
        for layer, g, v in zip(params, grads, vel):
            upd, uv = {}, {}
            for k in ("W", "b"):
                uv[k] = mom * v[k] - lr * g[k]
                upd[k] = layer[k] + uv[k]
            new_p.append(upd)
            nv.append(uv)
        return new_p, (nv,)

    def step(params, opt, samples, xb, yb, wb, bkey):
        l, grads = grad_fn(params, xb, yb, wb, bkey)
        params, opt = sgd_update(params, opt, grads, samples)
        return params, opt, samples + xb.shape[0], l

    return step


@lru_cache(maxsize=64)
def _jitted_step(cfg: _StepConfig):
    return jax.jit(_step_body(cfg))


def compiled_step(model: "DeepLearningModel"):
    """The optimizer step a train of ``model``'s parameters runs, compiled
    alone: the body of the epoch's scan, for a caller that follows a few
    steps from a given state (the benchmark's check). Arguments and
    result as ``_step_body`` says; ``opt`` is ``model.optimizer_state``'s
    layout."""
    sizes = ([int(model.net[0]["W"].shape[0])]
             + [int(ly["W"].shape[1]) for ly in model.net])
    return _jitted_step(_step_config(model.params, sizes, model.activation,
                                     model.task, model.dist_name))


@lru_cache(maxsize=64)
def _compiled_epoch(cfg: _StepConfig, batch, n_batches, use_rows, padded,
                    shuffle):
    """Build + cache the jitted epoch for a static config. Data rides as
    ARGUMENTS: a closure over the design matrix bakes it into the program
    as a constant (~90s XLA compile at MNIST shape), and a fresh closure
    per estimator re-pays the compile every train."""
    step = _step_body(cfg)

    @jax.jit
    def run_epoch(params, opt, samples, ekey, Xs, y, w, shift):
        pkey, dkey = jax.random.split(ekey)
        if shuffle:
            perm = jax.random.permutation(pkey, padded)
            Xp = Xs[perm][:use_rows]
            yp = y[perm][:use_rows]
            wp = w[perm][:use_rows]
        else:
            # rotate the start offset per epoch so the dropped tail
            # (padded - use_rows rows) cycles instead of permanently
            # excluding the same rows
            Xp = jnp.roll(Xs, shift, axis=0)[:use_rows]
            yp = jnp.roll(y, shift, axis=0)[:use_rows]
            wp = jnp.roll(w, shift)[:use_rows]

        def one_batch(carry, i):
            params, opt, samples = carry
            xb = jax.lax.dynamic_slice_in_dim(Xp, i * batch, batch)
            yb = jax.lax.dynamic_slice_in_dim(yp, i * batch, batch)
            wb = jax.lax.dynamic_slice_in_dim(wp, i * batch, batch)
            bkey = jax.random.fold_in(dkey, i)
            params, opt, samples, l = step(params, opt, samples, xb, yb, wb,
                                           bkey)
            return (params, opt, samples), l

        (params, opt, samples), losses = jax.lax.scan(
            one_batch, (params, opt, samples), jnp.arange(n_batches))
        return params, opt, samples, losses.mean()

    return run_epoch


class DeepLearningModel(Model):
    algo = "deeplearning"

    def __init__(self, key, params, spec, net_params, exp_names, impute_means,
                 xm, xs, task, dist_name, hidden, activation):
        super().__init__(key, params, spec)
        self.net = net_params
        self.exp_names = exp_names
        self.impute_means = {k: float(v) for k, v in impute_means.items()}
        self.xm = np.asarray(xm)
        self.xs = np.asarray(xs)
        self.task = task
        self.dist_name = dist_name
        self.hidden = list(hidden)
        self.activation = activation
        # ADADELTA's accumulators (or the momentum) as the last step left
        # them, on the device; a restored model has none
        self.optimizer_state = None

    def _predict_matrix(self, X, offset=None):
        from h2o3_tpu.models.glm import expand_scoring_matrix
        Xe = expand_scoring_matrix(self, X)
        Xs = (Xe - jnp.asarray(self.xm)[None, :]) / jnp.asarray(self.xs)[None, :]
        act = _ACTS[self.activation]
        out = _forward_rows(self.net, Xs, act)
        if self.task == "autoencoder":
            return out                    # standardized reconstruction
        if self.task == "classification":
            probs = jax.nn.softmax(out, axis=1)
            return probs
        mu = out[:, 0]
        if self.dist_name == "poisson":
            mu = jnp.exp(mu)
        if offset is not None:
            mu = mu + offset
        return mu

    def predict(self, frame):
        if self.task != "autoencoder":
            return super().predict(frame)
        # autoencoder: reconstruction in ORIGINAL units, one column per
        # expanded feature (h2o predict on an autoencoder model)
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.models.model_base import adapt_test_matrix
        X = adapt_test_matrix(self, frame)
        out = self._predict_matrix(X)
        recon = out * jnp.asarray(self.xs)[None, :] + \
            jnp.asarray(self.xm)[None, :]
        R = np.asarray(telemetry.device_get(
            recon, pipeline="score"))[: frame.nrow]
        names = [f"reconstr_{n}" for n in self.exp_names]
        return Frame(names, [Vec.from_numpy(R[:, i].astype(np.float32))
                             for i in range(R.shape[1])])

    def anomaly(self, frame, per_feature: bool = False):
        """Per-row reconstruction MSE in standardized space
        (h2o.anomaly / ModelMetricsAutoEncoder scoring)."""
        from h2o3_tpu.frame.frame import Frame
        from h2o3_tpu.frame.vec import Vec
        from h2o3_tpu.models.glm import expand_scoring_matrix
        from h2o3_tpu.models.model_base import adapt_test_matrix
        X = adapt_test_matrix(self, frame)
        Xe = expand_scoring_matrix(self, X)
        Xs = (Xe - jnp.asarray(self.xm)[None, :]) / \
            jnp.asarray(self.xs)[None, :]
        out = _forward_rows(self.net, Xs, _ACTS[self.activation])
        err = (out - Xs) ** 2
        if per_feature:
            E = np.asarray(telemetry.device_get(
                err, pipeline="score"))[: frame.nrow]
            names = [f"reconstr_{n}.SE" for n in self.exp_names]
            return Frame(names,
                         [Vec.from_numpy(E[:, i].astype(np.float32))
                          for i in range(E.shape[1])])
        mse = np.asarray(telemetry.device_get(
            err.mean(axis=1), pipeline="score"))[: frame.nrow]
        return Frame(["Reconstruction.MSE"],
                     [Vec.from_numpy(mse.astype(np.float32))])

    # -- persistence ----------------------------------------------------

    def _save_arrays(self):
        d = {"xm": self.xm, "xs": self.xs,
             **pack_impute_means(self.impute_means)}
        for i, layer in enumerate(self.net):
            d[f"W{i}"] = np.asarray(
                telemetry.device_get(layer["W"], pipeline="score"))
            d[f"b{i}"] = np.asarray(
                telemetry.device_get(layer["b"], pipeline="score"))
        return d

    def _save_extra_meta(self):
        return {"exp_names": self.exp_names, "task": self.task,
                "dist_name": self.dist_name, "hidden": self.hidden,
                "activation": self.activation, "n_layers": len(self.net)}

    @classmethod
    def _restore(cls, meta, arrays):
        m = cls._restore_base(meta)
        ex = meta["extra"]
        m.exp_names = list(ex["exp_names"])
        m.task = ex["task"]
        m.dist_name = ex["dist_name"]
        m.hidden = list(ex["hidden"])
        m.activation = ex["activation"]
        m.optimizer_state = None
        m.xm = arrays["xm"]
        m.xs = arrays["xs"]
        m.impute_means = unpack_impute_means(arrays)
        m.net = [{"W": jnp.asarray(arrays[f"W{i}"]),
                  "b": jnp.asarray(arrays[f"b{i}"])}
                 for i in range(ex["n_layers"])]
        return m


class H2ODeepLearningEstimator(ModelBuilder):
    algo = "deeplearning"

    def __init__(self, **params):
        merged = dict(DL_DEFAULTS)
        merged.update(params)
        super().__init__(**merged)
        # autoencoder mode is unsupervised: train() must not demand y
        self.supervised = not bool(merged.get("autoencoder"))

    def _resolve_checkpoint(self, spec: TrainingSpec, task: str,
                            act_name: str):
        """checkpoint continue-training (hex/Model.java:487 _checkpoint,
        DeepLearning restart semantics): the prior model's weights seed
        the network and epochs continue from its state. Accepts a model
        object, a DKV model key, or an artifact path."""
        ckpt = self.params.get("checkpoint")
        if not ckpt:
            return None
        if isinstance(ckpt, DeepLearningModel):
            prior = ckpt
        else:
            from h2o3_tpu import dkv
            got = dkv.get_opt(str(ckpt))
            if got is not None and got[0] == "model":
                prior = got[1]
            else:
                from h2o3_tpu.persist import load_model
                prior = load_model(str(ckpt))
        if not isinstance(prior, DeepLearningModel):
            raise ValueError(
                f"checkpoint '{ckpt}' is not a DeepLearning model")
        if prior.task != task:
            raise ValueError(f"checkpoint task '{prior.task}' != '{task}'")
        if prior.activation != act_name:
            raise ValueError(
                f"checkpoint activation '{prior.activation}' != "
                f"'{act_name}' (checkpoint topology must match)")
        hidden = [int(h) for h in (self.params.get("hidden") or (200, 200))]
        if list(prior.hidden) != hidden:
            raise ValueError(
                f"checkpoint hidden layers {prior.hidden} != {hidden}")
        if prior.nclasses != spec.nclasses:
            raise ValueError(
                f"checkpoint has {prior.nclasses} response classes but "
                f"the training frame has {spec.nclasses}")
        prd = (tuple(prior.response_domain) if prior.response_domain
               else None)
        srd = tuple(spec.response_domain) if spec.response_domain else None
        if prd != srd:
            raise ValueError(
                f"checkpoint response domain {prd} differs from the "
                f"training frame's {srd} — the prior output layer's "
                f"class columns would address swapped labels")
        return prior

    def _apply_initial_weights(self, net, sizes):
        """initial_weights / initial_biases (hex/deeplearning
        DeepLearningParameters): user-specified per-layer [in, out]
        weight matrices / [out] bias vectors; None entries keep the
        random init. Accepts numpy arrays or Frames."""
        p = self.params

        def _mat(v):
            if hasattr(v, "as_matrix"):     # Frame
                return np.asarray(telemetry.device_get(
                    v.as_matrix(v.names), pipeline="train"))[:v.nrow]
            return np.asarray(v, np.float32)

        for kind, idx in (("initial_weights", "W"),
                          ("initial_biases", "b")):
            vals = p.get(kind)
            if not vals:
                continue
            if len(vals) != len(net):
                raise ValueError(
                    f"{kind} needs one entry per layer "
                    f"({len(net)}), got {len(vals)}")
            for li, v in enumerate(vals):
                if v is None:
                    continue
                a = _mat(v).astype(np.float32)
                want = ((sizes[li], sizes[li + 1]) if idx == "W"
                        else (sizes[li + 1],))
                if idx == "b" and a.ndim == 2 and 1 in a.shape:
                    a = a.reshape(-1)    # single-column bias frame
                if (idx == "W" and a.ndim == 2 and a.shape != want
                        and a.shape == (sizes[li + 1], sizes[li])):
                    # the reference supplies weight matrices in [out, in]
                    # orientation (hex/deeplearning Neurons rows=units of
                    # THIS layer, cols=previous layer); the native layout
                    # here is [in, out] — accept the reference
                    # orientation by transposing. Square layers are
                    # shape-ambiguous and taken as [in, out] as-is.
                    a = a.T
                if a.shape != want:
                    # exact match required beyond the two orientations: a
                    # reshaped matrix would scramble the connections
                    hint = ((f" ([in, out] native orientation; the "
                             f"reference's [out, in] = "
                             f"{(sizes[li + 1], sizes[li])} is accepted "
                             f"and transposed)") if idx == "W" else "")
                    raise ValueError(
                        f"{kind}[{li}] has shape {a.shape}, layer "
                        f"expects {want}{hint}")
                net[li] = dict(net[li])
                net[li][idx] = jnp.asarray(a)
        return net

    def _train_impl(self, spec: TrainingSpec, valid_spec, job: Job):
        """The trainer's stages, each a span under ``train.train`` and a
        key of ``model.output["train_profile"]`` (model_base adds queue,
        spec, total and other): ``train.init`` (design matrix, moments,
        standardisation, the up-front permutation, ended by a fence),
        ``train.loop`` (the epochs up to the loop fence, then
        ``train.score``: the last epoch's training loss, or, where early
        stopping asks, every epoch's) and ``train.finalize`` (the model
        and its metrics). Scores nest in the loop as the tree trainers'
        do, so ``train_profile``'s ``score_s`` is inside ``loop_s``."""
        from h2o3_tpu.log import Profile
        p = self.params
        prof = Profile()
        autoenc = bool(p.get("autoencoder"))
        task = ("autoencoder" if autoenc else
                "classification" if spec.nclasses > 1 else "regression")
        dist_name = (p.get("distribution") or "auto").lower()
        if dist_name in ("auto", ""):
            dist_name = ("multinomial" if spec.nclasses > 2 else
                         "bernoulli" if spec.nclasses == 2 else "gaussian")
        act_name = (p.get("activation") or "rectifier").lower()
        if act_name not in _ACTS:
            raise ValueError(f"unsupported activation '{act_name}'; have "
                             f"{sorted(_ACTS)} (maxout not implemented)")
        act = _ACTS[act_name]
        prior = self._resolve_checkpoint(spec, task, act_name)
        with prof.phase("init"):
            Xe, exp_names, means = expand_design(
                spec, impute_means=(dict(prior.impute_means)
                                    if prior is not None else None))
            if prior is not None and list(prior.exp_names) != list(exp_names):
                raise ValueError(
                    f"checkpoint expanded design {prior.exp_names} differs "
                    f"from the training frame's {exp_names} — the prior "
                    f"weights would address the wrong inputs")
            Fe = Xe.shape[1]
            w = spec.w
            # weighted standardization
            if prior is not None:
                # continue in the PRIOR model's input space — its weights
                # are only valid under its own standardization (and the
                # fresh reduction would be discarded anyway)
                xm = jnp.asarray(prior.xm, jnp.float32)
                xs = jnp.asarray(prior.xs, jnp.float32)
            else:
                wsum = w.sum()
                xm = (Xe * w[:, None]).sum(0) / wsum
                xv = (w[:, None] * (Xe - xm[None, :]) ** 2).sum(0) / wsum
                xs = jnp.sqrt(jnp.maximum(xv, 1e-12))
                if not bool(p.get("standardize", True)):
                    xm = jnp.zeros_like(xm)
                    xs = jnp.ones_like(xs)
            Xs = (Xe - xm[None, :]) / xs[None, :]
            del Xe
            if task == "autoencoder":
                # the network reconstructs its own standardized inputs
                # (hex/deeplearning autoencoder mode)
                y = Xs
                n_out = Fe
            else:
                y = (spec.y.astype(jnp.int32) if task == "classification"
                     else spec.y.astype(jnp.float32))
                n_out = spec.nclasses if task == "classification" else 1
            hidden = [int(h) for h in (p.get("hidden") or (200, 200))]
            sizes = [Fe] + hidden + [n_out]
            seed = int(p.get("seed", -1) or -1)
            key = jax.random.PRNGKey(seed if seed != -1
                                     else int(time.time() * 1e3) % (2 ** 31))
            key, ik = jax.random.split(key)
            if prior is not None:
                net = [{"W": jnp.asarray(ly["W"], jnp.float32),
                        "b": jnp.asarray(ly["b"], jnp.float32)}
                       for ly in prior.net]
            else:
                net = _init_params(ik, sizes)
            net = self._apply_initial_weights(net, sizes)

            padded = Xs.shape[0]
            # cap the batch so an epoch always makes >=8 optimizer updates
            # (and never exceeds the frame): the reference's per-row
            # Hogwild loop gets nrow updates per epoch; one giant batch
            # would starve small frames of updates entirely
            batch = max(min(int(p.get("mini_batch_size", 256)),
                            max(padded // 8, 1)), 1)
            n_batches = padded // batch
            use_rows = n_batches * batch
            epochs = float(p.get("epochs", 10.0))
            prior_epochs = 0.0
            if prior is not None:
                # epochs is the TOTAL (hex/Model checkpoint semantics, same
                # contract as the GBM resolver's ntrees): continue for the
                # remainder, and reject a target the prior already met
                prior_epochs = float(prior.output.get("epochs_trained", 0.0))
                if epochs <= prior_epochs:
                    raise ValueError(
                        f"epochs ({epochs}) must exceed the checkpoint's "
                        f"epochs_trained ({prior_epochs})")
                epochs = epochs - prior_epochs
            cfg = _step_config(p, sizes, act_name, task, dist_name)
            opt0 = _init_opt(net, cfg.adaptive)
            shuffle = bool(p.get("shuffle_training_data", False))
            run_epoch = _compiled_epoch(cfg, batch, n_batches, use_rows,
                                        padded, shuffle)

            if not shuffle:
                key, pk = jax.random.split(key)
                perm0 = jax.random.permutation(pk, padded)
                Xs = Xs[perm0]
                y = y[perm0]
                w = w[perm0]
            keeper = ScoreKeeper(p.get("stopping_rounds", 0),
                                 p.get("stopping_metric"),
                                 p.get("stopping_tolerance", 1e-3),
                                 "binomial" if spec.nclasses == 2 else
                                 "multinomial" if spec.nclasses > 2 else
                                 "regression")
            n_epochs = max(int(np.ceil(epochs)), 1)
            # annealing/momentum ramp continue from the prior sample count
            samples = jnp.float32(prior.output.get("training_samples", 0.0)
                                  if prior is not None else 0.0)
            jax.block_until_ready((Xs, y, w))  # h2o3-lint: allow[transfer-seam] stage fence: train.init ends when the permuted design matrix is on the device
        history = []
        # what the epochs run, as the loop span and the model say it;
        # "default" matmul precision is bfloat16 operands with float32
        # accumulation on the TPU; weights and optimizer state are float32
        loop_rec = {"sizes": list(sizes), "batch": batch,
                    "n_batches": n_batches,
                    "optimizer": ("adadelta" if cfg.adaptive
                                  else "momentum_sgd"),
                    "precision": str(jax.config.jax_default_matmul_precision
                                     or "default")}
        with prof.phase("loop") as sp_loop:
            # cancel/max_runtime polling (the last ROADMAP-listed algo
            # without it — GLM/KMeans landed in PR 7): run_epoch dispatches
            # ASYNCHRONOUSLY, so an unbounded loop would enqueue every
            # remaining epoch before a watchdog cancel could land — the
            # cooperative poll would see nothing left to skip. Poll BEFORE
            # each dispatch and keep at most two epochs in flight by
            # blocking on epoch e-1's loss scalar before dispatching e+1:
            # compute still overlaps host work, but a cancel takes effect
            # within ~one epoch instead of at the end of the train.
            prev_loss = None
            e = 0
            for e in range(n_epochs):
                if job.cancel_requested:
                    e -= 1      # this epoch never dispatched
                    break
                key, ekey = jax.random.split(key)
                if prev_loss is not None:
                    jax.block_until_ready(prev_loss)  # h2o3-lint: allow[transfer-seam] deliberate depth bound: at most 2 epochs in flight (cancel-polling contract)
                net, opt0, samples, mloss = run_epoch(
                    net, opt0, samples, ekey, Xs, y, w,
                    jnp.int32((e * batch) % max(padded, 1)))
                prev_loss = mloss
                job.set_progress((e + 1) / n_epochs)
                if keeper.rounds > 0:
                    with prof.phase("score"):
                        entry = self._score(net, act, Xs, y, w, task,
                                            e + 1)
                    keeper.record(entry)
                    history.append(entry)
                    if keeper.should_stop():
                        break
                if job.cancel_requested:
                    break
            jax.block_until_ready(net[0]["W"])  # h2o3-lint: allow[transfer-seam] epoch-loop timing fence: the loop clock must cover device completion
            if e >= 0 and (not history or history[-1]["epoch"] != e + 1):
                # the last epoch's training loss, once the epochs are done
                with prof.phase("score"):
                    entry = self._score(net, act, Xs, y, w, task, e + 1)
                keeper.record(entry)
                history.append(entry)
            loop_rec["epochs"] = e + 1
            if sp_loop is not None:
                sp_loop.attrs.update(loop_rec)
        # counted once a train on the host, from shapes: what the
        # epochs dispatched, not a device fetch
        for name, n, what in (
                ("h2o3_dl_optimizer_steps_total", n_batches * (e + 1),
                 "optimizer steps of finished DeepLearning trains"),
                ("h2o3_dl_rows_trained_total", use_rows * (e + 1),
                 "rows the optimizer steps of finished DeepLearning "
                 "trains read")):
            telemetry.counter(name, {"algo": self.algo}, help=what).inc(n)

        with prof.phase("finalize"):
            model = DeepLearningModel(
                f"dl_{id(self) & 0xffffff:x}", self.params, spec, net,
                exp_names,
                {k: float(telemetry.device_get(v, pipeline="train"))
                 for k, v in means.items()},
                telemetry.device_get(xm, pipeline="train"),
                telemetry.device_get(xs, pipeline="train"), task, dist_name,
                hidden, act_name)
            model.optimizer_state = opt0
            model.scoring_history = history
            model.output["epochs_trained"] = prior_epochs + e + 1
            model.output["training_samples"] = float(
                telemetry.device_get(samples, pipeline="train"))
            model.output["train_loop"] = loop_rec
            model.output["precision"] = {
                "matmul": loop_rec["precision"], "weights": "float32",
                "optimizer_state": "float32"}
            if task == "autoencoder":
                self._autoencoder_metrics(model, net, act, Xs, w,
                                          valid_spec, means, xm, xs)
            else:
                out = model._predict_matrix(spec.X)
                model.training_metrics = compute_metrics(
                    out, spec.y, spec.w, spec.nclasses,
                    spec.response_domain)
                if valid_spec is not None:
                    vout = model._predict_matrix(valid_spec.X)
                    model.validation_metrics = compute_metrics(
                        vout, valid_spec.y, valid_spec.w, spec.nclasses,
                        spec.response_domain)
        model.output["training_loop_seconds"] = prof.phases["loop"]
        model.output["train_profile"] = {
            f"{k}_s": round(prof.phases.get(k, 0.0), 4)
            for k in ("init", "loop", "score", "finalize")}
        return model

    @staticmethod
    def _autoencoder_metrics(model, net, act, Xs, w, valid_spec, means, xm,
                             xs):
        """Reconstruction error metrics (hex/ModelMetricsAutoEncoder: MSE
        over all reconstructed cells), training and validation."""
        from h2o3_tpu.models.metrics import ModelMetricsRegression

        def recon_metrics(Xs_in, w_in):
            out_ = _forward_rows(net, Xs_in, act)
            per_row, wh = (np.asarray(v) for v in
                           telemetry.device_get(
                               (((out_ - Xs_in) ** 2).mean(axis=1),
                                w_in), pipeline="train"))
            live = wh > 0
            mse = float((per_row[live] * wh[live]).sum()
                        / max(wh[live].sum(), 1e-30))
            # MSE IS the reconstruction error — do not route per-row
            # MSEs through the regression maker (that would square
            # them again); ModelMetricsAutoEncoder reports the mean
            mm = ModelMetricsRegression(
                mse=mse, rmse=float(np.sqrt(mse)),
                mae=float("nan"), rmsle=float("nan"),
                r2=float("nan"), mean_residual_deviance=mse,
                nobs=int(live.sum()))
            return mm, mse

        model.training_metrics, mse = recon_metrics(Xs, w)
        model.output["reconstruction_mse"] = mse
        if valid_spec is not None:
            vXe, _, _ = expand_design(valid_spec, impute_means=means)
            vXs = (vXe - xm[None, :]) / xs[None, :]
            model.validation_metrics, vmse = recon_metrics(
                vXs, valid_spec.w)
            model.output["validation_reconstruction_mse"] = vmse

    @staticmethod
    def _score(net, act, Xs, y, w, task, epoch):
        out = _forward_rows(net, Xs, act)
        if task == "autoencoder":
            mse = float(telemetry.device_get(
                (w * ((out - y) ** 2).mean(axis=1)).sum() / w.sum(),
                pipeline="train"))
            return {"epoch": epoch, "mse": mse,
                    "rmse": float(np.sqrt(mse)), "deviance": mse}
        if task == "classification":
            logp = jax.nn.log_softmax(out, axis=1)
            ll = -jnp.take_along_axis(logp, y[:, None], axis=1)[:, 0]
            tl = float(telemetry.device_get(
                (w * ll).sum() / w.sum(), pipeline="train"))
            return {"epoch": epoch, "logloss": tl, "deviance": tl}
        mse = float(telemetry.device_get(
            (w * (out[:, 0] - y) ** 2).sum() / w.sum(),
            pipeline="train"))
        return {"epoch": epoch, "mse": mse, "rmse": float(np.sqrt(mse)),
                "deviance": mse}


register_model_class("deeplearning", DeepLearningModel)
