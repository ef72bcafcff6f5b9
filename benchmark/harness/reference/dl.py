"""The plain reference for a DeepLearning train cell: H2O-3's feed-forward
network (hex/deeplearning: DeepLearning.java, Neurons.java) in plain
``jax.numpy`` float32, every product at ``HIGHEST`` precision. It imports
nothing of the program.

``forward`` is a Rectifier network with a softmax output, ``xent`` its
cross-entropy, ``grads`` the gradients written out layer by layer (held to
``jax.grad`` of ``xent`` by ``tests/test_dl_reference.py``), ``adadelta`` one
update as Neurons applies it (``E[g^2] <- rho E[g^2] + (1 - rho) g^2``;
``dx = -sqrt(E[dx^2] + eps) / sqrt(E[g^2] + eps) g``;
``E[dx^2] <- rho E[dx^2] + (1 - rho) dx^2``; ``w <- w + dx``), ``sgd`` a plain
step at a fixed rate (a control), ``standardise`` the inputs' moments,
``init_params`` UniformAdaptive (uniform in +-sqrt(6 / (fan_in + fan_out)),
zero biases), and ``score`` a blocked pass over every row. ``q`` names a
dtype that every matrix product's operands are rounded to first (None:
float32 as they are).

Departures from H2O's description:
- a step takes the gradient of the MEAN loss over a batch of rows, where H2O
  applies each row on its own (``mini_batch_size=1``, Hogwild threads): the
  program's synchronous minibatch, which the configuration lists as assumed;
- the moments divide by the weight sum (population variance) where H2O's
  DataInfo takes the sample sigma: 1 + 1/(2n) apart, 5e-8 at 10M rows;
- no ``max_w2`` clamp (H2O's default is infinite), no l1/l2 (default 0), no
  dropout (default none), no momentum (ADADELTA replaces it);
- the log-loss clamps probabilities to [1e-15, 1 - 1e-15], as H2O's metrics do.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 1 << 20
CLAMP = 1e-15


def rounded(a, q=None):
    return a if q is None else a.astype(q).astype(jnp.float32)


def mm(a, b, q=None):
    return jnp.matmul(rounded(a, q), rounded(b, q), precision=HIGHEST)


def forward(net, x, q=None):
    """(logits, pre-activations, each layer's input): ``net`` is a list of
    ``(W [in, out], b [out])``; every hidden layer is a Rectifier."""
    h, zs, hs = x, [], []
    for i, (W, b) in enumerate(net):
        hs.append(h)
        z = mm(h, W, q) + b
        zs.append(z)
        h = jnp.maximum(z, 0.0) if i < len(net) - 1 else z
    return h, zs, hs


def xent(logits, y, w):
    """Weighted mean softmax cross-entropy of integer classes ``y``."""
    logp = jax.nn.log_softmax(logits, axis=1)
    per = -(jax.nn.one_hot(y, logits.shape[1]) * logp).sum(axis=1)
    return (w * per).sum() / jnp.maximum(w.sum(), 1e-12)


def grads(net, x, y, w, q=None):
    """d xent / d (W, b) of every layer, by hand: the output's error
    ``(softmax - onehot) w / sum(w)``, then back through each layer (its
    weights' gradient, and the error of the layer below through W^T and the
    Rectifier's mask; none for the inputs)."""
    logits, zs, hs = forward(net, x, q)
    d = ((jax.nn.softmax(logits, axis=1)
          - jax.nn.one_hot(y, logits.shape[1]))
         * (w / jnp.maximum(w.sum(), 1e-12))[:, None])
    out = [None] * len(net)
    for i in reversed(range(len(net))):
        out[i] = (mm(hs[i].T, d, q), d.sum(axis=0))
        if i:
            d = mm(d, net[i][0].T, q) * (zs[i - 1] > 0)
    return out


def adadelta(net, state, g, rho, eps):
    """One ADADELTA update of every array: (net, (E[g^2], E[dx^2]))."""
    Eg, Ed = state
    new, nEg, nEd = [], [], []
    for arrays, gs, egs, eds in zip(net, g, Eg, Ed):
        layer, leg, led = [], [], []
        for a, ga, eg, ed in zip(arrays, gs, egs, eds):
            eg2 = rho * eg + (1 - rho) * ga * ga
            dx = -jnp.sqrt(ed + eps) / jnp.sqrt(eg2 + eps) * ga
            layer.append(a + dx)
            leg.append(eg2)
            led.append(rho * ed + (1 - rho) * dx * dx)
        new.append(tuple(layer))
        nEg.append(tuple(leg))
        nEd.append(tuple(led))
    return new, (nEg, nEd)


def sgd(net, state, g, rate):
    """A plain step at a fixed rate (the ``sgd_in_place`` control)."""
    return [tuple(a - rate * ga for a, ga in zip(arrays, gs))
            for arrays, gs in zip(net, g)], state


def standardise(X, w):
    """(mean, sigma) of each column under row weights ``w``; sigma from the
    population variance, floored at 1e-6."""
    X = X.astype(jnp.float32)
    ws = w.sum()
    mean = (X * w[:, None]).sum(axis=0) / ws
    var = (w[:, None] * (X - mean[None, :]) ** 2).sum(axis=0) / ws
    return mean, jnp.sqrt(jnp.maximum(var, 1e-12))


def init_params(key, sizes):
    """UniformAdaptive: W uniform in +-sqrt(6 / (fan_in + fan_out)), b 0."""
    net = []
    for a, b in zip(sizes[:-1], sizes[1:]):
        key, k = jax.random.split(key)
        lim = float(np.sqrt(6.0 / (a + b)))
        net.append((jax.random.uniform(k, (a, b), jnp.float32, -lim, lim),
                    jnp.zeros((b,), jnp.float32)))
    return net


def oracle_logit(X):
    """The log-odds the generator (``generators/higgs_shaped.py``) draws
    each label from: the best any model can do on its rows."""
    return (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
            + 0.3 * jnp.sin(3.0 * X[:, 4]))


def _ll(p1, y):
    p1 = jnp.clip(p1, CLAMP, 1.0 - CLAMP)
    return -(y * jnp.log(p1) + (1.0 - y) * jnp.log1p(-p1))


@partial(jax.jit, static_argnames=("q", "held_by"))
def _block(net, xm, xs, X, y, live, held, q, held_by):
    """One block's sums: exact, held, base-rate counts, oracle."""
    Xs = (X - xm[None, :]) / xs[None, :]
    logits, _, _ = forward(net, Xs)
    p1 = jax.nn.softmax(logits, axis=1)[:, 1]
    if held_by == "quantised":
        held = jax.nn.softmax(forward(net, Xs, q)[0], axis=1)[:, 1]
    elif held_by == "exact":
        held = p1
    yf = jnp.where(live, y, 0.0)
    z = oracle_logit(X)
    ll_oracle = jax.nn.softplus(z) - yf * z
    return {"ll": jnp.where(live, _ll(p1, yf), 0.0).sum(),
            "ll_held": jnp.where(live, _ll(held, yf), 0.0).sum(),
            "gap": jnp.where(live, jnp.abs(held - p1), 0.0).max(),
            "ll_oracle": jnp.where(live, ll_oracle, 0.0).sum(),
            "pos": yf.sum(), "n": live.sum()}


def score(X, y, rows, xm, xs, net, held_p1=None, q=None, block=BLOCK):
    """Every row's exact probability, in blocks of ``block`` rows, against
    the held ones: ``held_p1`` (host array, the program's), or the
    reference's forward with operands rounded to ``q``, or (neither) the
    exact ones. Returns the exact mean log-loss ``logloss``, the held
    probabilities' ``held_logloss`` and widest gap ``p1_gap``, the base
    rate's ``base_logloss`` and the generator's own ``oracle_logloss``."""
    held_by = ("program" if held_p1 is not None
               else "quantised" if q is not None else "exact")
    xm, xs = jnp.asarray(xm, jnp.float32), jnp.asarray(xs, jnp.float32)
    net = [(jnp.asarray(W, jnp.float32), jnp.asarray(b, jnp.float32))
           for W, b in net]
    sums = {"ll": 0.0, "ll_held": 0.0, "ll_oracle": 0.0, "pos": 0.0,
            "n": 0.0}
    gap = 0.0
    for lo in range(0, rows, block):
        hi = min(lo + block, rows)
        Xb, yb = X[lo:lo + block], y[lo:lo + block]
        live = jnp.arange(Xb.shape[0]) < hi - lo
        held = (jnp.asarray(np.pad(held_p1[lo:hi],
                                   (0, Xb.shape[0] - (hi - lo))))
                if held_by == "program" else None)
        got = jax.device_get(_block(net, xm, xs, Xb, yb, live, held, q,
                                    held_by))
        for k in sums:
            sums[k] += float(got[k])
        gap = max(gap, float(got["gap"]))
    n = sums["n"]
    rate = sums["pos"] / n
    base = -(rate * np.log(rate) + (1.0 - rate) * np.log1p(-rate))
    return {"logloss": sums["ll"] / n, "held_logloss": sums["ll_held"] / n,
            "p1_gap": gap, "base_logloss": float(base),
            "oracle_logloss": sums["ll_oracle"] / n}
