"""``H2ODeepLearningEstimator`` held to the plain reference
(``benchmark/harness/reference/dl.py``) on the CPU, on seeded weights at a
small size: the forward pass, the loss and its gradients, one ADADELTA step
through the program's own compiled step (the body its epoch's scan runs),
the standardisation, and a whole one-epoch train from given initial weights
and biases, step for step. Both sides are float32 here (the CPU's default
matmul precision is float32), so the limits are float32 rounding's."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models import deeplearning as dl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.generators import higgs_shaped  # noqa: E402
from harness.reference import dl as ref  # noqa: E402

ROWS, F, SIZES = 2048, 28, (28, 48, 24, 2)
RHO, EPS = 0.99, 1e-8


def _batch(seed=39, rows=256):
    X, y = higgs_shaped.make(seed, rows, rows, F)
    return X, y.astype(jnp.int32), jnp.ones((rows,), jnp.float32)


def _net(seed=1):
    """Seeded weights with non-zero biases, as (W, b) pairs."""
    key = jax.random.PRNGKey(seed)
    net = []
    for a, b in zip(SIZES[:-1], SIZES[1:]):
        key, kw, kb = jax.random.split(key, 3)
        net.append((jax.random.normal(kw, (a, b)) / np.sqrt(a),
                    0.1 * jax.random.normal(kb, (b,))))
    return net


def _state(net):
    """ADADELTA's two accumulators, seeded and not zero: E[g^2] up to 1e-3,
    E[dx^2] up to 1e-5."""
    out = []
    for seed, top in ((1, 1e-3), (2, 1e-5)):
        keys = iter(jax.random.split(jax.random.PRNGKey(seed), 2 * len(net)))
        out.append([tuple(jax.random.uniform(next(keys), a.shape, maxval=top)
                          for a in pair) for pair in net])
    return tuple(out)


def _program(net):
    return [{"W": W, "b": b} for W, b in net]


def _close(got, want, rtol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(float(np.abs(want).max()), 1e-30)
    assert float(np.abs(got - want).max()) / scale <= rtol


def test_forward_and_loss_match_the_reference():
    X, y, w = _batch()
    net = _net()
    logits = dl._forward(_program(net), X, jax.nn.relu)
    want, _, _ = ref.forward(net, X)
    _close(logits, want, 1e-5)
    loss = dl._loss_fn(logits, y, w, "classification", "bernoulli")
    assert float(loss) == pytest.approx(float(ref.xent(want, y, w)),
                                        rel=1e-5)


def test_hand_gradients_are_jax_grad_of_the_plain_loss():
    X, y, w = _batch()
    net = _net()
    by_hand = ref.grads(net, X, y, w)
    auto = jax.grad(lambda n: ref.xent(ref.forward(n, X)[0], y, w))(net)
    for (gW, gb), (aW, ab) in zip(by_hand, auto):
        _close(gW, aW, 1e-5)
        _close(gb, ab, 1e-5)


def test_program_gradients_match_the_reference():
    X, y, w = _batch()
    net = _net()
    prog = jax.grad(lambda p: dl._loss_fn(
        dl._forward(p, X, jax.nn.relu), y, w, "classification",
        "bernoulli"))(_program(net))
    for got, (gW, gb) in zip(prog, ref.grads(net, X, y, w)):
        _close(got["W"], gW, 1e-5)
        _close(got["b"], gb, 1e-5)


def _trained_model(frame, **params):
    est = dl.H2ODeepLearningEstimator(
        hidden=list(SIZES[1:-1]), epochs=1, seed=5,
        distribution="bernoulli", mini_batch_size=256, **params)
    est.train(y="label", training_frame=frame)
    return est.model


@pytest.fixture(scope="module")
def frame():
    h2o.init()
    X, y = higgs_shaped.make(7, ROWS, ROWS, F)
    X, y = np.asarray(X), np.asarray(y)
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["label"] = y
    return h2o.Frame.from_numpy(cols), X, y


def test_one_adadelta_step_of_the_compiled_step_matches_the_reference(frame):
    """From seeded weights and a non-zero ADADELTA state, the program's
    compiled step and the reference's update agree on the weights and on
    both accumulators."""
    model = _trained_model(frame[0])
    step = dl.compiled_step(model)
    X, y, w = _batch(seed=40)
    net = _net(seed=3)
    state = _state(net)
    want, want_st = ref.adadelta(net, state, ref.grads(net, X, y, w),
                                 RHO, EPS)
    opt = tuple(_program(s) for s in state)
    got, got_opt, samples, _ = step(_program(net), opt, jnp.float32(0), X,
                                    y, w, jax.random.PRNGKey(0))
    assert float(samples) == 256.0
    for g, (W, b) in zip(got, want):
        _close(g["W"], W, 1e-5)
        _close(g["b"], b, 1e-5)
    for acc_got, acc_want in zip(got_opt, want_st):
        for g, (W, b) in zip(acc_got, acc_want):
            _close(g["W"], W, 1e-4)
            _close(g["b"], b, 1e-4)


def test_the_standardisation_is_the_references(frame):
    model = _trained_model(frame[0])
    mean, sigma = ref.standardise(jnp.asarray(frame[1]),
                                  jnp.ones((ROWS,), jnp.float32))
    _close(model.xm, mean, 1e-5)
    _close(model.xs, sigma, 1e-5)


def test_a_one_epoch_train_from_given_weights_is_the_references(frame):
    """``initial_weights`` / ``initial_biases`` seed the network; one epoch
    of the program (8 steps of 256 rows, after its one permutation of the
    rows) lands where the reference's 8 ADADELTA steps from a zero state
    land on the same rows in the same order."""
    fr, X, y = frame
    net = _net(seed=11)
    model = _trained_model(
        fr, initial_weights=[np.asarray(W) for W, _ in net],
        initial_biases=[np.asarray(b) for _, b in net])
    assert model.output["train_loop"]["n_batches"] == ROWS // 256
    # the program's one permutation: its documented key chain (seed ->
    # init key -> permutation key), epoch 0 starts at row 0
    key = jax.random.PRNGKey(5)
    key, _ = jax.random.split(key)
    _, pk = jax.random.split(key)
    perm = np.asarray(jax.random.permutation(pk, ROWS))
    w = jnp.ones((ROWS,), jnp.float32)
    mean, sigma = ref.standardise(jnp.asarray(X), w)
    Xs = ((jnp.asarray(X) - mean) / sigma)[perm]
    yc = jnp.asarray(y).astype(jnp.int32)[perm]
    zeros = [tuple(jnp.zeros_like(a) for a in pair) for pair in net]
    cur, st = [tuple(pair) for pair in net], (zeros, zeros)
    for s in range(ROWS // 256):
        sl = slice(s * 256, (s + 1) * 256)
        cur, st = ref.adadelta(cur, st, ref.grads(cur, Xs[sl], yc[sl],
                                                  w[sl]), RHO, EPS)
    for got, (W, b) in zip(model.net, cur):
        _close(got["W"], W, 1e-4)
        _close(got["b"], b, 1e-4)
    # the trained weights moved: the comparison is not of the start
    assert float(np.abs(np.asarray(model.net[1]["W"])
                        - np.asarray(net[1][0])).max()) > 1e-4


def test_the_oracle_is_the_generators_own_log_odds():
    """``learned`` is measured from the log-odds the generator draws each
    label from: the reference's ``oracle_logit`` gives back every label of
    the generator's rows from the generator's own uniforms."""
    rows = 4096
    X, y = higgs_shaped.make(2**31 + 39, rows, rows, F)
    _, ky = jax.random.split(higgs_shaped.key_of(2**31 + 39))
    again = jax.random.uniform(ky, (rows,)) < jax.nn.sigmoid(
        ref.oracle_logit(X))
    assert np.array_equal(np.asarray(again, np.float32), np.asarray(y))


def test_a_forward_pass_over_a_frame_goes_by_blocks_of_rows(monkeypatch):
    """At 10M rows one hidden layer's activations are 8 GB: scoring and
    ``Model.predict`` take the rows ``_FORWARD_BLOCK`` at a time, and the
    probabilities are the whole pass's, row for row."""
    X, _, _ = _batch(rows=2048)
    params = _program(_net())
    whole = np.asarray(dl._forward(params, X, jax.nn.relu))
    monkeypatch.setattr(dl, "_FORWARD_BLOCK", 512)
    np.testing.assert_array_equal(
        np.asarray(dl._forward_rows(params, X[:2000], jax.nn.relu)),
        whole[:2000])
