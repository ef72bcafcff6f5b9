"""What decides ``correct`` in a DeepLearning train cell: the model the
window's last train produced, held against the plain reference
(``reference/dl.py``). The rows are made again from the seed.

  p1_gap       widest |p1| between the program's probabilities over every
               training row (``Model.predict``: its design matrix,
               standardisation and forward pass) and the reference's
               forward pass on the exported weights and standardisation
  logloss_gap  reported training log-loss against the reference's log-loss
               of the exported model, relative           (finalize, metrics)
  weight_gap   the step follow: from the trained weights and the train's
               own last ADADELTA state, the program's compiled step (the
               body the timed scan runs) takes ``follow_steps`` consecutive
               batches of the rows, standardised by the exported moments;
               the reference takes the same steps. Per array the distance
               between the two changes over the size of the reference's
               change; the largest of the six arrays. A step that leaves
               the weights as they were reads 1.
  learned      the share of what the rows let a model learn that the model
               did not: (log-loss - the generator's own log-odds' log-loss)
               over (the base rate's log-loss - that); an untrained
               network reads about 1
  epochs_gap   epochs the device's own row counter (``training_samples``)
               says were trained, against ``params.epochs``, relative

``control`` puts the reference in the program's place with one fault:
``"fp8"`` rounds every product's operands to float8 e4m3, the precision
below the stated bfloat16 (probabilities, log-loss and steps);
``"sgd_in_place"`` steps by plain SGD at ``params.rate`` for ADADELTA;
``"last_epoch_dropped"`` is the model's record one epoch short;
``"init_weights"`` exports initial (UniformAdaptive) weights for the
trained ones.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness.loader import plugin
from harness.reference import dl as ref

CONTROLS = (None, "fp8", "sgd_in_place", "last_epoch_dropped",
            "init_weights")


def _net(model: dict) -> list:
    n = sum(1 for k in model if k.startswith("W"))
    return [(model[f"W{i}"], model[f"b{i}"]) for i in range(n)]


def _as_program(net, state):
    """(params, opt) in the program's layout: per layer {"W", "b"}."""
    def layers(arrays):
        return [{"W": jnp.asarray(W), "b": jnp.asarray(b)} for W, b in arrays]
    return layers(net), tuple(layers(s) for s in state)


def follow(product: dict, net, Xs, yb, steps: int, batch: int, params: dict,
           control: str | None) -> dict:
    """Each array's gap between the held and the reference's change over
    ``steps`` steps of ``batch`` rows from (``net``, the train's state)."""
    state = product["model"]["optimizer_state"]
    start = [(jnp.asarray(W), jnp.asarray(b)) for W, b in net]
    st = tuple([tuple(jnp.asarray(a) for a in layer) for layer in s]
               for s in state)
    rho, eps = float(params["rho"]), float(params["epsilon"])
    wb = jnp.ones((batch,), jnp.float32)
    want, want_st = start, st
    held, held_st = start, st
    prog = None
    if control not in ("fp8", "sgd_in_place"):
        prog = _as_program(start, st)
        step, samples = product["step"], jnp.float32(0.0)
        key = jax.random.PRNGKey(0)
    with jax.default_matmul_precision("highest"):
        for s in range(steps):
            xb = Xs[s * batch:(s + 1) * batch]
            yc = yb[s * batch:(s + 1) * batch]
            want, want_st = ref.adadelta(want, want_st,
                                         ref.grads(want, xb, yc, wb),
                                         rho, eps)
            if control == "fp8":
                held, held_st = ref.adadelta(
                    held, held_st,
                    ref.grads(held, xb, yc, wb, jnp.float8_e4m3fn), rho, eps)
            elif control == "sgd_in_place":
                held, held_st = ref.sgd(held, held_st,
                                        ref.grads(held, xb, yc, wb),
                                        float(params.get("rate", 0.005)))
    if prog is not None:
        # the program's step runs at the program's own precision
        params_p, opt_p = prog
        for s in range(steps):
            xb = Xs[s * batch:(s + 1) * batch]
            yc = yb[s * batch:(s + 1) * batch]
            params_p, opt_p, samples, _ = step(params_p, opt_p,
                                               samples, xb, yc, wb, key)
        held = [(ly["W"], ly["b"]) for ly in params_p]
    gaps = {}
    for i, (a0, a1, h1) in enumerate(zip(start, want, held)):
        for k, name in enumerate(("W", "b")):
            d_want = np.asarray(a1[k], np.float64) - np.asarray(a0[k],
                                                                np.float64)
            d_held = np.asarray(h1[k], np.float64) - np.asarray(a0[k],
                                                                np.float64)
            gaps[f"{name}{i}"] = float(np.linalg.norm(d_held - d_want)
                                       / max(np.linalg.norm(d_want), 1e-300))
    return gaps


def run(cell: dict, product: dict, seed: int, control: str | None = None,
        per_tree: dict | None = None) -> dict:
    """The numbers compared. ``per_tree``, where given, is filled with the
    readings behind them (``chip_control.py`` prints them)."""
    if control not in CONTROLS:
        raise ValueError(f"no control {control!r}; there are {CONTROLS[1:]}")
    config, params = cell["config"], cell["config"]["params"]
    data = config["data"]
    rows, padded = product["rows"], product["padded"]
    X, y = plugin("generators", data["generator"]).make(
        seed, rows, padded, int(data["features"]))
    model = product["model"]
    net = _net(model)
    if control == "init_weights":
        sizes = [net[0][0].shape[0]] + [W.shape[1] for W, _ in net]
        net = ref.init_params(jax.random.PRNGKey(seed & 0x7FFFFFFF), sizes)
    held_p1 = product["p1"] if control in (None, "sgd_in_place",
                                           "last_epoch_dropped") else None
    q = jnp.float8_e4m3fn if control == "fp8" else None
    got = ref.score(X, y, rows, model["xm"], model["xs"], net,
                    held_p1=held_p1, q=q)
    reported = (product["reported"]["logloss"] if held_p1 is not None
                else got["held_logloss"])
    batch = int(params["mini_batch_size"])
    steps = int(cell["check"]["follow_steps"])
    Xs = ((X[:steps * batch] - jnp.asarray(model["xm"])[None, :])
          / jnp.asarray(model["xs"])[None, :])
    yb = y[:steps * batch].astype(jnp.int32)
    gaps = follow(product, net, Xs, yb, steps, batch, params, control)
    samples = float(product["training_samples"])
    if control == "last_epoch_dropped":
        samples -= (padded // batch) * batch
    epochs = float(params["epochs"])
    trained = samples / ((padded // batch) * batch)
    if per_tree is not None:
        per_tree.update(weight_gaps=gaps, reference=got, reported=reported,
                        epochs_trained=trained)
    return {"p1_gap": got["p1_gap"],
            "logloss_gap": abs(reported - got["logloss"]) / got["logloss"],
            "weight_gap": max(gaps.values()),
            "learned": ((got["logloss"] - got["oracle_logloss"])
                        / (got["base_logloss"] - got["oracle_logloss"])),
            "epochs_gap": abs(trained - epochs) / epochs}
