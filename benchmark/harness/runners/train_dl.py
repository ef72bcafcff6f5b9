"""Runner "train_dl": ``runners/train.py``'s window of whole trains, for a
DeepLearning configuration.

A step is ``estimator(**params).train(y=, training_frame=)`` through the
platform's normal estimator -> scheduler -> trainer path, ended by a fence
on the network's weights (``system.fence_model`` reads tree arrays). A step
fails where it degrades or retries, trains fewer epochs than
``params.epochs``, or where the epoch loop's record (``model.output
["train_loop"]``, what span ``train.loop`` carries) differs from the
configuration's ``expect`` in ``sizes``, ``batch`` or ``optimizer``.
``product`` is what the check (``checks/dl_train_follow.py``) holds against
the reference: the exported network, its standardisation and the train's
last optimizer state as host arrays, the program's probabilities over every
training row, its compiled optimizer step, the reported metrics and the
device's row counter."""
from __future__ import annotations

import numpy as np

from harness import device, system
from harness.runners.train import (FAIL_COUNTERS, State, end_to_end,  # noqa: F401
                                   release, shape)


def fence(model) -> None:
    """Wait for the network's weights: the end of a train."""
    import jax
    jax.block_until_ready([ly[k] for ly in model.net for k in ("W", "b")])


def _off_path(state: State, model) -> str | None:
    """Why this train did not run what the cell measures, or None."""
    out, want = model.output, state.config["expect"]
    asked = float(state.config["params"]["epochs"])
    if float(out.get("epochs_trained", 0)) != asked:
        return f"{out.get('epochs_trained')} epochs trained of {asked}"
    loop = out.get("train_loop") or {}
    state.info.update(train_loop=loop, precision=out.get("precision"))
    for key in ("sizes", "batch", "optimizer"):
        if loop.get(key) != want[key]:
            return (f"train.loop {key} {loop.get(key)!r}, the configuration "
                    f"expects {want[key]!r}")
    return None


def step(state: State) -> bool:
    """One whole train. False where it degraded, retried or left the path."""
    before = [device.counter_total(n) for n in FAIL_COUNTERS]
    est = system.estimator(state.config)
    est.train(y=state.config["data"]["response"], training_frame=state.frame)
    fence(est.model)
    state.model = est.model
    state.profiles.append(dict(est.model.output.get("train_profile") or {}))
    after = [device.counter_total(n) for n in FAIL_COUNTERS]
    why = _off_path(state, est.model)
    if after != before:
        why = f"{dict(zip(FAIL_COUNTERS, after))} after the train"
    if why:
        state.info["last_failure"] = why
    return why is None


def setup(cell: dict, seed: int, rehearse: bool) -> State:
    """Frame from the seed, then one train: it compiles, or loads, every
    program the window's trains run. A program that exposes no compiled
    optimizer step cannot be followed by the check: it is told so at once,
    before a row is made."""
    from h2o3_tpu.models import deeplearning
    if not hasattr(deeplearning, "compiled_step"):
        raise RuntimeError("this program exposes no compiled optimizer step "
                           "(models/deeplearning.py:compiled_step)")
    system.init_cloud(cell["chips"])
    state = State(cell, system.build_frame(cell["config"], seed, rehearse),
                  rehearse)
    if not step(state):
        raise RuntimeError(f"warm-up train: {state.info['last_failure']}")
    state.profiles.clear()
    return state


def product(state: State) -> dict:
    """What the last train of the window produced, as host arrays, and the
    compiled step it ran."""
    import jax
    from h2o3_tpu.models import deeplearning
    m = state.model
    tm = m.training_metrics
    saved = m._save_arrays()
    model = {k: np.asarray(v) for k, v in saved.items()
             if k[0] in "Wb" and k[1:].isdigit() or k in ("xm", "xs")}
    model["optimizer_state"] = [
        [(np.asarray(jax.device_get(ly["W"])),
          np.asarray(jax.device_get(ly["b"]))) for ly in acc]
        for acc in m.optimizer_state]
    pred = m.predict(state.frame)
    p1 = np.asarray(jax.device_get(pred.vecs[-1].data))[:state.frame.nrow]
    del pred
    return {**shape(state.frame), "model": model, "p1": p1,
            "step": deeplearning.compiled_step(m),
            "training_samples": float(m.output["training_samples"]),
            "reported": {"logloss": float(tm.logloss), "auc": float(tm.auc)}}
