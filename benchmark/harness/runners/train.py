"""Runner "train": a window of whole trains on a frame already in the cloud.

A step is ``estimator(**params).train(y=, training_frame=)`` through the
platform's normal estimator -> trainer path, ended by a fence on the model's
device arrays. ``train_s`` is the window's elapsed time over the trains it
completed: all the time over all the steps.
"""
from __future__ import annotations

from harness import device, system

FAIL_COUNTERS = ("h2o3_degrade_total", "h2o3_retry_total")


class State:
    def __init__(self, cell, frame, rehearse):
        self.cell, self.frame, self.rehearse = cell, frame, rehearse
        self.config = cell["config"]
        self.model = None
        self.profiles = []          # train_profile of every window train
        self.info = {}


def _off_path(state: State, model) -> str | None:
    """Why this train did not take the path the cell measures, or None."""
    from h2o3_tpu.ops import hist_adaptive as ha
    out, want = model.output, state.config["expect"]
    pc = out.get("packed_codes") or {}
    if pc.get("enabled") is not True:
        return f"packed codes not enabled: {pc}"
    if out.get("streamed"):
        return "the train went through the streamed path"
    if int(model.ntrees_built) != int(state.config["params"]["ntrees"]):
        return f"{model.ntrees_built} trees built"
    if pc.get("W") != want["W"]:
        return f"lane width {pc.get('W')}, the configuration expects {want['W']}"
    kernel = ha.binned_level_kernel(pc["W"], int(state.config["data"]["features"]))
    state.info.update(level_kernel=kernel, packed_codes=pc)
    if not state.rehearse and kernel != want["level_kernel"]:
        return f"level kernel {kernel}, expected {want['level_kernel']}"
    return None


def step(state: State) -> bool:
    """One whole train. False where it degraded, retried or left the path."""
    before = [device.counter_total(n) for n in FAIL_COUNTERS]
    est = system.estimator(state.config)
    est.train(y=state.config["data"]["response"], training_frame=state.frame)
    system.fence_model(est.model)
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
    program the window's trains run."""
    system.init_cloud(cell["chips"])
    state = State(cell, system.build_frame(cell["config"], seed, rehearse),
                  rehearse)
    if not step(state):
        raise RuntimeError(f"warm-up train: {state.info['last_failure']}")
    state.profiles.clear()
    return state


def end_to_end(state: State, elapsed: float, steps_ok: int) -> dict:
    return {"train_s": {"value": elapsed / max(steps_ok, 1), "unit": "s"}}


def shape(frame) -> dict:
    return {"rows": int(frame.nrow),
            "padded": int(frame.vecs[0].data.shape[0])}


def product(state: State) -> dict:
    """What the last train of the window produced, as host arrays."""
    m = state.model
    tm = m.training_metrics
    return {**shape(state.frame), "model": system.model_arrays(m),
            "reported": {"logloss": float(tm.logloss), "auc": float(tm.auc)}}


def release(state: State) -> None:
    state.model = None
    state.frame = None
