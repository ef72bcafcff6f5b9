"""Runner "score": a window of whole ``model.predict(frame)`` calls.

Set-up trains the configuration's model once on the training frame and then
makes the frame the traffic names (``"frame": "score_rows"``: the held-out
table of the configuration's data; ``"rows"``: the training frame itself).
The training frame stays resident, as it does in the platform's flow: train,
then score in the same cloud. A step scores the whole frame and ends when
every column of the result frame is ready. ``score_rows_per_s`` is the rows
scored over the window's elapsed time.
"""
from __future__ import annotations

from harness import system
from harness.runners import train as train_runner


class State(train_runner.State):
    pred = None
    scored = None           # the frame a step scores
    rows_key = "rows"


def step(state: State) -> bool:
    pred = state.model.predict(state.scored)
    system.fence_frame(pred)
    state.pred = pred
    ok = pred.nrow == state.scored.nrow
    if not ok:
        state.info["last_failure"] = f"scored {pred.nrow} of {state.scored.nrow}"
    return ok


def setup(cell: dict, seed: int, rehearse: bool) -> State:
    system.init_cloud(cell["chips"])
    state = State(cell, system.build_frame(cell["config"], seed, rehearse),
                  rehearse)
    if not train_runner.step(state):
        raise RuntimeError(f"set-up train: {state.info['last_failure']}")
    state.profiles.clear()
    state.rows_key = cell["traffic"].get("frame", "rows")
    state.scored = (state.frame if state.rows_key == "rows" else
                    system.build_frame(cell["config"], seed, rehearse,
                                       rows_key=state.rows_key))
    if not step(state):
        raise RuntimeError(f"warm-up predict: {state.info['last_failure']}")
    return state


def end_to_end(state: State, elapsed: float, steps_ok: int) -> dict:
    rows = steps_ok * state.scored.nrow
    return {"score_rows_per_s": {"value": rows / elapsed, "unit": "rows/s"}}


def product(state: State) -> dict:
    """The model that scored and the last result frame of the window; the
    columns stay on the device for the comparison."""
    pred = state.pred
    return {**train_runner.shape(state.scored), "part": system.PARTS[state.rows_key],
            "model": system.model_arrays(state.model),
            "pred": {n: pred.vec(n).data for n in pred.names},
            "pred_domain": list(pred.vec("predict").domain or ())}


def release(state: State) -> None:
    state.model = None
    state.frame = None
    state.scored = None
    state.pred = None
