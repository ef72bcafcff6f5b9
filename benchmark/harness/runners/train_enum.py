"""Runner "train_enum": ``runners/train.py``'s window of whole trains, on a
frame whose columns have the TYPES the configuration states.

``system.build_frame`` makes every column a real; here the configuration's
``data.kinds`` says which are enums, ``data.cardinalities`` how many levels
each has, ``data.names`` what they are called: an enum column is a ``T_ENUM``
Vec of int32 level codes with a domain of that many labels, as the parser
hands one over. The step, the metric, the shape and the release are
``runners/train.py``'s own; ``product`` exports the trees' sets beside the
arrays every train cell exports, for a check that follows them. A train whose
record lacks the expected lane layout is a failed step."""
from __future__ import annotations

import numpy as np

from harness import system
from harness.loader import plugin
from harness.runners import train
from harness.runners.train import State, end_to_end, release, shape  # noqa: F401


def build_frame(config: dict, seed: int, rehearse: bool = False):
    """The training table from the seed, typed as the configuration says."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import ENUM_NA, T_ENUM, T_REAL, Vec, split_columns
    from h2o3_tpu.parallel.mesh import data_sharding, padded_len
    data = system.data_shape(config, rehearse)
    rows, F = int(data["rows"]), int(data["features"])
    padded = padded_len(rows)
    X, y = plugin("generators", data["generator"]).make(seed, rows, padded, F)
    sh = data_sharding()
    cols = split_columns(jax.device_put(X, sh), F)
    del X
    vecs = []
    for c, kind, card in zip(cols, data["kinds"], data["cardinalities"]):
        if kind == "enum":
            codes = jnp.where(jnp.isnan(c), ENUM_NA, c).astype(jnp.int32)
            vecs.append(Vec(jax.device_put(codes, sh), rows, T_ENUM,
                            domain=[f"L{i}" for i in range(int(card))]))
        else:
            vecs.append(Vec(c, rows, T_REAL))
    del cols
    # the response is categorical in the source's table ("N" / "Y"): an
    # enum Vec, so that no train pays a host pass to make it one
    labels = jnp.where(jnp.isnan(y), ENUM_NA, y).astype(jnp.int32)
    vecs.append(Vec(jax.device_put(labels, sh), rows, T_ENUM,
                    domain=["N", "Y"]))
    frame = Frame(list(data["names"]) + [data["response"]], vecs)
    jax.block_until_ready([v.data for v in frame.vecs])
    return frame


def step(state: State) -> bool:
    """``train.step``, and the lane layout and the set features the
    configuration expects."""
    ok = train.step(state)
    want = state.config["expect"]
    pc = (state.model.output.get("packed_codes") or {}) if state.model else {}
    for key in ("lane_layout", "lanes", "set_features"):
        if ok and key in want and pc.get(key) != want[key]:
            state.info["last_failure"] = (
                f"{key} {pc.get(key)!r}, the configuration expects "
                f"{want[key]!r}")
            ok = False
    return ok


def setup(cell: dict, seed: int, rehearse: bool) -> State:
    """Frame from the seed, then one train: it compiles, or loads, every
    program the window's trains run. A program from before category-set
    splits cannot run the configuration: it is told so at once, before a
    row is made."""
    from h2o3_tpu.models import tree
    if cell["config"]["expect"].get("set_features") and not hasattr(
            tree, "set_split_features"):
        raise RuntimeError("this program has no category-set splits "
                           "(models/tree.py:set_split_features)")
    system.init_cloud(cell["chips"])
    state = State(cell, build_frame(cell["config"], seed, rehearse), rehearse)
    if not step(state):
        raise RuntimeError(f"warm-up train: {state.info['last_failure']}")
    state.profiles.clear()
    return state


def product(state: State) -> dict:
    """``train.product``, and the trees' sets: ``cat_set`` [T, M, words]
    uint32 (bit b of a node's words: level b goes left) and ``is_set``
    [T, M]."""
    out = train.product(state)
    saved = state.model._save_arrays()
    for k in ("cat_set", "is_set"):
        if k in saved:
            out["model"][k] = np.asarray(saved[k])
    return out
