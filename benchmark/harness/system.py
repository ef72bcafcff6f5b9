"""The calls into the system under test that every runner shares: the cloud,
the frame from the seed, the estimator from the configuration's file."""
from __future__ import annotations

import importlib

from harness.loader import plugin


def init_cloud(chips: int):
    import h2o3_tpu as h2o
    return h2o.init(n_data=chips)


PARTS = {"rows": 0, "score_rows": 1}     # data key -> the generator's part


def data_shape(config: dict, rehearse: bool) -> dict:
    data = dict(config["data"])
    if rehearse:
        data.update({k: int(v) for k, v in config["rehearse"].items()
                     if k in PARTS})
    return data


def build_frame(config: dict, seed: int, rehearse: bool = False,
                rows_key: str = "rows"):
    """A frame of the configuration's ``data[rows_key]`` rows (``rows``: the
    training table; ``score_rows``: the held-out one), made on the device
    from the seed and handed to the platform as its ingest hands columns
    over: one padded row-sharded matrix, split into per-column Vecs
    (``frame.vec.split_columns``)."""
    import jax
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import T_REAL, Vec, split_columns
    from h2o3_tpu.parallel.mesh import data_sharding, padded_len
    data = data_shape(config, rehearse)
    rows, F = int(data[rows_key]), int(data["features"])
    padded = padded_len(rows)
    X, y = plugin("generators", data["generator"]).make(
        seed, rows, padded, F, part=PARTS[rows_key])
    sh = data_sharding()
    cols = split_columns(jax.device_put(X, sh), F)
    del X
    names = [f"f{i}" for i in range(F)] + [data["response"]]
    vecs = [Vec(c, rows, T_REAL) for c in cols]
    vecs.append(Vec(jax.device_put(y, sh), rows, T_REAL))
    frame = Frame(names, vecs)
    jax.block_until_ready([v.data for v in frame.vecs])
    return frame


def estimator(config: dict):
    """A new estimator with the configuration's parameters, by its name."""
    mod, _, cls = config["estimator"].rpartition(".")
    return getattr(importlib.import_module(mod), cls)(**config["params"])


def fence_model(model) -> None:
    """Wait for the model's device arrays: the end of a train."""
    import jax
    jax.block_until_ready([a for a in (
        model._feat, model._thr, model._na_left, model._is_split,
        model._value, model._node_w) if a is not None])


def fence_frame(frame) -> None:
    """Wait for every column of a result frame: the end of a predict."""
    import jax
    jax.block_until_ready([v.data for v in frame.vecs
                           if v.data is not None])


def model_arrays(model) -> dict:
    """The trained model as host arrays, in its exported layout: per tree a
    heap of ``2**(depth+1)-1`` nodes; a row at a split node goes right when
    ``x >= thr`` (NA: right unless ``na_left``); leaf values carry the
    learning rate; the margin is ``f0`` plus the sum over trees."""
    import numpy as np
    out = {k: np.asarray(v) for k, v in model._save_arrays().items()
           if k in ("feat", "thr", "na_left", "is_split", "value", "node_w",
                    "f0")}
    out["max_depth"] = int(model.max_depth)
    out["ntrees"] = int(model.ntrees_built)
    return out
