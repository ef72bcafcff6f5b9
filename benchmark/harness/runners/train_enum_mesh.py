"""Runner "train_enum_mesh": ``runners/train_enum.py``'s window of whole
trains on a typed frame, ROW-SHARDED over the chips of the configuration's
``deployment``.

The cloud is ``h2o.init(n_data=chips)``; the rows come from a generator that
makes each block on the chip that holds it (``data.generator``, called with
``devices=deployment.n_data``), so the table is never on one chip or on the
host. Frame, step, product and release are ``train_enum``'s. A train is a
failed step besides where its record says it ran under another mesh layout
than the deployment's (``model.output["spmd"]``) or made its edges elsewhere
than the configuration expects (``model.output["packed_codes"]["sketch"]``:
``mesh``, per-shard statistics reduced over the data axis; ``host`` means
the whole table was copied to the host for its edges)."""
from __future__ import annotations

from harness import system
from harness.loader import plugin
from harness.runners import train_enum
from harness.runners.train import State, end_to_end, release, shape  # noqa: F401
from harness.runners.train_enum import product  # noqa: F401


def build_frame(config: dict, seed: int, rehearse: bool = False):
    """``train_enum.build_frame`` over rows made where they live: the
    generator returns them split over the deployment's chips as the
    platform's data axis splits them, so ``device_put`` moves nothing."""
    import jax
    import jax.numpy as jnp
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.frame.vec import ENUM_NA, T_ENUM, T_REAL, Vec, split_columns
    from h2o3_tpu.parallel.mesh import data_sharding, padded_len
    data = system.data_shape(config, rehearse)
    rows, F = int(data["rows"]), int(data["features"])
    X, y = plugin("generators", data["generator"]).make(
        seed, rows, padded_len(rows), F,
        devices=int(config["deployment"]["n_data"]))
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
    labels = jnp.where(jnp.isnan(y), ENUM_NA, y).astype(jnp.int32)
    vecs.append(Vec(jax.device_put(labels, sh), rows, T_ENUM,
                    domain=["N", "Y"]))
    frame = Frame(list(data["names"]) + [data["response"]], vecs)
    jax.block_until_ready([v.data for v in frame.vecs])
    return frame


def step(state: State) -> bool:
    """``train_enum.step``, and the mesh layout and the sketch's place."""
    ok = train_enum.step(state)
    if not ok:
        return False
    want, out = state.config["expect"], state.model.output
    got = {"n_data": (out.get("spmd") or {}).get("n_data"),
           "sketch": (out.get("packed_codes") or {}).get("sketch")}
    for key, value in got.items():
        if key in want and value != want[key]:
            state.info["last_failure"] = (
                f"{key} {value!r}, the configuration expects {want[key]!r}")
            return False
    return True


def setup(cell: dict, seed: int, rehearse: bool) -> State:
    """The cloud over the cell's chips, the frame from the seed, one train.
    A program that cannot make a row-sharded table's edges on the mesh
    (``ops/binning.py`` from before ``_mesh_sketch_edges``) copies the whole
    table to the host in every train and can pass no step of this cell
    (``expect.sketch``): it is told so at once, before a row is made."""
    from h2o3_tpu.models import tree
    from h2o3_tpu.ops import binning
    config = cell["config"]
    if config["expect"].get("set_features") and not hasattr(
            tree, "set_split_features"):
        raise RuntimeError("this program has no category-set splits "
                           "(models/tree.py:set_split_features)")
    if config["expect"].get("sketch") == "mesh" and not hasattr(
            binning, "_mesh_sketch_edges"):
        raise RuntimeError("this program makes a sharded table's bin edges "
                           "from a host copy of it "
                           "(ops/binning.py has no _mesh_sketch_edges)")
    if int(config["deployment"]["n_data"]) != int(cell["chips"]):
        raise RuntimeError(f"the deployment splits rows over "
                           f"{config['deployment']['n_data']} chips, the "
                           f"cell has {cell['chips']}")
    system.init_cloud(cell["chips"])
    state = State(cell, build_frame(config, seed, rehearse), rehearse)
    if not step(state):
        raise RuntimeError(f"warm-up train: {state.info['last_failure']}")
    state.profiles.clear()
    return state
