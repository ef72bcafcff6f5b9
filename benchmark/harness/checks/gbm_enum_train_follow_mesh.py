"""``checks/gbm_enum_train_follow.py`` for a cell whose table is split over
several chips: the same protocol, numbers and controls, against
``reference/gbm_enum_mesh.py``, which lays the row blocks over the chips of
the configuration's ``deployment`` so that the whole table fits and the
check takes what the one-chip cell's does.

``run`` is that file's, with the reference's rows made on
``deployment.n_data`` chips; the gaps of a tree, the controls' stand-ins and
the layout are imported from it unchanged."""
from __future__ import annotations

import numpy as np

from harness.checks import gbm_train_follow as gbm
from harness.checks.gbm_enum_train_follow import (  # noqa: F401
    CONTROLS, SET_KEYS, TREE_KEYS, data_layout, on_other_edges,
    sets_one_level_up, tree_gaps)
from harness.loader import plugin
from harness.reference import gbm_enum_mesh as ref


def run(cell: dict, product: dict, seed: int, control: str | None = None,
        per_tree: dict | None = None) -> dict:
    """The numbers compared. ``per_tree``, where given, is filled with each
    followed tree's own gaps (``chip_control.py`` prints them)."""
    if control not in CONTROLS:
        raise ValueError(f"no control {control!r}; there are {CONTROLS[1:]}")
    config, params = cell["config"], cell["config"]["params"]
    data = config["data"]
    rows, padded = product["rows"], product["padded"]
    gen = plugin("generators", data["generator"])
    Xb, yb, wb = ref.make_rows(gen, seed, rows, padded,
                               int(data["features"]),
                               int(config["deployment"]["n_data"]))
    lay = data_layout(config)
    model = product["model"]
    depth, lr = int(model["max_depth"]), float(params["learn_rate"])
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    followed = tuple(sorted({int(k) for k in cell["check"]["follow_trees"]
                             if int(k) < model["ntrees"]}))
    packed, thr, value, words = ref.pack_tree_table(model)
    margins, lls = ref.score(Xb, yb, wb, packed, thr, value, words, f0, depth,
                             stops=followed)
    del packed, thr, value, words
    edges = ref.uniform_edges(Xb, lay)
    codes = ref.digitize(Xb, edges, lay)
    half_edges = None
    if control == "half_batch":
        half_edges = ref.uniform_edges(Xb[:, ::2], lay)
    follow = (depth, lay, float(params["min_rows"]),
              float(params.get("min_split_improvement", 1e-5)))
    worst: dict = {}
    for k, margin in zip(followed, margins):
        tree = {n: np.asarray(model[n][k]) for n in TREE_KEYS + SET_KEYS
                if n in model}
        ghw = ref.grad_hess(margin, yb, wb)
        st = ref.follow_tree(Xb, codes, ghw, tree, *follow,
                             ordinal=control == "ordinal_sets")
        held = tree
        if control == "ordinal_sets":
            # the best an ordinal scan offers stands where the program's
            # own split's gain did
            st = {**st, "own_gain": np.where(np.isnan(st["own_gain"]),
                                             np.nan, st["ordinal_gain"])}
        elif control == "bin_off_by_one":
            held = sets_one_level_up(
                on_other_edges(tree, edges, edges, shift=1))
            st = ref.follow_tree(Xb, codes, ghw, held, *follow)
            held = gbm.stand_in(held, st, lr)
        elif control in ("fp8", "half_batch"):
            held = gbm.stand_in(tree, ref.follow_tree(
                Xb, codes, gbm.degrade(ghw, control), tree, *follow), lr)
            if half_edges:
                held = on_other_edges(held, edges, half_edges)
        found = tree_gaps(held, st, edges, depth, lr)
        if per_tree is not None:
            per_tree[k] = found
        for name, v in found.items():
            worst[name] = max(worst.get(name, 0.0), v)
    want = float(lls[-1])
    reported = (float(lls[-2]) if control == "last_step_dropped"
                else product["reported"]["logloss"])
    worst["logloss_gap"] = abs(reported - want) / want
    return worst
