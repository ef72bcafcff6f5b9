"""What decides ``correct`` in a train cell on a table with enum columns: the
model the window's last train produced, held against
``reference/gbm_enum.py``.

The protocol is ``gbm_train_follow``'s: the reference scores the whole
exported model over every row (the log-loss to hold against the reported one,
and the margin before each tree of ``follow_trees``), then follows each of
those trees node by node under the program's own routing, SETS INCLUDED. The
numbers are that check's:

  cover_gap, node_value_gap, leaf_gap, logloss_gap   as there
  edge_gap      the chosen threshold of a split on a NUMERIC column against
                the reference's nearest edge (an enum's bins have no edge)
  split_regret  best exact gain on offer at a node over thresholds AND sets
                (an enum's levels in the order of G/H, every prefix) less
                the exact gain of the split the program chose

``control`` puts the reference in the program's place with one fault:
``"fp8"``, ``"half_batch"``, ``"last_step_dropped"`` as there;
``"bin_off_by_one"`` moves every threshold on a numeric column one bin up
and every set one level up (level b + 1 goes where b went: the codes of an
enum read off by one), with sums that are right for that tree;
``"ordinal_sets"`` chooses at every node
the best split a scan of the enums' levels IN INDEX ORDER offers, which is
what a program without set splits does: it has to fail ``split_regret``.
"""
from __future__ import annotations

import numpy as np

from harness.checks import gbm_train_follow as gbm
from harness.checks.gbm_train_follow import _rel
from harness.loader import plugin
from harness.reference import gbm_enum as ref

CONTROLS = gbm.CONTROLS + ("ordinal_sets",)
TREE_KEYS = ("feat", "thr", "na_left", "is_split", "value", "node_w")
SET_KEYS = ("cat_set", "is_set")


def data_layout(config: dict) -> ref.Layout:
    data = config["data"]
    return ref.layout(data["kinds"], data["cardinalities"],
                      int(config["params"]["nbins"]))


def by_set(tree: dict) -> np.ndarray:
    return (np.asarray(tree["is_set"], bool) if "is_set" in tree
            else np.zeros(len(tree["feat"]), bool))


def tree_gaps(tree: dict, st: dict, edges, depth: int, lr: float) -> dict:
    M = 2 ** (depth + 1) - 1
    baseD = 2 ** depth - 1
    tot = st["totals"]
    arrived = ~np.isnan(tot[:, 2])
    inner = arrived & (np.arange(M) < baseD) & tree["is_split"].astype(bool)
    ends = arrived & ~inner
    want_value = -tot[:, 0] / (tot[:, 1] + ref.EPS_H) * lr
    value = tree["value"].astype(np.float64)
    searched = arrived & (np.arange(M) < baseD)
    best, own = st["best_gain"], st["own_gain"]
    regret = 0.0
    if searched.any():
        scale = np.maximum(best[searched], np.median(best[searched]))
        regret = float(np.max((best[searched] - own[searched])
                              / np.maximum(scale, 1e-300)))
    edge = 0.0
    for i in np.flatnonzero(inner & ~by_set(tree)):
        e = edges[int(tree["feat"][i])]
        if e is None:
            edge = np.inf         # a threshold on an enum: not this cell's
        else:
            edge = max(edge, float(np.min(np.abs(
                e.astype(np.float64) - float(tree["thr"][i])))) if len(e)
                else np.inf)
    return {"cover_gap": _rel(tree["node_w"].astype(np.float64), tot[:, 2],
                              arrived),
            "node_value_gap": _rel(value, want_value, inner),
            "leaf_gap": _rel(value, want_value, ends),
            "split_regret": regret, "edge_gap": edge}


def on_other_edges(tree: dict, edges, other, shift: int = 0) -> dict:
    """``gbm.on_other_edges`` over the splits on numeric columns."""
    thr = tree["thr"].copy()
    for i in np.flatnonzero(tree["is_split"].astype(bool) & ~by_set(tree)):
        f = int(tree["feat"][i])
        if edges[f] is None:
            continue
        t = int(np.argmin(np.abs(edges[f] - thr[i]))) + shift
        if 0 <= t < len(other[f]):
            thr[i] = other[f][t]
    return {**tree, "thr": thr}


def sets_one_level_up(tree: dict) -> dict:
    """The tree whose sets hold level b + 1 where they held level b."""
    if "cat_set" not in tree:
        return tree
    words = np.asarray(tree["cat_set"], np.uint32)
    bits = ((words[:, :, None] >> np.arange(32, dtype=np.uint32)) & 1
            ).reshape(len(words), -1)
    bits = np.roll(bits, 1, axis=1)
    bits[:, 0] = 0
    up = (bits.reshape(words.shape + (32,)).astype(np.uint32)
          << np.arange(32, dtype=np.uint32)).sum(axis=-1, dtype=np.uint32)
    return {**tree, "cat_set": up}


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
    Xb, yb, wb = ref.make_rows(gen, seed, rows, padded, int(data["features"]))
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
