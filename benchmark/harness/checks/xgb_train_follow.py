"""What decides ``correct`` in the XGBoost-hist train cell: the model the
window's last train produced, held against ``reference/xgb.py``.

As ``gbm_train_follow``: the whole exported model is scored over every row
by the reference (the log-loss to hold against the reported one, and the
margin before each tree of ``follow_trees``), and each of those trees is
followed node by node under the program's own routing. The numbers are that
check's, computed with XGBoost's objective (L2 ``reg_lambda`` and L1
``reg_alpha`` in gain and node value, ``min_child_weight`` a bound on a
child's hessian sum), and one more:

  cover_gap, node_value_gap, leaf_gap, split_regret, edge_gap, logloss_gap
                    as in gbm_train_follow, the values -eta T(G)/(H+lambda)
  child_weight_gap  how far the lighter child of a chosen split falls short
                    of ``min_child_weight`` in exact hessian sum, as a share
                    of the bound (0 where every split is allowed)

``control`` puts the reference in the program's place with one fault, as
there: ``"fp8"``, ``"half_batch"``, ``"bin_off_by_one"``,
``"last_step_dropped"``; and ``"bf16"``, the node sums from gradients
rounded to bfloat16: the precision below where the program sums in float32
(under 2**18 rows, the CPU tests' size).
"""
from __future__ import annotations

import numpy as np

from harness.checks import gbm_train_follow as gbm
from harness.checks.gbm_train_follow import _rel, on_other_edges
from harness.loader import plugin
from harness.reference import xgb as ref


CONTROLS = gbm.CONTROLS + ("bf16",)


def degrade(ghw, control: str):
    if control == "bf16":
        return ghw.astype(ref.jnp.bfloat16).astype(ref.jnp.float32)
    return gbm.degrade(ghw, control)


def objective(params: dict) -> dict:
    """The estimator's parameters as the reference's formulas name them."""
    return {"lam": float(params["reg_lambda"]),
            "alpha": float(params["reg_alpha"]),
            "min_child_weight": float(params["min_child_weight"]),
            "gamma": float(params["gamma"])}


def bins(params: dict) -> int:
    """``max_bins`` lanes hold ``max_bins - 2`` real bins and the NA lane."""
    return int(params["max_bins"]) - 2


def tree_gaps(tree: dict, st: dict, edges, depth: int, lr: float,
              obj: dict) -> dict:
    M = 2 ** (depth + 1) - 1
    baseD = 2 ** depth - 1
    tot = st["totals"]
    arrived = ~np.isnan(tot[:, 2])
    inner = arrived & (np.arange(M) < baseD) & tree["is_split"].astype(bool)
    ends = arrived & ~inner
    want_value = ref.weight(tot[:, 0], tot[:, 1], obj["lam"],
                            obj["alpha"]) * lr
    value = tree["value"].astype(np.float64)
    searched = arrived & (np.arange(M) < baseD)
    best, own = st["best_gain"], st["own_gain"]
    regret = 0.0
    if searched.any():
        scale = np.maximum(best[searched], np.median(best[searched]))
        regret = float(np.max((best[searched] - own[searched])
                              / np.maximum(scale, 1e-300)))
    edge, short = 0.0, 0.0
    for i in np.flatnonzero(inner):
        e = edges[int(tree["feat"][i])]
        edge = max(edge, float(np.min(np.abs(
            e.astype(np.float64) - float(tree["thr"][i])))) if len(e)
            else np.inf)
        lighter = np.nanmin(np.nan_to_num(tot[[2 * i + 1, 2 * i + 2], 1]))
        if obj["min_child_weight"] > 0:
            short = max(short, (obj["min_child_weight"] - lighter)
                        / obj["min_child_weight"])
    return {"cover_gap": _rel(tree["node_w"].astype(np.float64), tot[:, 2],
                              arrived),
            "node_value_gap": _rel(value, want_value, inner),
            "leaf_gap": _rel(value, want_value, ends),
            "split_regret": regret, "edge_gap": edge,
            "child_weight_gap": short}


def stand_in(tree: dict, st: dict, lr: float, obj: dict) -> dict:
    """The tree as a program would export it whose node sums are ``st``'s."""
    tot = st["totals"]
    arrived = ~np.isnan(tot[:, 2])
    value = np.where(arrived, ref.weight(tot[:, 0], tot[:, 1], obj["lam"],
                                         obj["alpha"]) * lr, 0.0)
    return {**tree, "value": value.astype(np.float32),
            "node_w": np.where(arrived, tot[:, 2], 0.0).astype(np.float32)}


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
    nb, obj = bins(params), objective(params)
    model = product["model"]
    depth, lr = int(model["max_depth"]), float(params["eta"])
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    followed = tuple(sorted({int(k) for k in cell["check"]["follow_trees"]
                             if int(k) < model["ntrees"]}))
    packed, thr, value = ref.pack_tree_table(model)
    margins, lls = ref.score(Xb, yb, wb, packed, thr, value, f0, depth,
                             stops=followed)
    del packed, thr, value
    edges = ref.quantile_edges(Xb, nb)
    codes = ref.digitize(Xb, ref.edge_matrix(edges), nb)
    half_edges = None
    if control == "half_batch":
        half_edges = ref.quantile_edges(Xb[:, ::2], nb)
    follow = (depth, nb, obj["lam"], obj["alpha"], obj["min_child_weight"],
              obj["gamma"])
    worst: dict = {}
    for k, margin in zip(followed, margins):
        tree = {n: model[n][k] for n in ("feat", "thr", "na_left", "is_split",
                                         "value", "node_w")}
        ghw = ref.grad_hess(margin, yb, wb)
        st = ref.follow_tree(Xb, codes, ghw, tree, *follow)
        held = tree
        if control == "bin_off_by_one":
            held = on_other_edges(tree, edges, edges, shift=1)
            st = ref.follow_tree(Xb, codes, ghw, held, *follow)
            held = stand_in(held, st, lr, obj)
        elif control in ("fp8", "bf16", "half_batch"):
            held = stand_in(tree, ref.follow_tree(
                Xb, codes, degrade(ghw, control), tree, *follow), lr, obj)
            if half_edges:
                held = on_other_edges(held, edges, half_edges)
        found = tree_gaps(held, st, edges, depth, lr, obj)
        if per_tree is not None:
            per_tree[k] = found
        for name, v in found.items():
            worst[name] = max(worst.get(name, 0.0), v)
    want = float(lls[-1])
    reported = (float(lls[-2]) if control == "last_step_dropped"
                else product["reported"]["logloss"])
    worst["logloss_gap"] = abs(reported - want) / want
    return worst
