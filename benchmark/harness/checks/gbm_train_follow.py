"""What decides ``correct`` in a train cell: the model the window's last
train produced, held against the plain reference (``reference/gbm.py``).

The whole exported model is scored over every row by the reference, which
gives the log-loss to hold against the one the program reported and the
margin before each of the trees that ``follow_trees`` lists (the first, a
middle and the last one: late trees, whose gradients are small, are where
bfloat16 sums are weakest). Each of those trees is then followed node by node
under the program's own routing. Numbers, each the worst over nodes and
followed trees, a node's gap measured against the reference's value there or
the median node's, whichever is larger:

  cover_gap       rows the program counted in a node        (histogram w)
  node_value_gap  Newton value of an inner node             (histogram g, h:
                  the sums the level kernel makes in the stated precision)
  leaf_gap        value of a node in which rows end         (segment totals)
  split_regret    best exact gain on offer at a node less the exact gain of
                  the split the program chose               (split search)
  edge_gap        the chosen threshold against the reference's own nearest
                  bin edge of that feature                  (sketch, digitise)
  logloss_gap     reported training log-loss against the exported model
                  scored by the reference                   (finalize, metrics)

``control`` puts the reference in the program's place, with one fault:
``"fp8"`` makes the node sums from gradients rounded to float8 (e4m3), the
precision below the stated bfloat16; ``"half_batch"`` makes sums and bin edges
from every second row only; ``"bin_off_by_one"`` splits every node one bin
above the program's choice, with sums that are right for that split;
``"last_step_dropped"`` reports the log-loss from before the last tree.
"""
from __future__ import annotations

import numpy as np

from harness.loader import plugin
from harness.reference import gbm as ref


def _rel(got, want, keep):
    """Worst |got - want| over ``keep``, against max(|want|, median |want|)."""
    if not keep.any():
        return 0.0
    w = np.abs(want[keep])
    return float(np.max(np.abs(got[keep] - want[keep])
                        / np.maximum(w, np.median(w))))


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
    for i in np.flatnonzero(inner):
        e = edges[int(tree["feat"][i])]
        edge = max(edge, float(np.min(np.abs(
            e.astype(np.float64) - float(tree["thr"][i])))) if len(e)
            else np.inf)
    return {"cover_gap": _rel(tree["node_w"].astype(np.float64), tot[:, 2],
                              arrived),
            "node_value_gap": _rel(value, want_value, inner),
            "leaf_gap": _rel(value, want_value, ends),
            "split_regret": regret, "edge_gap": edge}


CONTROLS = (None, "fp8", "half_batch", "bin_off_by_one", "last_step_dropped")


def degrade(ghw, control: str):
    jnp = ref.jnp
    if control == "fp8":
        return ghw.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    keep = (jnp.arange(ghw.shape[1]) % 2 == 0).astype(jnp.float32)
    return ghw * keep[None, :, None]                     # half_batch


def on_other_edges(tree: dict, edges, other, shift: int = 0) -> dict:
    """The tree with each threshold moved from ``edges`` to the same bin
    (plus ``shift``) of ``other``, where that bin exists."""
    thr = tree["thr"].copy()
    for i in np.flatnonzero(tree["is_split"]):
        f = int(tree["feat"][i])
        t = int(np.argmin(np.abs(edges[f] - thr[i]))) + shift
        if 0 <= t < len(other[f]):
            thr[i] = other[f][t]
    return {**tree, "thr": thr}


def stand_in(tree: dict, st: dict, lr: float) -> dict:
    """The tree as a program would export it whose node sums are ``st``'s."""
    tot = st["totals"]
    arrived = ~np.isnan(tot[:, 2])
    value = np.where(arrived, -tot[:, 0] / (tot[:, 1] + ref.EPS_H) * lr, 0.0)
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
    nbins = int(params["nbins"])
    model = product["model"]
    depth, lr = int(model["max_depth"]), float(params["learn_rate"])
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    followed = tuple(sorted({int(k) for k in cell["check"]["follow_trees"]
                             if int(k) < model["ntrees"]}))
    packed, thr, value = ref.pack_tree_table(model)
    margins, lls = ref.score(Xb, yb, wb, packed, thr, value, f0, depth,
                             stops=followed)
    del packed, thr, value
    edges = ref.EDGES[config["reference"]["edges"]](Xb, rows, nbins)
    codes = ref.digitize(Xb, ref.edge_matrix(edges))
    half_edges = None
    if control == "half_batch":
        half_edges = ref.EDGES[config["reference"]["edges"]](
            Xb[:, ::2], rows // 2, nbins)
    worst: dict = {}
    for k, margin in zip(followed, margins):
        tree = {n: model[n][k] for n in ("feat", "thr", "na_left", "is_split",
                                         "value", "node_w")}
        ghw = ref.grad_hess(margin, yb, wb)
        follow = (depth, nbins, float(params["min_rows"]),
                  float(params.get("min_split_improvement", 1e-5)))
        st = ref.follow_tree(Xb, codes, ghw, tree, *follow)
        held = tree
        if control == "bin_off_by_one":
            held = on_other_edges(tree, edges, edges, shift=1)
            st = ref.follow_tree(Xb, codes, ghw, held, *follow)
            held = stand_in(held, st, lr)
        elif control in ("fp8", "half_batch"):
            held = stand_in(tree, ref.follow_tree(
                Xb, codes, degrade(ghw, control), tree, *follow), lr)
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
