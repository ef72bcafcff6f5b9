"""The work a GBM train or a batch score NEEDS, from the configuration's
shapes alone: rows, features, bins (-> bytes per code), depth, trees.

Nothing here comes from ``cost_analysis()``, from HLO, or from a kernel's own
padding, lane width, one-hot products or MXU passes: whichever kernel or
fusion implements a level is held against the same count, so no share can
pass 100% by a count gone stale.

Per row and level the algorithm reads the row's F codes, its node id and its
(g, h, w), writes its node id, and makes 3*F accumulates. With int8 codes and
F=28 that is 48 bytes against 84 adds: bandwidth-bound on a v5e
(819 GB/s : 197 TFLOP/s).
"""
from __future__ import annotations

import math


def shapes(config: dict) -> dict:
    p, d = config["params"], config["data"]
    nbins = int(p["nbins"])
    return {"rows": int(d["rows"]), "F": int(d["features"]), "nbins": nbins,
            "score_rows": int(d.get("score_rows", d["rows"])),
            "code_bytes": 1 if nbins + 1 <= 256 else 2,
            "depth": int(p["max_depth"]), "trees": int(p["ntrees"]),
            "quantiles": config["reference"]["edges"] == "quantiles_global"}


def _phase(name, rows, bytes_per_row, flops_per_row):
    return {"name": name, "bytes": rows * bytes_per_row,
            "flops": rows * flops_per_row}


def tree_levels(config: dict) -> list[dict]:
    """All levels of one tree: ``depth`` histogram levels, then the routing
    of every row to its leaf with the leaf's exact (g, h, w) totals."""
    s = shapes(config)
    level = _phase("level", s["rows"],
                   s["F"] * s["code_bytes"] + 4 + 12 + 4, 3 * s["F"])
    last = _phase("leaf_route", s["rows"], s["code_bytes"] + 4 + 4 + 12, 3)
    return [level] * s["depth"] + [last]


def levels(config: dict) -> list[dict]:
    """Every level of every tree of one train."""
    return tree_levels(config) * shapes(config)["trees"]


def train(config: dict) -> list[dict]:
    """One whole train: sketch, digitise, then per tree the gradients, the
    levels and the margin update, then the final metrics."""
    s = shapes(config)
    rows, F = s["rows"], s["F"]
    cmp_per_value = math.ceil(math.log2(s["nbins"]))
    sketch = _phase("sketch", rows, F * 4,
                    F * (cmp_per_value if s["quantiles"] else 2))
    digitise = _phase("digitise", rows, F * (4 + s["code_bytes"]),
                      F * cmp_per_value)
    grad = _phase("gradients", rows, 12 + 8, 10)
    update = _phase("margin_update", rows, 4 + 4 + 4, 1)
    metrics = _phase("metrics", rows, 12, 10)
    per_tree = [grad] + tree_levels(config) + [update]
    return [sketch, digitise] + per_tree * s["trees"] + [metrics]


def score(config: dict) -> list[dict]:
    """One batch score of the held-out table (``data.score_rows``; the
    training rows where the configuration names none): read every row's F
    f32 features (and the stacked trees, which are nothing beside them),
    write label, p0 and p1."""
    s = shapes(config)
    return [_phase("score", s["score_rows"], s["F"] * 4 + 12,
                   s["trees"] * (s["depth"] + 1) + 10)]


BY_NAME = {"levels": levels, "train": train, "score": score}
