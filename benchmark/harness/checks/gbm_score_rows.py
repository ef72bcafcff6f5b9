"""What decides ``correct`` in the score cell: every row of the last result
frame of the window, held against the plain scorer (``reference/gbm.py``)
walking the same exported model over the same rows from the seed (the table
the window scored: the product's ``part`` names it).

  p1_gap     widest |p1 - reference p1| over all rows   (traversal, margin
             sum, link)
  p0_gap     widest |p0 - (1 - reference p1)|
  label_gap  how far from one half the reference's p1 lies on the row where
             the predicted label and the reference's disagree most (0: none)

``precision="bfloat16"`` is the control: the reference in the next
precision below, put in the program's place.
"""
from __future__ import annotations

import numpy as np

from harness.loader import plugin
from harness.reference import gbm as ref


def reference_p1(cell: dict, product: dict, seed: int, precision="float32"):
    data = cell["config"]["data"]
    rows, padded = product["rows"], product["padded"]
    gen = plugin("generators", data["generator"])
    Xb, yb, wb = ref.make_rows(gen, seed, rows, padded, int(data["features"]),
                               part=int(product.get("part", 0)))
    model = product["model"]
    packed, thr, value = ref.pack_tree_table(model)
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    margin, _ = ref.score(Xb, yb, wb, packed, thr, value, f0,
                          int(model["max_depth"]), precision=precision)
    return ref.sigmoid(margin.astype(ref.jnp.float32)).reshape(-1)[:rows]


def gaps(pred: dict, want_p1, rows: int) -> dict:
    jnp = ref.jnp
    p1 = jnp.asarray(pred["p1"])[:rows]
    p0 = jnp.asarray(pred["p0"])[:rows]
    label = jnp.asarray(pred["predict"])[:rows].astype(jnp.int32)
    wrong = label != (want_p1 > 0.5).astype(jnp.int32)
    return {"p1_gap": float(jnp.max(jnp.abs(p1 - want_p1))),
            "p0_gap": float(jnp.max(jnp.abs(p0 - (1.0 - want_p1)))),
            "label_gap": float(jnp.max(jnp.where(
                wrong, jnp.abs(want_p1 - 0.5), 0.0)))}


def run(cell: dict, product: dict, seed: int, control: str | None = None,
        per_tree: dict | None = None) -> dict:
    """``per_tree`` is the checks' common argument; a scorer has none."""
    if control not in (None, "bfloat16"):
        raise ValueError(f"no control {control!r}; there is 'bfloat16'")
    want = reference_p1(cell, product, seed)
    pred = product["pred"]
    if control:
        p1 = reference_p1(cell, product, seed, precision=control)
        pred = {"p1": p1, "p0": 1.0 - p1, "predict": p1 > 0.5}
    return gaps(pred, want, product["rows"])
