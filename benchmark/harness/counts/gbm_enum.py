"""The work a GBM train on a table with enum columns NEEDS, from the
configuration's shapes alone: ``counts/gbm.py``'s phases with the code width
and the digitise of the table's own columns.

A code has to hold the widest column's values: ``max(cardinalities, nbins)``
bins and the missing value, two bytes from 256 values on. Per row and level
the algorithm reads the row's F codes, its node id and its (g, h, w), writes
its node id and makes 3*F accumulates, WHATEVER lanes a kernel lays the bins
out on: at F=8 and two bytes a code 8*2 + 4 + 12 + 4 = 36 bytes against 24
adds a row and level (bandwidth-bound on a v5e). An enum column needs no
sketch and no compare to digitise (its value is its bin); a numeric column
needs ceil(log2(nbins)) compares a value.
"""
from __future__ import annotations

import math

from harness.counts import gbm


def _as_gbm(config: dict) -> dict:
    """The configuration as ``counts/gbm.py`` reads one: ``nbins`` the
    widest column's bins, which sizes the code."""
    d = config["data"]
    widest = max([int(c) for c in d["cardinalities"]]
                 + [int(config["params"]["nbins"])])
    return {**config, "params": dict(config["params"], nbins=widest)}


def levels(config: dict) -> list[dict]:
    """Every level of every tree of one train: one phase a kernel call."""
    return gbm.levels(_as_gbm(config))


def train(config: dict) -> list[dict]:
    """One whole train: ``gbm.train`` with the sketch and the digitise of
    the numeric columns alone."""
    s = gbm.shapes(_as_gbm(config))
    d = config["data"]
    numeric = sum(k != "enum" for k in d["kinds"])
    cmp_per_value = math.ceil(math.log2(int(config["params"]["nbins"])))
    phases = gbm.train(_as_gbm(config))
    rows, F = s["rows"], s["F"]
    phases[0] = gbm._phase("sketch", rows, F * 4, 2 * numeric)
    phases[1] = gbm._phase("digitise", rows, F * (4 + s["code_bytes"]),
                           numeric * cmp_per_value)
    return phases


BY_NAME = {"levels": levels, "train": train}
