"""ONE chip's share of the work a GBM train on a row-sharded table needs:
``counts/gbm_enum.py``'s phases at ``rows / deployment.n_data`` rows.

The readers that take a count (``least_time_share``,
``trace_pattern_roofline``) divide by ONE chip's peaks and read the FIRST
chip's op line and the step's wall time. Every chip of the data axis does
the same work over its own rows in step with the others, so one chip's share
over one chip's peak is the whole table's work over the four chips' peak: the
share these metrics report. The whole table's count against one chip's peak
would read ``n_data`` times too high.

What crosses the chips is listed as a phase of its own, ``all_reduce``: the
float32 sums a level builds (one child a previous-level node: ``3 * max(N/2,
1)`` rows at a level of N nodes, the sibling by subtraction) over every lane,
and the leaves' totals. Its bytes travel the interconnect, not HBM, so the
phase carries ``ici_bytes`` and adds nothing to ``bytes`` or ``flops``: a
count may not grow by what a layout chose to move. The lanes are the sum over
columns of (bins + the NA lane): what a histogram holds, not a kernel's
padding."""
from __future__ import annotations

from harness.counts import gbm_enum


def per_chip(config: dict) -> dict:
    """The configuration as one chip of its data axis sees it."""
    n = int(config["deployment"]["n_data"])
    rows = -(-int(config["data"]["rows"]) // n)
    return {**config, "data": dict(config["data"], rows=rows)}


def lanes(config: dict) -> int:
    d, nbins = config["data"], int(config["params"]["nbins"])
    return sum((int(c) if k == "enum" else nbins) + 1
               for k, c in zip(d["kinds"], d["cardinalities"]))


def all_reduce(config: dict) -> dict:
    """What one train sends through the level all-reduces, a chip."""
    depth = int(config["params"]["max_depth"])
    rows = sum(3 * max(2 ** d // 2, 1) for d in range(depth))
    per_tree = 4 * (rows * lanes(config) + 3 * 2 ** depth)
    return {"name": "all_reduce", "bytes": 0, "flops": 0,
            "ici_bytes": per_tree * int(config["params"]["ntrees"])}


def levels(config: dict) -> list[dict]:
    """One phase a kernel call of the first chip: its rows' levels."""
    return gbm_enum.levels(per_chip(config))


def train(config: dict) -> list[dict]:
    """One chip's share of a whole train, and the all-reduce."""
    return gbm_enum.train(per_chip(config)) + [all_reduce(config)]


BY_NAME = {"levels": levels, "train": train}
