"""The work an XGBoost-hist train NEEDS: ``counts/gbm.py``'s phases, from
the same shapes. That file reads ``params["nbins"]``; this configuration
states ``max_bins``, so the count is handed the configuration with
``nbins = max_bins``: 256 bins a feature and the missing value beside them
are 257 values, two bytes a code, so F=28 is 28*2 + 4 + 12 + 4 = 76 bytes
against 84 adds a row and level (bandwidth-bound on a v5e), and a quantile
sketch and a digitise of ceil(log2(256)) = 8 compares a value. Whichever
kernel implements a level is held against the same count.
"""
from __future__ import annotations

from harness.counts import gbm


def _as_gbm(config: dict) -> dict:
    params = dict(config["params"], nbins=int(config["params"]["max_bins"]))
    return {**config, "params": params}


def levels(config: dict) -> list[dict]:
    """Every level of every tree of one train: one phase a kernel call."""
    return gbm.levels(_as_gbm(config))


def train(config: dict) -> list[dict]:
    """One whole train, as ``gbm.train`` with the sketch's quantiles read
    (``reference.edges`` of the configuration)."""
    return gbm.train(_as_gbm(config))


BY_NAME = {"levels": levels, "train": train}
