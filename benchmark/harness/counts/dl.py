"""The work a DeepLearning train NEEDS, from the configuration's shapes
alone: rows, features, hidden widths, outputs, batch, epochs.

Flops: a row's forward pass is one multiply-add a weight; its backward pass
is one a weight for the weights' gradients and one a weight for the error
of every layer below the top, none for the inputs (the first layer's input
gradient is never needed). At 28 -> 200 -> 200 -> 2 that is 46,000 +
46,000 + 40,400 = 132,400 multiply-adds, 264,800 flops a row. The biases,
the Rectifier, the softmax and ADADELTA's update are left out: elementwise,
per weight or per unit, not per row and weight.

Bytes: an epoch reads the design matrix once (a row's features, its label
and its weight, 4 bytes each) and reads and writes the weights and both of
ADADELTA's accumulators once: what it needs if they stayed on the chip
between steps. An epoch's rows are whole batches, ``rows // batch`` of them;
no kernel's padding counts.
"""
from __future__ import annotations


def shapes(config: dict) -> dict:
    p, d = config["params"], config["data"]
    outputs = 2 if p["distribution"] in ("bernoulli", "binomial") else 1
    sizes = [int(d["features"])] + [int(h) for h in p["hidden"]] + [outputs]
    batch = int(p["mini_batch_size"])
    rows = int(d["rows"])
    return {"rows": rows, "sizes": sizes, "batch": batch,
            "epoch_rows": rows // batch * batch, "epochs": int(p["epochs"]),
            "weights": sum(a * b + b for a, b in zip(sizes, sizes[1:]))}


def macs_per_row(sizes) -> dict:
    layers = list(zip(sizes, sizes[1:]))
    forward = sum(a * b for a, b in layers)
    return {"forward": forward, "weight_grads": forward,
            "error": sum(a * b for a, b in layers[1:])}


def epochs(config: dict) -> list[dict]:
    """One phase an epoch: the forward and both backward products of every
    row of its batches, the design matrix read once, and the weights and
    the two accumulators read and written once."""
    s = shapes(config)
    macs = sum(macs_per_row(s["sizes"]).values())
    row_bytes = 4 * (s["sizes"][0] + 2)
    epoch = {"name": "epoch", "flops": 2 * macs * s["epoch_rows"],
             "bytes": row_bytes * s["epoch_rows"] + 2 * 3 * 4 * s["weights"]}
    return [epoch] * s["epochs"]


def train(config: dict) -> list[dict]:
    """One whole train: the epochs, then two forward passes over every
    training row (the last epoch's training loss, and finalize's
    probabilities for the metrics), each reading a row's features and
    writing its outputs."""
    s = shapes(config)
    fwd = macs_per_row(s["sizes"])["forward"]
    score = {"name": "forward", "flops": 2 * fwd * s["rows"],
             "bytes": 4 * (s["sizes"][0] + s["sizes"][-1]) * s["rows"]}
    return epochs(config) + [score, score]


BY_NAME = {"epochs": epochs, "train": train}
