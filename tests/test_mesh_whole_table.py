"""A row-sharded airline-shaped train (ISSUE 35): GBM on the benchmark's
airline table at 4,096 rows split over ``n_data=4`` against the same train
on one device, and what the train says of the mesh it ran under.

The four-shard frame comes from the benchmark's mesh runner
(``benchmark/harness/runners/train_enum_mesh.py``: rows made on the shard
that holds them), the one-device frame from ``train_enum``; both are the same
table bit for bit. The trainer runs the packed path on the CPU's scatter
reference. With float32 histograms the two trains grow the same trees (a
psum of four partial sums rounds apart from one sum in the last place: the
values agree to rounding, the splits and sets exactly at depth 3, where nodes
hold hundreds of rows). With bf16 sums, whose levels take the sibling by
subtraction after the psum, both are held to the plain reference.
"""
import json
import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import system  # noqa: E402
from harness.checks import gbm_enum_train_follow as check  # noqa: E402
from harness.runners import train_enum, train_enum_mesh  # noqa: E402

from h2o3_tpu import telemetry  # noqa: E402
from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh  # noqa: E402

ROWS, SEED = 4096, 35
pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")


def cell_for(config_file, depth, **params):
    with open(os.path.join(BENCH, "configs", config_file)) as f:
        config = json.load(f)
    config["params"].update({"ntrees": 3, "max_depth": depth,
                             "packed_codes": True, **params})
    config["data"]["rows"] = ROWS
    return {"name": "airline_gbm.train", "config": config,
            "check": {"follow_trees": [0, 1, 2]}}


def train(n_data, depth, **params):
    """(model, product, spans) of one train under a mesh of ``n_data``
    data shards."""
    runner, file = ((train_enum_mesh, "gbm_airline_whole_table.json")
                    if n_data > 1 else (train_enum, "gbm_perf_airline.json"))
    cell = cell_for(file, depth, **params)
    old = current_mesh()
    set_mesh(make_mesh(n_data=n_data, devices=jax.devices()[:n_data]))
    try:
        frame = runner.build_frame(cell["config"], SEED)
        assert len(frame.vecs[0].data.sharding.device_set) == n_data
        est = system.estimator(cell["config"])
        telemetry.install()
        telemetry.clear_spans()
        est.train(y=cell["config"]["data"]["response"], training_frame=frame)
        spans = {s.name: s for s in telemetry.finished_spans()}
        state = train_enum.State(cell, frame, True)
        state.model = est.model
        return est.model, cell, train_enum.product(state), spans
    finally:
        set_mesh(old)


def psum_bytes_by_hand(depth, trees, both_children):
    """896 lanes; a level of N nodes psums (g, h, w) of one child a
    previous-level node, of both with float32 histograms; 2**depth
    leaves' totals; float32."""
    rows = sum((1 if d == 0 or not both_children else 2)
               * 3 * max(2 ** d // 2, 1) for d in range(depth))
    return trees * 4 * (rows * 896 + 3 * 2 ** depth)


def _counter():
    return telemetry.registry().value(
        "h2o3_collective_bytes_total", {"algo": "gbm", "op": "psum"}) or 0


@pytest.mark.parametrize("precision", ["float32", "bfloat16"])
def test_four_shards_say_what_they_ran_and_all_reduced(precision):
    before = _counter()
    m, _, _, spans = train(4, 3, histogram_precision=precision)
    pc, spmd = m.output["packed_codes"], m.output["spmd"]
    assert (spmd["n_data"], spmd["n_model"]) == (4, 1)
    assert (pc["n_data"], pc["n_model"], pc["sketch"]) == (4, 1, "mesh")
    both = precision == "float32"
    assert pc["level_hist"] == ("both_children" if both else "smaller_child")
    assert pc["psum_bytes"] == psum_bytes_by_hand(3, 3, both)
    assert _counter() - before == pc["psum_bytes"]
    sketch, loop = spans["train.bin.sketch"], spans["train.loop"]
    # finite count, min and max of 8 columns: 3 x 8 x 4 bytes
    assert (sketch.attrs["where"], sketch.attrs["d2h_bytes"]) == ("mesh", 96)
    assert sketch.attrs["ranked_features"] == pc["ranked_features"] == 0
    for key in ("n_data", "n_model", "psum_bytes"):
        assert loop.attrs[key] == pc[key], key
    seen = spmd.get("collective", {})
    assert loop.attrs.get("straggler_ratio") == seen.get("straggler_ratio")


def test_one_shard_all_reduces_nothing():
    before = _counter()
    m, _, _, spans = train(1, 3)
    pc = m.output["packed_codes"]
    assert (pc["n_data"], pc["sketch"], pc["psum_bytes"]) == (1, "device", 0)
    assert _counter() == before
    # one device: the same extremes, no column sorted, the same 96 bytes
    sketch = spans["train.bin.sketch"]
    assert (sketch.attrs["where"], sketch.attrs["d2h_bytes"]) == ("device", 96)
    assert sketch.attrs["ranked_features"] == pc["ranked_features"] == 0
    assert spans["train.loop"].attrs["psum_bytes"] == 0
    assert "straggler_ratio" not in spans["train.loop"].attrs


def test_float32_histograms_grow_the_same_trees_on_four_shards_as_on_one():
    m1, _, p1, _ = train(1, 3)
    m4, _, p4, _ = train(4, 3)
    a, b = p1["model"], p4["model"]
    for k in ("feat", "is_split", "na_left", "is_set", "cat_set", "thr"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    np.testing.assert_array_equal(a["node_w"], b["node_w"])
    np.testing.assert_allclose(a["value"], b["value"], rtol=2e-5, atol=1e-7)
    assert a["is_set"].sum() > 10
    assert abs(p1["reported"]["logloss"] - p4["reported"]["logloss"]) < 1e-6


# the derived-sibling levels against the float32-exact reference at depth 5
# (nodes of a hundred rows and more); the widest reading of the two cases
# is beside each (this file, CPU, where the scatter reference adds bf16
# sums in float32)
DERIVED_LIMITS = {"cover_gap": 0.0, "edge_gap": 0.0,
                  "node_value_gap": 1e-5,      # 3.2e-6
                  "leaf_gap": 5e-6,            # 3.1e-7
                  "logloss_gap": 1e-6,         # 9.3e-8
                  "split_regret": 0.05}        # 9.7e-3


@pytest.mark.parametrize("n_data", [1, 4])
def test_smaller_child_levels_hold_the_reference_after_the_psum(n_data):
    """bf16 sums (the cells' precision) take each sibling by subtraction
    from the parent's histogram AFTER the psum of the built half: held to
    the plain reference on four shards as on one. (Depth 10 at 4,096 rows
    is no case for derived levels: one-row cells, tests/test_set_splits.py
    and PERF.md section 7 item 19d.)"""
    m, cell, product, _ = train(n_data, 5, histogram_precision="bfloat16")
    assert m.output["packed_codes"]["level_hist"] == "smaller_child"
    numbers = check.run(cell, product, SEED)
    assert not {n: v for n, v in numbers.items()
                if not v <= DERIVED_LIMITS[n]}, numbers
    assert product["model"]["is_set"].sum() > 30


@pytest.mark.parametrize("n_data", [1, 4])
def test_zero_weight_rows_ride_through_the_binomial_metrics(n_data,
                                                            monkeypatch):
    """Past the exact sweep's size every binomial kernel weighs its terms
    by ``w``: pad rows are not gathered away (on a mesh that gather brings
    every shard's rows to every chip), and the metrics are those of the
    compacted rows."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from h2o3_tpu.models import metrics, model_base
    rng = np.random.default_rng(3)
    n, live = 262_144, 262_137
    p1 = rng.random(n).astype(np.float32)
    y = (rng.random(n) < p1).astype(np.int32)
    w = (np.arange(n) < live).astype(np.float32)
    y[live:] = 0
    sh = NamedSharding(make_mesh(n_data=n_data,
                                 devices=jax.devices()[:n_data]), P("data"))
    probs = jnp.stack([1.0 - jax.device_put(p1, sh),
                       jax.device_put(p1, sh)], axis=1)
    want = metrics.make_binomial_metrics(p1[:live], y[:live], w[:live])
    monkeypatch.setattr(jnp, "take", lambda *a, **k: 1 / 0)   # no gather
    got = model_base.compute_metrics(probs, jax.device_put(y, sh),
                                     jax.device_put(w, sh), 2)
    assert got.nobs == want.nobs == live
    for key in ("auc", "aucpr", "logloss", "mse", "max_f1", "f1_threshold"):
        assert getattr(got, key) == pytest.approx(getattr(want, key),
                                                  rel=2e-6), key
    np.testing.assert_allclose(got.confusion_matrix, want.confusion_matrix)
