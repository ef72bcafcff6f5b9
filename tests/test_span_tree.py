"""One span tree on the profiler's clock (ISSUE 27).

A train and a predict leave the span tree that ``models/gbm.py``,
``ops/binning.py`` and ``models/model_base.py`` document, with the right
parents; ``train_profile`` is those spans' durations and nothing else;
the jit stages (trace, lower, load, build) are counted where they happen
and land as one ``jit.*`` span a stage under the calling thread's span;
every live span is an event of a ``jax.profiler`` trace; and
``H2O3_TELEMETRY=0`` leaves ring, histograms and trace empty.
"""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import telemetry
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

ROWS, FEATURES = 200_000, 8


@pytest.fixture(autouse=True)
def _telemetry_on():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.install()
    yield
    telemetry.set_enabled(was)


@pytest.fixture(scope="module")
def frame():
    rng = np.random.default_rng(27)
    X = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=ROWS) > 0)
    cols = {f"x{i}": X[:, i] for i in range(FEATURES)}
    cols["y"] = y.astype(np.float32)
    return h2o.Frame.from_numpy(cols)


def _train(frame):
    """(estimator, the spans its train left), packed path on the CPU."""
    telemetry.install()
    est = H2OGradientBoostingEstimator(
        ntrees=6, max_depth=3, distribution="bernoulli", seed=1,
        packed_codes=True, score_tree_interval=3)
    telemetry.clear_spans()
    est.train(y="y", training_frame=frame)
    return est, telemetry.finished_spans()


@pytest.fixture(scope="module")
def warm_train(frame):
    """The second of two trains: nothing compiles in it."""
    _train(frame)
    return _train(frame)


def _tree(spans):
    """{name: [parent's name, ...]} and {name: [span, ...]}."""
    by_id = {s.span_id: s for s in spans}
    parents, named = {}, {}
    for s in spans:
        up = by_id.get(s.parent_id)
        parents.setdefault(s.name, []).append(up.name if up else None)
        named.setdefault(s.name, []).append(s)
    return parents, named


def test_a_train_leaves_the_span_tree_with_the_right_parents(frame,
                                                             warm_train):
    est, spans = warm_train
    parents, named = _tree(spans)
    want = {"train.gbm": None, "train.queue": "train.gbm",
            "train.spec": "train.gbm", "train.train": "train.gbm",
            "train.bin": "train.train", "train.bin.sketch": "train.bin",
            "train.bin.digitize": "train.bin", "train.bin.pack": "train.bin",
            "train.init": "train.train",
            "train.loop": "train.train", "train.score": "train.loop",
            "train.finalize": "train.train"}
    for name, parent in want.items():
        assert name in parents, f"no span {name}: {sorted(parents)}"
        assert set(parents[name]) == {parent}, (name, parents[name])
    # one span per wait on a score entry: 6 trees scored every 3
    assert len(named["train.score"]) == 2
    assert len(named["train.bin"]) == len(named["train.loop"]) == 1
    assert len(named["train.init"]) == 1     # the stage before the loop
    # the numeric 0/1 response's factor: the host formula's under the row
    # floor of the device's range pass, the range pass's at or above it
    from h2o3_tpu.frame import factor
    assert named["train.spec"][0].attrs["response_factor"] == (
        "device_range" if ROWS >= factor.DEVICE_MIN_ROWS else "host")
    loop = named["train.loop"][0]
    assert loop.attrs["trees"] == 6 and loop.attrs["chunks"] == 2
    # the packed path says what its levels ran, as the model's record does
    pc = est.model.output["packed_codes"]
    for key in ("W", "kernel", "feature_block", "row_tile", "leaf_lookup",
                "n_nodes", "lanes", "lane_layout", "set_features",
                "level_hist", "acc_rows"):
        assert loop.attrs[key] == pc[key], key
    # a numeric frame: every feature W lanes, no set feature
    assert (pc["lanes"], pc["lane_layout"], pc["set_features"]) == (
        32 * FEATURES, "uniform", 0)
    # depth 3: 15 nodes, whose values the margin update selects
    assert (pc["leaf_lookup"], pc["n_nodes"]) == ("select", 15)
    assert loop.attrs["code_bytes"] == pc["bytes_per_value"] == 1
    assert (pc["W"], pc["feature_block"]) == (32, FEATURES)
    sketch = named["train.bin.sketch"][0]
    assert (sketch.attrs["edges"], sketch.attrs["n_edges"]) == ("uniform", 19)
    assert (sketch.attrs["enum_features"],
            sketch.attrs["numeric_features"]) == (0, FEATURES)
    # uniform edges read a min and a max: no column is sorted (ISSUE 36)
    assert sketch.attrs["ranked_features"] == pc["ranked_features"] == 0
    # the stages follow one another inside train.train
    order = [named[n][0] for n in ("train.bin.sketch", "train.bin.digitize",
                                   "train.bin.pack", "train.init",
                                   "train.loop", "train.finalize")]
    starts = [s.t0 for s in order]
    assert starts == sorted(starts)
    for a, b in zip(order, order[1:]):
        assert a.t0 + a.duration_s <= b.t0 + 1e-6


def test_train_profile_is_the_spans_durations(warm_train):
    est, spans = warm_train
    _, named = _tree(spans)
    tp = est.model.output["train_profile"]
    assert set(tp) == {"bin_s", "sketch_s", "digitize_s", "pack_s", "init_s",
                       "loop_s", "score_s", "finalize_s", "queue_s",
                       "spec_s", "total_s", "other_s"}

    def seconds(name):
        return sum(s.duration_s for s in named[name])

    for key, name in (("bin_s", "train.bin"), ("init_s", "train.init"),
                      ("loop_s", "train.loop"),
                      ("score_s", "train.score"),
                      ("finalize_s", "train.finalize"),
                      ("spec_s", "train.spec"), ("queue_s", "train.queue"),
                      ("sketch_s", "train.bin.sketch"),
                      ("digitize_s", "train.bin.digitize"),
                      ("pack_s", "train.bin.pack")):
        assert tp[key] == pytest.approx(seconds(name), abs=6e-5), key
    assert est.model.output["training_loop_seconds"] == seconds("train.loop")
    parts = tp["sketch_s"] + tp["digitize_s"] + tp["pack_s"]
    assert parts <= tp["bin_s"] + 3e-4
    # 5% of bin_s; 2 ms for the host lines between the three (gates,
    # TreeConfig) where a loaded CPU makes bin_s small
    assert tp["bin_s"] - parts <= 0.05 * tp["bin_s"] + 2e-3
    assert tp["other_s"] >= 0.0
    assert tp["total_s"] >= seconds("train.gbm")
    # what _close_train_profile subtracts, init_s among it, and what is left
    stages = sum(tp[k] for k in ("queue_s", "spec_s", "bin_s", "init_s",
                                 "loop_s", "finalize_s", "other_s"))
    assert stages == pytest.approx(tp["total_s"], abs=1e-3)


def test_a_packed_drf_train_carries_the_bin_spans_and_gbms_record(
        frame, warm_train):
    """The bin stage is one function (tree.prepare_tree_inputs): a DRF
    train leaves GBM's bin spans, each fenced, and GBM's record of what
    its packed levels run."""
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    gbm, _ = warm_train
    telemetry.clear_spans()
    est = H2ORandomForestEstimator(ntrees=4, max_depth=3, seed=1,
                                   packed_codes=True)
    est.train(y="y", training_frame=frame)
    parents, named = _tree(telemetry.finished_spans())
    for name, parent in (("train.drf", None), ("train.train", "train.drf"),
                         ("train.bin", "train.train"),
                         ("train.bin.sketch", "train.bin"),
                         ("train.bin.digitize", "train.bin"),
                         ("train.bin.pack", "train.bin")):
        assert set(parents.get(name, ())) == {parent}, (name, parents)
    order = [named[n][0] for n in ("train.bin.sketch", "train.bin.digitize",
                                   "train.bin.pack")]
    for a, b in zip(order, order[1:]):
        assert a.t0 + a.duration_s <= b.t0 + 1e-6
    sketch = named["train.bin.sketch"][0]
    assert (sketch.attrs["edges"], sketch.attrs["n_edges"]) == ("uniform", 19)
    pc, want = est.model.output["packed_codes"], gbm.model.output["packed_codes"]
    assert set(pc) == set(want)
    # both depth 3, the same frame and mesh; DRF's 4 trees all-reduce
    # four sixths of what GBM's 6 did
    assert pc == {**want, "n_nodes": 15,
                  "psum_bytes": want["psum_bytes"] * 4 // 6}


def test_a_predict_leaves_its_four_children(frame, warm_train):
    est, _ = warm_train
    telemetry.clear_spans()
    est.model.predict(frame)            # the shape's first: programs compile
    # what the host pays once a shape is under the span that was open, by
    # name: the scorer's program under score.dispatch, one span a stage
    # however many events it folds
    dispatch, = _tree(telemetry.finished_spans())[1]["score.dispatch"]
    traced, = _jit_spans("trace", dispatch)
    lowered, = _jit_spans("lower", dispatch)
    assert traced.attrs["n"] > 1
    assert any("_score_stack" in name for name, _ in lowered.attrs["top"])
    assert 0 < traced.duration_s + lowered.duration_s <= dispatch.duration_s
    telemetry.clear_spans()
    pred = est.model.predict(frame)
    assert pred.nrow == ROWS
    parents, named = _tree(telemetry.finished_spans())
    root, = named["score.predict"]
    assert parents["score.predict"] == [None]
    assert root.attrs == {"rows": ROWS, "model": est.model.key}
    kids = ("score.adapt", "score.dispatch", "score.fetch", "score.frame")
    for k in kids:
        assert parents[k] == ["score.predict"], (k, parents.get(k))
    total = sum(named[k][0].duration_s for k in kids)
    assert total <= root.duration_s
    assert total == pytest.approx(root.duration_s, rel=0.05)
    # a warm predict is cache hits all through (PR 38): it costs the ring
    # its five spans and no jit.* span
    assert sorted(parents) == sorted(("score.predict",) + kids)
    assert len(telemetry.finished_spans()) == 5


@pytest.mark.parametrize("form", ["predicate", "gather"])
def test_the_dispatch_span_says_which_form_the_scorer_ran(
        frame, warm_train, monkeypatch, form):
    """Depth 3: 15 nodes, which the scorer descends by predicates; with
    the rule's bound at 0 the same predict gathers, and says so."""
    from h2o3_tpu.models import tree
    est, _ = warm_train
    if form == "gather":
        monkeypatch.setattr(tree, "SCORER_PREDICATE_MAX", 0)
    telemetry.clear_spans()
    est.model.predict(frame)
    _, named = _tree(telemetry.finished_spans())
    dispatch, = named["score.dispatch"]
    assert dispatch.attrs == {"node_form": form, "n_nodes": 15}


def _counter(name, algo="gbm"):
    return sum(s["value"] for s in telemetry.registry().samples()
               if s["name"] == name and s.get("labels", {}).get("algo") == algo)


def _enum_frame():
    from h2o3_tpu.frame.vec import T_ENUM, Vec
    rng = np.random.default_rng(34)
    c = rng.integers(0, 12, ROWS)
    x = rng.normal(size=ROWS).astype(np.float32)
    y = (rng.normal(size=12)[c] + 0.5 * x > 0).astype(np.int32)
    return h2o.Frame(["c", "x", "y"], [
        Vec.from_numpy(c, T_ENUM, [f"k{i}" for i in range(12)]),
        Vec.from_numpy(x), Vec.from_numpy(y, T_ENUM, ["n", "y"])])


@pytest.mark.parametrize("algo,depth,acc_rows,precision", [
    ("gbm", 5, 24, "bfloat16"), ("xgboost", 6, 48, "auto"),
    ("gbm-sets", 4, 12, "bfloat16"), ("gbm-sets", 4, 12, "auto"),
    ("gbm", 1, 3, "auto")],
    ids=["gbm-d5-bf16", "xgboost-d6", "set-splits-d4-bf16", "set-splits-d4",
         "stump"])
def test_the_loop_span_says_what_a_level_accumulates(frame, algo, depth,
                                                     acc_rows, precision):
    """A call of the packed level accumulates one child of every
    previous-level node, the one with the smaller w. With bf16 sums its
    sibling comes by subtraction (``smaller_child``); float32 histograms
    (``auto`` on this small frame) build it by a second call
    (``both_children``). The loop span and the model's record say which,
    with the accumulator rows of the deepest level, 3 * 2^(depth - 2)."""
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator
    Est = H2OXGBoostEstimator if algo == "xgboost" else (
        H2OGradientBoostingEstimator)
    more = {} if precision == "auto" else {"histogram_precision": precision}
    est = Est(ntrees=2, max_depth=depth, seed=1, packed_codes=True,
              score_tree_interval=0, **more)
    telemetry.install()
    telemetry.clear_spans()
    est.train(y="y", training_frame=_enum_frame() if algo == "gbm-sets"
              else frame)
    _, named = _tree(telemetry.finished_spans())
    loop, pc = named["train.loop"][0], est.model.output["packed_codes"]
    assert pc["set_features"] == (1 if algo == "gbm-sets" else 0)
    assert (pc["level_hist"], pc["acc_rows"]) == (
        "smaller_child" if precision == "bfloat16" else "both_children",
        acc_rows)
    assert (loop.attrs["level_hist"], loop.attrs["acc_rows"]) == (
        pc["level_hist"], pc["acc_rows"])


def test_a_set_split_train_says_so_in_spans_counters_and_routes():
    """An enum column on the packed path (ISSUE 33): the sketch span
    counts the enum features, the loop span and the model's record carry
    the lane layout and the set features, a predict's dispatch span the
    set nodes, and the two split counters move by the model's own
    arrays; /3/Timeline and /metrics show them."""
    from h2o3_tpu.api import server
    from h2o3_tpu.frame.vec import T_ENUM, Vec
    rng = np.random.default_rng(33)
    n = 4096
    c = rng.integers(0, 40, n)
    effect = rng.normal(size=40)
    x = rng.normal(size=n).astype(np.float32)
    y = (effect[c] + 0.5 * x + 0.3 * rng.normal(size=n) > 0).astype(np.int32)
    frame = h2o.Frame(["c", "x", "y"], [
        Vec.from_numpy(c, T_ENUM, [f"k{i}" for i in range(40)]),
        Vec.from_numpy(x), Vec.from_numpy(y, T_ENUM, ["n", "y"])])
    before = {k: _counter(k) for k in ("h2o3_tree_splits_total",
                                       "h2o3_tree_set_splits_total")}
    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=1,
                                       packed_codes=True)
    telemetry.clear_spans()
    est.train(y="y", training_frame=frame)
    _, named = _tree(telemetry.finished_spans())
    sketch, loop = named["train.bin.sketch"][0], named["train.loop"][0]
    assert (sketch.attrs["enum_features"],
            sketch.attrs["numeric_features"]) == (1, 1)
    # an enum response needs no factor
    assert named["train.spec"][0].attrs["response_factor"] == "none"
    pc = est.model.output["packed_codes"]
    # 40 levels + NA -> 48 lanes, 20 bins + NA -> 24
    assert (pc["lane_layout"], pc["lanes"], pc["set_features"], pc["W"]) == (
        "ragged", 72, 1, 48)
    for key in ("lanes", "lane_layout", "set_features", "level_hist",
                "acc_rows"):
        assert loop.attrs[key] == pc[key], key
    m = est.model
    splits = int(np.asarray(m._is_split).sum())
    sets = int(np.asarray(m._is_set).sum())
    assert 0 < sets <= splits
    assert _counter("h2o3_tree_splits_total") - before[
        "h2o3_tree_splits_total"] == splits
    assert _counter("h2o3_tree_set_splits_total") - before[
        "h2o3_tree_set_splits_total"] == sets
    telemetry.clear_spans()
    m.predict(frame)
    _, named = _tree(telemetry.finished_spans())
    dispatch, = named["score.dispatch"]
    assert dispatch.attrs["set_nodes"] == sets
    assert dispatch.attrs["n_nodes"] == 15
    # the routes: the trace of the span ring, and the exposition
    trace = server._timeline({"format": "trace"}, None)["__raw"]
    text = trace.decode() if isinstance(trace, bytes) else str(trace)
    assert "set_nodes" in text
    exposition = server._metrics({}, None)["__raw"]
    exposition = (exposition.decode() if isinstance(exposition, bytes)
                  else str(exposition))
    assert 'h2o3_tree_set_splits_total{algo="gbm"}' in exposition
    assert 'h2o3_tree_splits_total{algo="gbm"}' in exposition


@pytest.mark.parametrize("stopping_rounds", [0, 2],
                         ids=["scored_once", "scored_every_epoch"])
def test_a_deeplearning_train_leaves_its_stages_attrs_and_counters(
        frame, stopping_rounds):
    """A DeepLearning train (ISSUE 39) leaves ``train.init``,
    ``train.loop`` (attrs: what the epochs ran, as ``model.output
    ["train_loop"]`` says it) with ``train.score`` in it, and
    ``train.finalize`` under ``train.train``; its ``train_profile`` is
    their durations; the two counters move by what the epochs dispatched,
    from shapes; /metrics shows them. The last epoch is scored once after
    the loop's fence; early stopping scores every epoch (three epochs never
    meet two rounds of two) and nothing after."""
    from h2o3_tpu.api import server
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator
    names = ("h2o3_dl_optimizer_steps_total", "h2o3_dl_rows_trained_total")
    before = {k: _counter(k, "deeplearning") for k in names}
    est = H2ODeepLearningEstimator(
        hidden=[16, 8], epochs=3, seed=1, distribution="bernoulli",
        mini_batch_size=4096, stopping_rounds=stopping_rounds)
    telemetry.clear_spans()
    est.train(y="y", training_frame=frame)
    parents, named = _tree(telemetry.finished_spans())
    want = {"train.deeplearning": None, "train.queue": "train.deeplearning",
            "train.spec": "train.deeplearning",
            "train.train": "train.deeplearning", "train.init": "train.train",
            "train.loop": "train.train", "train.score": "train.loop",
            "train.finalize": "train.train"}
    for name, parent in want.items():
        assert name in parents, f"no span {name}: {sorted(parents)}"
        assert set(parents[name]) == {parent}, (name, parents[name])
    assert len(named["train.score"]) == (3 if stopping_rounds else 1)
    out = est.model.output
    n_batches = ROWS // 4096
    assert out["train_loop"] == {
        "sizes": [FEATURES, 16, 8, 2], "batch": 4096,
        "n_batches": n_batches, "epochs": 3, "optimizer": "adadelta",
        "precision": "default"}
    loop = named["train.loop"][0]
    for key, value in out["train_loop"].items():
        assert loop.attrs[key] == value, key
    assert out["precision"] == {"matmul": "default", "weights": "float32",
                                "optimizer_state": "float32"}
    tp = out["train_profile"]
    assert set(tp) == {"init_s", "loop_s", "score_s", "finalize_s",
                       "queue_s", "spec_s", "total_s", "other_s"}
    for key, name in (("init_s", "train.init"), ("loop_s", "train.loop"),
                      ("score_s", "train.score"),
                      ("finalize_s", "train.finalize"),
                      ("spec_s", "train.spec")):
        assert tp[key] == pytest.approx(
            sum(s.duration_s for s in named[name]), abs=6e-5), key
    order = [named[n][0] for n in ("train.init", "train.loop",
                                   "train.finalize")]
    assert [s.t0 for s in order] == sorted(s.t0 for s in order)
    assert out["training_loop_seconds"] == pytest.approx(tp["loop_s"],
                                                         abs=6e-5)
    assert _counter(names[0], "deeplearning") - before[names[0]] == (
        3 * n_batches)
    assert _counter(names[1], "deeplearning") - before[names[1]] == (
        3 * n_batches * 4096)
    exposition = server._metrics({}, None)["__raw"]
    exposition = (exposition.decode() if isinstance(exposition, bytes)
                  else str(exposition))
    for name in names:
        assert f'{name}{{algo="deeplearning"}}' in exposition


def test_a_deeplearning_model_hands_out_the_step_its_epochs_ran(frame):
    """``deeplearning.compiled_step`` is the epoch's scan body compiled
    alone: from the train's own last optimizer state, one step on a batch
    moves the weights and the accumulators, and counts the batch's rows."""
    from h2o3_tpu.models import deeplearning as dl
    est = dl.H2ODeepLearningEstimator(hidden=[16, 8], epochs=1, seed=1,
                                      distribution="bernoulli",
                                      mini_batch_size=4096)
    est.train(y="y", training_frame=frame)
    m = est.model
    Eg, Ed = m.optimizer_state
    assert [ly["W"].shape for ly in Eg] == [ly["W"].shape for ly in m.net]
    xb = jnp.zeros((64, FEATURES), jnp.float32).at[:, 0].set(1.0)
    yb = jnp.ones((64,), jnp.int32)
    net, (nEg, nEd), samples, loss = dl.compiled_step(m)(
        m.net, m.optimizer_state, jnp.float32(100.0), xb, yb,
        jnp.ones((64,), jnp.float32), jax.random.PRNGKey(0))
    assert float(samples) == 164.0 and float(loss) > 0
    assert not np.array_equal(np.asarray(net[2]["b"]),
                              np.asarray(m.net[2]["b"]))
    assert not np.array_equal(np.asarray(nEd[2]["b"]),
                              np.asarray(Ed[2]["b"]))


@pytest.mark.parametrize("hist,where", [("uniform_adaptive", "mesh"),
                                        ("quantiles_global", "device")])
def test_the_sketch_and_loop_spans_say_where_edges_were_made_and_what_crossed(
        frame, hist, where):
    """On the suite's 8-shard mesh (ISSUE 35): ``train.bin.sketch`` says
    where the edges were made and what the host fetched for them,
    ``train.loop`` and the model's record the mesh layout and the bytes the
    train all-reduced, ``h2o3_collective_bytes_total`` moves by them once,
    and the shards' straggler ratio rides the loop span."""
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards
    nd = n_data_shards(current_mesh())
    assert nd == 8
    before = _counter("h2o3_collective_bytes_total")
    est = H2OGradientBoostingEstimator(
        ntrees=4, max_depth=3, distribution="bernoulli", seed=1,
        packed_codes=True, histogram_type=hist, nbins=20)
    telemetry.clear_spans()
    est.train(y="y", training_frame=frame)
    _, named = _tree(telemetry.finished_spans())
    sketch, loop = named["train.bin.sketch"][0], named["train.loop"][0]
    assert sketch.attrs["where"] == where
    # the extremes alone are 3 numbers a column; ranks add their neighbours
    assert (sketch.attrs["d2h_bytes"] == 3 * FEATURES * 4 if where == "mesh"
            else sketch.attrs["d2h_bytes"] > 3 * FEATURES * 4)
    # uniform edges sort no column, quantile edges every one (ISSUE 36)
    ranked = 0 if where == "mesh" else FEATURES
    assert sketch.attrs["ranked_features"] == ranked
    pc = est.model.output["packed_codes"]
    assert (pc["sketch"], pc["n_data"], pc["n_model"]) == (where, nd, 1)
    assert pc["ranked_features"] == ranked
    # 25,000 rows a shard: float32 histograms, both children a level;
    # F x W = 8 x 32 lanes; (1 + 2 + 4) x 3 rows and 8 leaves' totals
    assert pc["psum_bytes"] == 4 * 4 * (21 * FEATURES * 32 + 3 * 8)
    for key in ("n_data", "n_model", "psum_bytes"):
        assert loop.attrs[key] == pc[key], key
    assert _counter("h2o3_collective_bytes_total") - before == pc[
        "psum_bytes"]
    seen = est.model.output["spmd"].get("collective") or {}
    assert loop.attrs.get("straggler_ratio") == seen.get("straggler_ratio")
    exposition = __import__("h2o3_tpu.api.server", fromlist=["x"])._metrics(
        {}, None)["__raw"]
    exposition = (exposition.decode() if isinstance(exposition, bytes)
                  else str(exposition))
    assert 'h2o3_collective_bytes_total{algo="gbm",op="psum"}' in exposition


BOOT = r"""
import json
import h2o3_tpu as h2o
from h2o3_tpu import telemetry
h2o.init()
print(json.dumps([[s.name, s.span_id, s.parent_id, s.t_wall, s.duration_s]
                  for s in telemetry.finished_spans()]))
"""


def test_a_process_boots_under_two_root_spans_once():
    """Root ``boot.import`` (the package's import, first line to last)
    and root ``boot.init`` around ``h2o3_tpu.init``: one of each in a new
    process, the ring's earliest spans (ISSUE 37)."""
    import json
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu", H2O3_TELEMETRY="1",
               PYTHONPATH=repo)
    done = subprocess.run([sys.executable, "-c", BOOT], env=env, cwd=repo,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    spans = json.loads(done.stdout.strip().splitlines()[-1])
    by_name = {}
    for name, _span_id, parent_id, t_wall, seconds in spans:
        by_name.setdefault(name, []).append((parent_id, t_wall, seconds))
    assert {n: len(v) for n, v in by_name.items()
            if n.startswith("boot.")} == {"boot.import": 1, "boot.init": 1}
    (imp_parent, imp_start, imp_s), = by_name["boot.import"]
    (init_parent, init_start, init_s), = by_name["boot.init"]
    assert imp_parent == 0 and init_parent == 0
    assert imp_s > 0 and init_s > 0
    # the import is the ring's earliest span and is over when init starts
    assert imp_start == min(row[3] for row in spans)
    assert imp_start + imp_s <= init_start + 1e-3


def _jit_spans(stage, parent=None):
    return [s for s in telemetry.finished_spans()
            if s.name == f"jit.{stage}"
            and (parent is None or s.parent_id == parent.span_id)]


def _jit_histogram(stage):
    """(count, seconds) of ``h2o3_span_seconds{span="jit.<stage>"}``."""
    h = telemetry.stage_seconds("jit.").get(f"jit.{stage}")
    return (h["count"], h["seconds"]) if h else (0, 0.0)


def test_reports_under_one_span_fold_into_one_child_counted_once():
    from h2o3_tpu.telemetry.spans import fold_span
    telemetry.clear_spans()
    with telemetry.span("t.fold") as parent:
        fold_span("t.fold.x", 10.0, 1.0)
        fold_span("t.fold.x", 11.5, 0.5)
        fold_span("t.fold.x", 9.0, 4.0)     # holds the two before it
        fold_span("t.fold.x", 14.0, 1.0)
        fold_span("t.fold.y", 12.0, 0.25)
        assert len(telemetry.finished_spans()) == 0     # not yet written
    x, y, top = telemetry.finished_spans()
    assert top is parent
    assert (x.name, x.parent_id, x.attrs) == ("t.fold.x", top.span_id,
                                              {"n": 4})
    assert (x.t_wall, x.duration_s) == (9.0, 5.0)
    assert (y.name, y.attrs, y.duration_s) == ("t.fold.y", {"n": 1}, 0.25)
    # under no span a report is a span of its own
    fold_span("t.fold.x", 20.0, 2.0)
    alone = telemetry.finished_spans()[-1]
    assert (alone.parent_id, alone.attrs, alone.duration_s) == (0, {"n": 1},
                                                                2.0)
    assert telemetry.stage_seconds("t.fold.")["t.fold.x"] == {
        "count": 2, "seconds": 7.0}


def test_a_folded_child_names_its_five_longest_labels():
    """``top`` (ISSUE 37): the up to five ``[label, seconds]`` of most
    seconds among the reports a folded child kept, a label's reports
    summed; a report inside a later one is counted in ``n`` and not in
    the seconds nor in ``top``; reports without a label leave no
    ``top``."""
    from h2o3_tpu.telemetry.spans import TOP_LABELS, fold_span
    telemetry.clear_spans()
    with telemetry.span("t.top"):
        fold_span("t.top.x", 10.0, 0.5, label="inner")
        fold_span("t.top.x", 9.0, 2.0, label="outer")      # holds inner
        for i in range(7):
            fold_span("t.top.x", 20.0 + i, 0.1 * (i + 1), label=f"f{i}")
        fold_span("t.top.x", 30.0, 0.25, label="f0")        # f0 again
        fold_span("t.top.x", 31.0, 4.0)                     # no label
        fold_span("t.top.y", 40.0, 1.0)
    x, y, _ = telemetry.finished_spans()
    assert x.attrs["n"] == 11
    assert x.duration_s == pytest.approx(2.0 + 2.8 + 0.25 + 4.0)
    top = x.attrs["top"]
    assert TOP_LABELS == 5 and len(top) == 5
    assert [name for name, _ in top] == ["outer", "f6", "f5", "f4", "f3"]
    assert [sec for _, sec in top] == pytest.approx([2.0, 0.7, 0.6, 0.5, 0.4])
    assert y.attrs == {"n": 1}
    # under no span a labelled report is a span of its own, and named
    fold_span("t.top.x", 50.0, 2.0, label="alone")
    assert telemetry.finished_spans()[-1].attrs == {
        "n": 1, "top": [["alone", 2.0]]}


def test_a_jit_cache_miss_names_the_program_it_built():
    """A miss under an open span leaves ``jit.trace``, ``jit.lower`` and
    ``jit.build`` whose ``top`` names the jitted function and whose
    seconds are the span's (ISSUE 37); JAX hands the name over with the
    event (``fun_name``)."""
    salt = float(time.time_ns() % 1_000_003)    # a program no run has cached

    @jax.jit
    def salted_miss(x):
        return jnp.cos(x) * salt - jnp.cumsum(x)

    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1e9)
    x = jnp.arange(8, dtype=jnp.float32)
    telemetry.clear_spans()
    try:
        with telemetry.span("t.miss") as parent:
            salted_miss(x)
        with telemetry.span("t.hit") as again:
            salted_miss(x)
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    for stage in ("trace", "lower", "build"):
        sp, = _jit_spans(stage, parent)
        names = [name for name, _ in sp.attrs["top"]]
        assert any("salted_miss" in name for name in names), (stage, names)
        assert len(names) <= 5
        assert sum(sec for _, sec in sp.attrs["top"]) == pytest.approx(
            sp.duration_s)
    built, = _jit_spans("build", parent)
    assert built.attrs["n"] == 1 and len(built.attrs["top"]) == 1
    assert not _jit_spans("load", parent)
    # a warm dispatch fires none of these events
    assert not [s for s in telemetry.finished_spans()
                if s.parent_id == again.span_id]


def test_a_cost_capture_after_the_dispatch_lowers_nothing_anew():
    """What gbm's ``chunk_lowering`` leans on, read on the v5e in PR 37
    (2 ms a chunk key, ``jit.lower`` ``n`` 1): asked for the ``Lowered``
    of a step it has just dispatched, by shape, JAX serves its own
    lowering cache; the capture leaves no ``jit.lower`` and no
    ``jit.build`` behind."""
    from functools import partial
    from h2o3_tpu.telemetry import costmodel

    @jax.jit
    def captured_step(x, y):
        return jnp.dot(x, y).sum()

    x = jnp.ones((32, 32), jnp.float32)
    telemetry.clear_spans()
    with telemetry.span("t.dispatch") as dispatch:
        captured_step(x, x)
    with telemetry.span("t.capture") as capture:
        shape = jax.ShapeDtypeStruct(x.shape, x.dtype)
        cost = costmodel.lowered_cost(
            partial(captured_step.lower, shape, shape))
    assert cost is not None and cost.flops > 0      # the CPU gives costs
    lowered, = _jit_spans("lower", dispatch)
    assert lowered.attrs["n"] == 1
    assert not _jit_spans("lower", capture)
    assert not _jit_spans("build", capture) and not _jit_spans("load",
                                                               capture)


def test_an_unjitted_scan_traces_on_every_call_a_jitted_one_once():
    def scan_sum(xs):
        def body(c, x):     # a new closure a call
            return jax.lax.add(c, x), c
        return jax.lax.scan(body, jnp.float32(0), xs)

    xs = jnp.arange(16, dtype=jnp.float32)
    scan_sum(xs)            # all but the scan itself is in JAX's caches now
    telemetry.clear_spans()
    _, seconds0 = _jit_histogram("trace")
    with telemetry.span("t.unjitted.once") as once:
        scan_sum(xs)
    with telemetry.span("t.unjitted.twice") as twice:
        scan_sum(xs)
        scan_sum(xs)
    for stage in ("trace", "lower"):
        one, = _jit_spans(stage, once)
        two, = _jit_spans(stage, twice)
        assert one.attrs["n"] >= 1 and two.attrs["n"] == 2 * one.attrs["n"]
    jitted = jax.jit(scan_sum)
    with telemetry.span("t.jitted.first") as first:
        jitted(xs)
    with telemetry.span("t.jitted.again") as again:
        jitted(xs)
    traced, = _jit_spans("trace", first)    # scan_sum and traces inside it
    assert traced.attrs["n"] >= 1
    lowered, = _jit_spans("lower", first)           # one program, by name
    assert lowered.attrs == {"n": 1,
                             "top": [["jit(scan_sum)", lowered.duration_s]]}
    assert not [s for s in telemetry.finished_spans()
                if s.parent_id == again.span_id]
    # what /metrics exports of the stage is the spans' seconds
    assert _jit_histogram("trace")[1] - seconds0 == pytest.approx(
        sum(s.duration_s for s in _jit_spans("trace")), abs=5e-6)


def test_a_trace_inside_a_trace_is_counted_but_its_seconds_are_not():
    salt = float(time.time_ns() % 1_000_003)

    @jax.jit
    def inner(x):
        return jnp.cumsum(x) * salt

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 2.0)

    telemetry.clear_spans()
    with telemetry.span("t.nested") as parent:
        t0 = time.perf_counter()
        outer(jnp.arange(8, dtype=jnp.float32))
        wall = time.perf_counter() - t0
    trace, = _jit_spans("trace", parent)
    assert trace.attrs["n"] >= 2           # outer and, inside it, inner
    stages = [s for s in telemetry.finished_spans()
              if s.name.startswith("jit.") and s.parent_id == parent.span_id]
    assert sum(s.duration_s for s in stages) <= wall


def test_a_persistent_cache_hit_is_a_load_not_a_build():
    assert jax.config.jax_compilation_cache_dir, "conftest sets the cache"
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    salt = float(time.time_ns() % 1_000_003)    # a program no run has cached

    @jax.jit
    def salted(x):
        return jnp.sin(x) * salt + jnp.cumsum(x)

    def events(parent):
        return {st: sum(s.attrs["n"] for s in _jit_spans(st, parent))
                for st in ("build", "load")}

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        x = jnp.arange(8, dtype=jnp.float32)
        compiles = telemetry.registry().value("h2o3_xla_compiles_total")
        with telemetry.span("t.cold") as cold:
            first = np.asarray(salted(x))
        jax.clear_caches()              # the jit cache, not the directory
        with telemetry.span("t.cached") as cached:
            again = np.asarray(salted(x))
    finally:
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          floor)
    np.testing.assert_array_equal(first, again)
    assert events(cold) == {"build": 1, "load": 0}
    assert events(cached) == {"build": 0, "load": 1}
    # JAX reports a retrieval without a name: the load takes the name of the
    # backend_compile_duration event that closes around it (ISSUE 37)
    for stage, parent in (("build", cold), ("load", cached)):
        sp, = _jit_spans(stage, parent)
        (name, seconds), = sp.attrs["top"]
        assert "salted" in name and seconds == sp.duration_s
    # the old counter counts both, as its help text now says
    assert telemetry.registry().value("h2o3_xla_compiles_total") \
        - compiles == 2


def _host_event_names(trace_dir):
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, f"no trace under {trace_dir}"
    return {e.name for plane in ProfileData.from_file(found[-1]).planes
            if plane.name == "/host:CPU"
            for line in plane.lines for e in line.events}


def _harness_trace(path):
    """As ``benchmark/run.py:start_trace`` starts its trace."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)


@pytest.mark.parametrize("how", ["harness_options", "telemetry_profile"])
def test_a_profiler_trace_of_a_predict_holds_the_programs_spans(
        how, frame, warm_train, tmp_path):
    est, _ = warm_train
    if how == "harness_options":
        _harness_trace(str(tmp_path))
        try:
            est.model.predict(frame)
        finally:
            jax.profiler.stop_trace()
    else:
        with telemetry.profile("predict", trace_dir=str(tmp_path),
                               log=lambda *a: None):
            est.model.predict(frame)
    names = _host_event_names(str(tmp_path))
    assert {"score.predict", "score.adapt", "score.dispatch", "score.fetch",
            "score.frame"} <= names
    # the spans, not one event per Python frame
    assert not any(n.startswith("$") for n in names)


def test_telemetry_off_leaves_ring_histograms_and_trace_empty(
        frame, warm_train, tmp_path):
    est, _ = warm_train

    def jit_events():
        return {**telemetry.stage_seconds("jit."),
                **telemetry.stage_seconds("boot."),
                **telemetry.stage_seconds("train.")}

    telemetry.clear_spans()
    before = jit_events()
    telemetry.set_enabled(False)
    try:
        _harness_trace(str(tmp_path))
        try:
            with telemetry.span("t.off") as sp:
                est.model.predict(frame)
            # what ISSUE 37 added: the boot spans, train.init, a labelled
            # report and the import span's own call
            from h2o3_tpu.parallel.mesh import current_mesh, set_mesh
            from h2o3_tpu.telemetry.spans import fold_span
            mesh = current_mesh()
            try:
                h2o.init(n_data=mesh.shape["data"],
                         n_model=mesh.shape["model"])
            finally:
                set_mesh(mesh)
            h2o._record_import_span()
            fold_span("jit.build", time.time(), 1.0, label="t.off.program")
            H2OGradientBoostingEstimator(
                ntrees=2, max_depth=2, seed=1, packed_codes=True).train(
                    y="y", training_frame=frame)
        finally:
            jax.profiler.stop_trace()
        assert sp is None
    finally:
        telemetry.set_enabled(True)
    assert telemetry.finished_spans() == []
    assert jit_events() == before
    names = _host_event_names(str(tmp_path))
    assert not any(n.startswith(("score.", "jit.", "t.off", "boot.",
                                 "train.")) for n in names)
