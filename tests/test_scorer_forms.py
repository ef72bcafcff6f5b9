"""``tree.predict_raw_stacked``: the scorer descends a tree by predicates on
whole columns, selected on the node id's bits, where it gathered five things
a level by row (PR 32). Its [rows, T] contributions have to equal the
gather form's bit for bit at every depth on both sides of the rule
(``tree.scorer_node_form``: by the tree's size and by the rows), and a
model's prediction frame with them. Since PR 38 the scan is one jitted
program (``tree._score_stack``) keyed on shapes, depth and form, with the
tables as arguments: a warm predict compiles and traces nothing, a second
model of the same shape runs the first's program, and the form stays part
of the key.

``reference`` is the gather form as the scorer had it before PR 32, kept
here so that the comparison does not lean on the code under test.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import telemetry
from h2o3_tpu.models import tree
from h2o3_tpu.models.tree import (SCORER_PREDICATE_MAX, SCORER_ROWS_PER_NODE,
                                  predict_raw_stacked, scorer_node_form)

F = 7
# the shallowest complete tree on the gather side of the rule at any rows
GATHER_DEPTH = (SCORER_PREDICATE_MAX + 2).bit_length() - 1


def _n_rows(depth, form):
    """A row count, no multiple of 128, that puts a tree of ``depth`` on
    ``form``'s side of the rule."""
    fewest = SCORER_ROWS_PER_NODE * (2 ** (depth + 1) - 1)
    if depth >= GATHER_DEPTH:
        assert form == "gather"
        return 1000
    rows = fewest + 77 if form == "predicate" else min(fewest - 1, 1000)
    return rows + (rows % 128 == 0)


def reference(X, feat, thr, na_left, is_split, value, max_depth):
    rows = X.shape[0]

    def one_tree(carry, t):
        nid = jnp.zeros(rows, jnp.int32)
        for _ in range(max_depth):
            f = feat[t][nid]
            s = is_split[t][nid]
            th = thr[t][nid]
            nl = na_left[t][nid]
            x = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None],
                                    axis=1)[:, 0]
            go_right = jnp.where(jnp.isnan(x), ~nl, x >= th)
            nid = jnp.where(s, 2 * nid + 1 + go_right.astype(jnp.int32), nid)
        return carry, value[t][nid]

    _, contribs = jax.lax.scan(one_tree, None, jnp.arange(feat.shape[0]))
    return contribs.T


def _stack(rng, trees, depth, stop=0.15):
    """Random complete heaps as the trainers export them: a node splits
    only under a splitting parent, ``feat`` is -1 where nothing splits,
    thresholds carry -0.0 and both infinities."""
    M, inner = 2 ** (depth + 1) - 1, 2 ** depth - 1
    is_split = np.zeros((trees, M), bool)
    is_split[:, :inner] = rng.random((trees, inner)) > stop
    for m in range(1, inner):
        is_split[:, m] &= is_split[:, (m - 1) // 2]
    feat = np.where(is_split, rng.integers(0, F, (trees, M)), -1)
    thr = rng.standard_normal((trees, M)).astype(np.float32)
    special = rng.random((trees, M))
    thr[special < 0.06] = -0.0
    thr[special < 0.04] = np.inf
    thr[special < 0.02] = -np.inf
    return {"feat": feat.astype(np.int32), "thr": thr,
            "na_left": rng.random((trees, M)) > 0.5, "is_split": is_split,
            "value": rng.standard_normal((trees, M)).astype(np.float32)}


def _rows(rng, rows):
    X = rng.standard_normal((rows, F)).astype(np.float32)
    X[rng.random(X.shape) < 0.1] = np.nan
    X[rng.random(X.shape) < 0.05] = 0.0       # meets thr = -0.0
    X[rng.random(X.shape) < 0.02] = np.inf
    return X


def _both(X, stack, depth):
    args = [jnp.asarray(a) for a in (X, *stack.values())]
    return (np.asarray(predict_raw_stacked(*args, depth)).view(np.int32),
            np.asarray(reference(*args, depth)).view(np.int32))


@pytest.mark.parametrize("trees", [1, 50])
@pytest.mark.parametrize("depth,form", [
    (1, "predicate"), (2, "predicate"), (5, "predicate"), (6, "predicate"),
    (8, "predicate"), (1, "gather"), (2, "gather"), (5, "gather"),
    (6, "gather"), (8, "gather"), (GATHER_DEPTH, "gather")])
def test_contributions_equal_the_gather_forms_bits(depth, form, trees):
    if depth >= 8:
        trees = min(trees, 3)                 # a second on the CPU, not ten
    rows = _n_rows(depth, form)
    assert scorer_node_form(2 ** (depth + 1) - 1, rows) == form
    rng = np.random.default_rng(100 * depth + trees)
    got, want = _both(_rows(rng, rows), _stack(rng, trees, depth), depth)
    assert got.shape == (rows, trees)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("depth", [2, 6, 8])
@pytest.mark.parametrize("case", ["root_is_a_leaf", "stops_at_level_1",
                                  "na_left", "na_right", "thr_plus_inf",
                                  "thr_minus_inf", "thr_minus_zero"])
def test_the_edges_of_the_routing(case, depth):
    rng = np.random.default_rng(depth)
    stack = _stack(rng, 4, depth, stop=0.0)
    X = _rows(rng, _n_rows(depth, "predicate"))
    M = stack["feat"].shape[1]
    if case == "root_is_a_leaf":
        stack["is_split"][:] = False
        stack["feat"][:] = -1
    elif case == "stops_at_level_1":
        stack["is_split"][:, 1:] = False
        stack["feat"][:, 1:] = -1
    elif case in ("na_left", "na_right"):
        stack["na_left"][:] = case == "na_left"
        X[::2] = np.nan                       # whole rows of NA
    else:
        stack["thr"][:] = {"thr_plus_inf": np.inf, "thr_minus_inf": -np.inf,
                           "thr_minus_zero": -0.0}[case]
    got, want = _both(X, stack, depth)
    np.testing.assert_array_equal(got, want)
    if case == "root_is_a_leaf":
        root = stack["value"][:, 0].view(np.int32)
        assert (got == root[None, :]).all()
    if case == "na_right":                    # an NA row ends rightmost
        last = stack["value"][:, M - 1].view(np.int32)
        assert (got[::2] == last[None, :]).all()


@pytest.mark.parametrize("M,rows,form", [
    (63, SCORER_ROWS_PER_NODE * 63, "predicate"),
    (63, SCORER_ROWS_PER_NODE * 63 - 1, "gather"),
    (63, 64, "gather"),                       # a serving bucket
    (63, 500_000, "predicate"),               # the score cell
    (127, 10_002_432, "predicate"),
    (SCORER_PREDICATE_MAX, 500_000, "predicate"),
    (SCORER_PREDICATE_MAX + 1, 10_002_432, "gather"),
    (2 ** 17 - 1, 10_002_432, "gather")])     # DRF's depth-16 heaps
def test_the_form_follows_the_static_shapes(M, rows, form):
    assert scorer_node_form(M, rows) == form
    depth = (M + 1).bit_length() - 2
    if M != 2 ** (depth + 1) - 1:
        return                                # the rule alone: no such heap
    sds = jax.ShapeDtypeStruct
    tm = (2, M)
    jaxpr = str(jax.make_jaxpr(
        lambda *a: predict_raw_stacked(*a, depth))(
            sds((rows, F), jnp.float32), sds(tm, jnp.int32),
            sds(tm, jnp.float32), sds(tm, jnp.bool_), sds(tm, jnp.bool_),
            sds(tm, jnp.float32)))
    assert ("gather" in jaxpr) == (form == "gather")


def _frame():
    rng = np.random.default_rng(32)
    n = 9000                                  # over 8 rows a node at depth 8
    X = rng.standard_normal((n, 6)).astype(np.float32)
    X[rng.random(X.shape) < 0.03] = np.nan
    y = (np.nan_to_num(X[:, 0]) - 0.7 * np.nan_to_num(X[:, 1] * X[:, 2])
         + 0.3 * rng.standard_normal(n)) > 0
    cols = {f"x{i}": X[:, i] for i in range(X.shape[1])}
    return h2o.Frame.from_numpy({**cols, "resp": np.where(y, "a", "b")})


def _estimator(algo, depth):
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator
    if algo == "gbm":
        return H2OGradientBoostingEstimator(
            ntrees=5, max_depth=depth, seed=32, distribution="bernoulli",
            min_rows=2)
    if algo == "xgboost":
        return H2OXGBoostEstimator(
            ntrees=5, max_depth=depth, seed=32, distribution="bernoulli",
            tree_method="hist", max_bins=64)
    if algo == "gbm-sets":        # packed: enums split on sets of levels
        return H2OGradientBoostingEstimator(
            ntrees=5, max_depth=depth, seed=32, min_rows=5, packed_codes=True)
    return H2ORandomForestEstimator(ntrees=5, max_depth=depth, seed=32)


@pytest.mark.parametrize("algo,depth", [("gbm", 5), ("xgboost", 6),
                                        ("drf", 8)])
def test_a_models_prediction_frame_is_the_gather_forms(monkeypatch, algo,
                                                       depth):
    fr = _frame()
    est = _estimator(algo, depth)
    est.train(y="resp", training_frame=fr)
    X = jnp.zeros((fr.nrow, 6))
    assert est.model._score_attrs(X) == {"node_form": "predicate",
                                         "n_nodes": 2 ** (depth + 1) - 1}
    pred = est.model.predict(fr)
    monkeypatch.setattr(tree, "SCORER_PREDICATE_MAX", 0)
    assert est.model._score_attrs(X)["node_form"] == "gather"
    want = est.model.predict(fr)
    assert pred.names == want.names
    for name in pred.names:
        a, b = (np.asarray(p.vec(name).data) for p in (pred, want))
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(a.view(np.int32), b.view(np.int32),
                                      err_msg=name)


@pytest.fixture
def spans_on():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.install()
    yield
    telemetry.set_enabled(was)


def _set_frame():
    """An enum column whose levels' effects are not monotone in the level
    index beside a numeric one, as ``tests/test_set_splits.py`` has them:
    a packed GBM splits the enum on sets of levels."""
    from h2o3_tpu.frame.vec import T_ENUM, Vec
    rng = np.random.default_rng(38)
    n = 9000
    c = rng.integers(0, 40, n)
    x = rng.standard_normal(n).astype(np.float32)
    y = (rng.standard_normal(40)[c] + 0.5 * x
         + 0.3 * rng.standard_normal(n)) > 0
    return h2o.Frame(["c", "x", "resp"], [
        Vec.from_numpy(c, T_ENUM, [f"k{i}" for i in range(40)]),
        Vec.from_numpy(x),
        Vec.from_numpy(y.astype(np.int32), T_ENUM, ["a", "b"])])


def _tables(model):
    return {k: getattr(model, k) for k in (
        "_feat", "_thr", "_na_left", "_is_split", "_value", "_cat_set",
        "_is_set") if getattr(model, k, None) is not None}


def _predict_counted(model, fr):
    """(the prediction frame, executables compiled or loaded meanwhile,
    the ``jit.*`` spans the predict left)."""
    compiles = telemetry.registry().value("h2o3_xla_compiles_total")
    telemetry.clear_spans()
    pred = model.predict(fr)
    return (pred,
            telemetry.registry().value("h2o3_xla_compiles_total") - compiles,
            [s.name for s in telemetry.finished_spans()
             if s.name.startswith("jit.")])


@pytest.mark.parametrize("algo,depth", [("gbm", 5), ("xgboost", 6),
                                        ("drf", 8), ("gbm-sets", 4)])
def test_a_warm_predict_compiles_and_traces_nothing(spans_on, algo, depth):
    """The scorer's program is JAX's to cache: the second predict of a
    frame is a hit (no executable, no ``jit.trace``), and so is the FIRST
    predict of a second model whose tables have the first's shapes, since
    the tables are arguments of the program and not constants in it."""
    def trained(second):
        # a second model of the same shapes and other numbers: another
        # step size where trees are boosted, another bootstrap in a forest
        est = _estimator(algo, depth)
        if second:
            est.params.update({"seed": 33} if algo == "drf" else
                              {"learn_rate": 0.2})
        est.train(y="resp", training_frame=fr)
        return est.model

    fr = _set_frame() if algo == "gbm-sets" else _frame()
    one = trained(False)
    if algo == "gbm-sets":
        assert int(np.asarray(one._is_set).sum()) > 0
    first, _, _ = _predict_counted(one, fr)       # the shape's first call
    again, compiles, jit = _predict_counted(one, fr)
    assert (compiles, jit) == (0, [])
    other = trained(True)
    t1, t2 = _tables(one), _tables(other)
    assert {k: (v.shape, v.dtype) for k, v in t1.items()} == {
        k: (v.shape, v.dtype) for k, v in t2.items()}
    assert not np.array_equal(np.asarray(t1["_value"]),
                              np.asarray(t2["_value"]))
    theirs, compiles, jit = _predict_counted(other, fr)
    assert (compiles, jit) == (0, [])
    # one program, each model's own numbers through it
    for a, b, same in ((first, again, True), (first, theirs, False)):
        pa, pb = (np.asarray(p.vec(p.names[-1]).data)[:fr.nrow]
                  for p in (a, b))
        assert np.array_equal(pa, pb) == same


def test_the_form_is_part_of_the_programs_key(spans_on, monkeypatch):
    """The rule is read outside the jitted program on every call and
    handed in as a static argument: with the bound at 0 between two calls
    of ONE shape the second is a program of its own, which gathers, and
    not the predicates the shape first compiled; the bits are equal."""
    depth = 5
    rows = _n_rows(depth, "predicate")
    rng = np.random.default_rng(38)
    args = [jnp.asarray(a) for a in (_rows(rng, rows),
                                     *_stack(rng, 3, depth).values())]

    def program():
        return str(jax.make_jaxpr(
            lambda *a: predict_raw_stacked(*a, depth))(*args))

    def run(name):
        telemetry.clear_spans()
        with telemetry.span(name):
            out = np.asarray(predict_raw_stacked(*args, depth))
        return out.view(np.int32), [s.name for s in telemetry.finished_spans()
                                    if s.name == "jit.trace"]

    by_predicates, traced = run("t.predicates")
    assert traced and "gather" not in program()
    again, traced = run("t.predicates.again")
    assert not traced
    np.testing.assert_array_equal(again, by_predicates)
    monkeypatch.setattr(tree, "SCORER_PREDICATE_MAX", 0)
    by_gathers, traced = run("t.gathers")
    assert traced and "gather" in program()
    np.testing.assert_array_equal(by_gathers, by_predicates)
