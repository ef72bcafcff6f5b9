"""Category-set splits (ISSUE 33): GBM on enum columns held to the plain
reference (``benchmark/harness/reference/gbm_enum.py``) on the CPU, and a
set-split model through everything that reads its trees.

The frame is the benchmark's airline-shaped table at 4,096 rows: enum
columns of 12, 31, 7, 22, 300 and 300 levels and two numeric ones. The
trainer runs the packed path (scatter reference, or the Pallas kernels
interpreted), float32 histograms (``histogram_precision='auto'`` under 2**18
rows), so the program's sums are the reference's to rounding.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import system  # noqa: E402
from harness.checks import gbm_enum_train_follow as check  # noqa: E402
from harness.generators import airline_shaped  # noqa: E402
from harness.reference import gbm_enum as ref  # noqa: E402
from harness.runners import train_enum  # noqa: E402

import h2o3_tpu as h2o  # noqa: E402
from h2o3_tpu.models import tree as T  # noqa: E402
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator  # noqa: E402

ROWS, SEED = 4096, 33
# float32 program against the float32-exact reference; the widest sound
# reading of the cases below is beside each (this file, CPU)
LIMITS = {"cover_gap": 0.0, "edge_gap": 0.0,
          "node_value_gap": 5e-6,      # 4.6e-7
          "leaf_gap": 5e-6,            # 1.3e-6
          "logloss_gap": 1e-6,         # 1.2e-7
          # depth 3: nodes of hundreds of rows, the order of an enum's bins
          # is the reference's but for float32 rounding of G/H
          "split_regret": 1e-4}
# depth 10 at 4,096 rows: nodes of 20-100 rows in which many of 300 levels
# have ONE row and the same G/H; float32 and float64 break those ties
# apart differently and ``min_rows`` then cuts inside a tie group
DEEP_REGRET = 0.25


def cell_for(depth, ntrees=3, **params):
    with open(os.path.join(BENCH, "configs", "gbm_perf_airline.json")) as f:
        config = json.load(f)
    config["params"].update({"ntrees": ntrees, "max_depth": depth,
                             "packed_codes": True, **params})
    config["data"]["rows"] = ROWS
    return {"name": "airline_gbm.train", "config": config,
            "check": {"follow_trees": list(range(ntrees))}}


@pytest.fixture
def holes(monkeypatch):
    """3% of every column missing, in the generator both sides draw from."""
    real = airline_shaped.make

    def with_nans(seed, rows, padded, features=8, part=0):
        X, y = real(seed, rows, padded, features, part=part)
        gone = np.random.default_rng(seed).random(X.shape) < 0.03
        return jnp.where(jnp.asarray(gone), jnp.nan, X), y
    monkeypatch.setattr(airline_shaped, "make", with_nans)


def train(cell, frame=None):
    h2o.init()
    frame = frame or train_enum.build_frame(cell["config"], SEED)
    est = system.estimator(cell["config"])
    est.train(y=cell["config"]["data"]["response"], training_frame=frame)
    m = est.model
    pc = m.output["packed_codes"]
    assert (pc["enabled"], pc["lane_layout"], pc["lanes"], pc["W"],
            pc["set_features"]) == (True, "ragged", 896, 304, 6), pc
    state = train_enum.State(cell, frame, True)
    state.model = m
    return m, frame, train_enum.product(state)


def over(numbers, **limits):
    lim = {**LIMITS, **limits}
    return {n: v for n, v in numbers.items() if not v <= lim[n]}


# ------------------------------------------------ the split search alone


def _node_hist(rng, B, with_na):
    """One node's (g, h, w) by level of one enum column: some levels empty."""
    w = rng.integers(0, 9, B).astype(np.float64) * (rng.random(B) < 0.8)
    g = rng.normal(size=B) * w
    h = (0.1 + rng.random(B)) * w
    na = np.array([rng.normal(), 0.5, 3.0]) if with_na else np.zeros(3)
    return g, h, w, na


@pytest.mark.parametrize("seed", range(6))
@pytest.mark.parametrize("with_na", [False, True], ids=["dense", "na"])
def test_prefix_scan_finds_the_best_of_all_subsets(seed, with_na):
    """|P| <= 10: the best gain over the prefixes of the G/H order equals
    the best over all 2^(|P|-1) - 1 two-way partitions, in the reference
    and in the program's ``_find_splits`` on the same histogram."""
    rng = np.random.default_rng(100 * seed + with_na)
    B = int(rng.integers(3, 11))
    g, h, w, na = _node_hist(rng, B, with_na)
    if (w > 0).sum() < 2:
        w[:2], g[:2], h[:2] = 3.0, [1.0, -1.0], 1.0
    brute = ref.brute_force_best(g, h, w, na, min_rows=2.0)
    lay = ref.layout(["enum"], [B], 20)
    hist = np.zeros((1, 3, lay.lanes))
    hist[0, :, :B], hist[0, :, B] = np.stack([g, h, w]), na
    gain, _, pick = ref.best_splits(hist, lay, min_rows=2.0)
    assert gain[0] == pytest.approx(brute, rel=1e-9)
    # the unconstrained partition is never better than a prefix either
    assert ref.best_splits(hist, lay, 0.0)[0][0] == pytest.approx(
        ref.brute_force_best(g, h, w, na, 0.0), rel=1e-9)
    # the program: one node, one set feature of B bins and the NA lane
    cfg = T.TreeConfig(max_depth=1, n_bins=B, n_features=1, min_rows=2.0,
                       min_split_improvement=0.0, set_feats=(True,),
                       bin_counts=(B,), lane_widths=(B + 1,))
    trip = tuple(jnp.asarray(np.concatenate([a, [n]])[None, None, :],
                             jnp.float32)
                 for a, n in zip((g, h, w), na))
    out = T._find_splits(trip, cfg, jnp.ones(1, bool))
    if np.isfinite(brute):
        assert float(out[0][0]) == pytest.approx(brute, rel=2e-4, abs=1e-5)
        left = np.asarray(out[11][0])
        want = pick[0][2]
        present = w > 0
        if not np.array_equal(left[present], want[present]):
            # another set: only at an exact-gain tie
            assert ref.best_splits(hist, lay, 2.0)[0][0] == pytest.approx(
                float(out[0][0]), rel=2e-4)
        # a level no row has goes where NA goes
        assert (left[~present] == bool(out[3][0])).all()
    else:
        assert float(out[0][0]) < -1e29


def test_an_ordinal_scan_loses_to_the_set_scan():
    """Effects that zigzag over the level index: every threshold on the
    index splits badly, the G/H order splits them apart."""
    B = 12
    w = np.full(B, 50.0)
    g = np.where(np.arange(B) % 2 == 0, -20.0, 20.0)
    h = np.full(B, 12.0)
    lay = ref.layout(["enum"], [B], 20)
    hist = np.zeros((1, 3, lay.lanes))
    hist[0, :, :B] = np.stack([g, h, w])
    sets = ref.best_splits(hist, lay, 10.0)[0][0]
    ordinal = ref.best_splits(hist, lay, 10.0, ordinal=True)[0][0]
    assert sets > 10 * ordinal > 0


# ------------------------------------- the program against the reference


@pytest.mark.parametrize("depth,na", [(3, False), (3, True), (10, False),
                                      (10, True)],
                         ids=["d3", "d3-na", "d10", "d10-na"])
def test_program_follows_the_reference(request, depth, na):
    if na:
        request.getfixturevalue("holes")
    cell = cell_for(depth)
    m, _, product = train(cell)
    numbers = check.run(cell, product, SEED)
    regret = LIMITS["split_regret"] if depth == 3 else DEEP_REGRET
    assert not over(numbers, split_regret=regret), numbers
    model = product["model"]
    assert model["is_set"].sum() > (10 if depth == 3 else 200)
    # a set node has a set and no threshold, a threshold node the reverse
    assert np.isnan(model["thr"][model["is_set"]]).all()
    assert model["cat_set"][model["is_set"]].any(axis=-1).all()
    assert not model["cat_set"][~model["is_set"]].any()
    assert np.isfinite(model["thr"][model["is_split"] & ~model["is_set"]]).all()
    assert m.output["categorical_encoding"] == {
        "requested": "auto", "applied": "enum", "enum_features": 6,
        "set_features": 6, "honoured": True}


def test_same_sets_as_the_reference_up_to_gain_ties():
    """Depth 3, tree 0: at every node the reference's own search picks the
    program's column and set, or one of the same exact gain."""
    cell = cell_for(3, ntrees=1)
    _, _, product = train(cell)
    config = cell["config"]
    Xb, yb, wb = ref.make_rows(airline_shaped, SEED, product["rows"],
                               product["padded"], 8)
    lay = check.data_layout(config)
    model = product["model"]
    codes = ref.digitize(Xb, ref.uniform_edges(Xb, lay), lay)
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    ghw = ref.grad_hess(jnp.full(yb.shape, f0, jnp.float32), yb, wb)
    tree = {k: model[k][0] for k in check.TREE_KEYS + check.SET_KEYS}
    packed, thr, _, words = ref.pack_tree_table(
        {k: v[None] for k, v in tree.items()})
    nid = jnp.zeros(codes.shape[:2], jnp.int32)
    same = ties = 0
    for d in range(3):
        N, lo = 2 ** d, 2 ** d - 1
        hist = np.asarray(ref.level_hist(codes, nid, ghw, lo, N, lay.lanes))
        gain, _, pick = ref.best_splits(hist, lay, 10.0)
        for n in range(N):
            i = lo + n
            if not tree["is_split"][i]:
                continue
            f, _, left = pick[n]
            mine = T.set_levels(tree["cat_set"][i], len(left))
            present = hist[n, 2, lay.offsets[f]:lay.offsets[f] + len(left)] > 0
            if (int(tree["feat"][i]) == f and tree["is_set"][i]
                    and np.array_equal(mine[present], left[present])):
                same += 1
            else:
                ties += 1
        nid = ref.route_rows(Xb, nid, packed[0], thr[0], words[0], d)
    st = ref.follow_tree(Xb, codes, ghw, tree, 3, lay, 10.0, 1e-5)
    # where the pick differs the exact gains agree
    assert np.nanmax(st["best_gain"][:7] - st["own_gain"][:7]) <= 1e-4 * \
        np.nanmax(st["best_gain"][:7])
    assert same >= 5 and same + ties == int(tree["is_split"].sum())


@pytest.mark.parametrize("control,must_fail", [
    ("ordinal_sets", "split_regret"), ("half_batch", "cover_gap"),
    ("bin_off_by_one", "split_regret"), ("last_step_dropped", "logloss_gap"),
    ("fp8", "node_value_gap")])
def test_planted_faults_are_refused(control, must_fail):
    cell = cell_for(3)
    numbers = check.run(cell, train(cell)[2], SEED, control=control)
    assert must_fail in over(numbers), numbers
    if control == "ordinal_sets":
        assert numbers["split_regret"] > 0.3       # 300 airports, ordinal


def test_label_encoder_keeps_thresholds_and_fits_worse():
    cell = cell_for(3)
    frame = train_enum.build_frame(cell["config"], SEED)
    by_set, _, _ = train(cell, frame)
    est = H2OGradientBoostingEstimator(**{
        **cell["config"]["params"], "categorical_encoding": "label_encoder"})
    est.train(y="dep_delayed_15min", training_frame=frame)
    m = est.model
    # 300 ordinal bins pass the uniform layout's 254: the adaptive grower
    assert m._cat_set is None and not m.output["packed_codes"]["enabled"]
    assert m.output["categorical_encoding"]["applied"] == "ordinal"
    assert m.output["categorical_encoding"]["honoured"] is True
    assert float(by_set.training_metrics.logloss) < float(
        m.training_metrics.logloss) - 0.005


@pytest.mark.parametrize("how,params,applied", [
    ("other-scheme", {"categorical_encoding": "one_hot_explicit"}, "enum"),
    ("off-the-packed-path", {"packed_codes": False}, "ordinal")])
def test_an_encoding_not_honoured_is_reported(how, params, applied):
    cell = cell_for(2, ntrees=1, **params)
    h2o.init()
    frame = train_enum.build_frame(cell["config"], SEED)
    est = system.estimator(cell["config"])
    est.train(y="dep_delayed_15min", training_frame=frame)
    rec = est.model.output["categorical_encoding"]
    assert rec["honoured"] is False and rec["applied"] == applied, rec
    assert rec["note"]


# -------------------------------- the kernels against the scatter reference


def test_interpreted_kernels_match_the_scatter_reference():
    """The Pallas level and route kernels on GLOBAL lanes, interpreted,
    against ``binned_level_xla`` on local codes: the same routing and the
    same histogram, lane for lane."""
    from h2o3_tpu.ops import hist_adaptive as ha
    from h2o3_tpu.ops.binning import lane_widths
    rng = np.random.default_rng(4)
    bins = (12, 31, 7, 100, 22, 300, 300, 100)
    widths, rows, n_prev = lane_widths(bins), 1024, 4
    W, off = max(widths), ha.lane_offsets(widths)
    rm = np.stack([rng.integers(0, b, rows) for b in bins], axis=1)
    na = rng.random(rm.shape) < 0.05
    rm = np.where(na, np.asarray(widths) - 1, rm).astype(np.int16)
    ct = jnp.asarray((rm + np.asarray(off, np.int16)).T)
    ghw = jnp.asarray(rng.normal(size=(3, rows)).astype(np.float32))
    nid = jnp.asarray(rng.integers(3, 7, rows).astype(np.int32))   # level 2
    feat = np.array([5, 3, 0, 6])
    left = rng.random((n_prev, W)) < 0.5
    tables = (jnp.asarray(feat, jnp.float32),
              jnp.asarray(np.asarray(off)[feat], jnp.float32),
              # ``can``: the left child built, the right one, no split
              jnp.zeros(n_prev), jnp.asarray([1.0, 2.0, 0.0, 1.0]),
              jnp.asarray(left, jnp.float32))
    want_nid, want = ha.binned_level_xla(jnp.asarray(rm), nid, ghw, tables,
                                         n_prev, 7, W, widths)
    got_nid, got = ha.binned_level_tpu_t(ct, nid, ghw, tables, n_prev, 7,
                                         W, tile=512, interpret=True,
                                         mxu_dtype=jnp.float32, widths=widths)
    assert np.array_equal(np.asarray(got_nid), np.asarray(want_nid))
    # one child a parent, on the parents' rows; nothing where none splits
    assert got.shape == want.shape == (3, n_prev, sum(widths))
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    assert not np.asarray(want[:, 2]).any() and np.asarray(want[2, 1]).any()
    routed = ha.binned_route_only_tpu_t(ct, nid, tables, n_prev, 7, W,
                                        tile=512, interpret=True)
    assert np.array_equal(np.asarray(routed), np.asarray(want_nid))
    # a row at a split node went left iff its code is in the node's set
    node = np.asarray(nid) - 3
    code = rm[np.arange(rows), feat[node]]
    went_left = np.asarray(want_nid) == 2 * np.asarray(nid) + 1
    moved = np.asarray(want_nid) != np.asarray(nid)
    assert np.array_equal(moved, node != 2)
    assert np.array_equal(went_left[moved], left[node, code][moved])


@pytest.mark.parametrize("method", ["scatter", "pallas"],
                         ids=["scatter", "kernel"])
def test_smaller_child_levels_grow_the_direct_formulations_tree(monkeypatch,
                                                                method):
    """Exact arithmetic on a ragged layout with routing by set: integer g,
    h, w make every sum exact, so accumulating each parent's smaller child
    and deriving its sibling grows, bit for bit, the tree (sets included)
    of the formulation that builds every node directly
    (tests/_direct_levels.py); NAs, depth 5, parents that do not split."""
    from _direct_levels import assert_same_tree, grow_direct
    from h2o3_tpu.ops import hist_adaptive as ha
    from h2o3_tpu.ops.binning import lane_widths
    monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(34)
    bins, rows, depth = (5, 12, 3, 20), 1800, 5
    widths = lane_widths(bins)
    rm = np.stack([rng.integers(0, b, rows) for b in bins], axis=1)
    rm = np.where(rng.random(rm.shape) < 0.06, np.asarray(widths) - 1,
                  rm).astype(np.int16)
    g, h, w = (jnp.asarray(rng.integers(lo, hi, rows).astype(np.float32))
               for lo, hi in ((-4, 5), (1, 4), (1, 3)))
    cfg = T.TreeConfig(max_depth=depth, n_bins=max(bins), n_features=4,
                       min_rows=200.0, min_split_improvement=0.0,
                       hist_method=method, histogram_precision="bfloat16",
                       set_feats=(True, True, False, True), bin_counts=bins,
                       lane_widths=widths)
    col_mask = jnp.ones(4, bool)
    ct = jnp.asarray((rm + np.asarray(ha.lane_offsets(widths), np.int16)).T)
    tree, nid = T.grow_tree_binned(jnp.asarray(rm), g, h, w, cfg, col_mask,
                                   ct=ct)
    want, want_nid = grow_direct(jnp.asarray(rm), g, h, w, cfg, col_mask)
    assert_same_tree(tree, nid, want, want_nid, depth)
    assert np.asarray(tree["set_split"])[np.asarray(tree["is_split"])].any()


# ---------------------------------------- everything that reads the trees


@pytest.fixture(scope="module")
def deep():
    """One depth-10, 4-tree set-split model, its frame and raw matrix."""
    cell = cell_for(10, ntrees=4)
    m, frame, product = train(cell)
    from h2o3_tpu.models.model_base import adapt_test_matrix
    return cell, m, frame, adapt_test_matrix(m, frame), product


def _contribs(m, X, form):
    """The scorer on one side of its rule, whatever the shapes say."""
    fn = {"predicate": T._score_tree_predicates,
          "gather": T._score_tree_gather}[form]
    Xs = X.T if form == "predicate" else X
    return np.stack([np.asarray(fn(
        Xs, m._feat[t], m._thr[t], m._na_left[t], m._is_split[t],
        m._value[t], m.max_depth, cat_set=m._cat_set[t], is_set=m._is_set[t]))
        for t in range(m._feat.shape[0])], axis=1)


def test_scorer_forms_agree_with_the_train_margin_and_the_reference(deep):
    cell, m, frame, X, product = deep
    assert T.scorer_node_form(2047, X.shape[0]) == "gather"
    assert T.scorer_node_form(2047, 8 * 2047) == "predicate"
    by_gather = _contribs(m, X, "gather")
    by_predicate = _contribs(m, X, "predicate")
    assert np.array_equal(by_gather, by_predicate)
    # the reference's scorer over the same rows
    Xb, yb, wb = ref.make_rows(airline_shaped, SEED, product["rows"],
                               product["padded"], 8)
    packed, thr, value, words = ref.pack_tree_table(product["model"])
    f0 = float(np.asarray(m.f0).reshape(-1)[0])
    margins, lls = ref.score(Xb, yb, wb, packed, thr, value, words, f0, 10)
    mine = f0 + by_gather.sum(axis=1)
    want = np.asarray(margins[-1]).reshape(-1)[:len(mine)]
    assert np.allclose(mine[:ROWS], want[:ROWS], atol=2e-6)
    assert float(lls[-1]) == pytest.approx(
        float(m.training_metrics.logloss), rel=1e-6)
    # values the training table never had: an unseen level, NA, a negative
    odd = np.asarray(X[:6]).copy()
    odd[0, 5], odd[1, 5], odd[2, 6], odd[3, 4] = 5000.0, np.nan, -3.0, 299.0
    odd = jnp.asarray(odd)
    assert np.array_equal(_contribs(m, odd, "gather"),
                          _contribs(m, odd, "predicate"))


def test_predict_staged_and_leaf_paths(deep):
    _, m, frame, X, _ = deep
    pred = m.predict(frame)
    p1 = np.asarray(pred.vecs[-1].to_numpy())
    f0 = float(np.asarray(m.f0).reshape(-1)[0])
    margin = f0 + _contribs(m, X, "gather").sum(axis=1)[:ROWS]
    assert np.allclose(p1, 1 / (1 + np.exp(-margin)), atol=1e-6)
    staged = m.staged_predict_proba(frame)
    assert len(staged.names) == 8
    last = np.asarray(staged.vecs[-1].to_numpy())
    assert np.allclose(last, p1, atol=1e-6)
    first = np.asarray(staged.vecs[1].to_numpy())
    assert not np.allclose(first, p1, atol=1e-3)


def test_serving_buckets_score_a_set_split_model(deep):
    from h2o3_tpu import serve
    _, m, frame, X, _ = deep
    dep = serve.deploy(m.key, model=m, buckets=(1, 8, 64), max_batch=64)
    try:
        assert dep.scorer.jitted and set(dep.scorer.warm_seconds) == {1, 8, 64}
        rows = np.asarray(X[:64])
        names = list(m.feature_names)
        dom = {n: m.cat_domains.get(n) for n in names}
        dicts = [{n: (dom[n][int(v)] if dom[n] else float(v))
                  for n, v in zip(names, r)} for r in rows]
        want = np.asarray(m.predict(frame).vecs[-1].to_numpy())
        for lo, hi in ((0, 1), (1, 7), (0, 64)):        # buckets 1, 8, 64
            out = dep.predict_rows(dicts[lo:hi])
            got = np.array([list(o["classProbabilities"].values())[-1]
                            if isinstance(o["classProbabilities"], dict)
                            else o["classProbabilities"][-1] for o in out])
            assert np.allclose(got, want[lo:hi], atol=1e-6)
    finally:
        serve.undeploy(m.key)


def test_save_arrays_round_trip(deep, tmp_path):
    _, m, frame, X, _ = deep
    path = h2o.save_model(m, str(tmp_path), force=True)
    back = h2o.load_model(path)
    assert back._cat_set is not None
    assert np.array_equal(np.asarray(back._cat_set), np.asarray(m._cat_set))
    assert np.array_equal(np.asarray(back._is_set), np.asarray(m._is_set))
    assert np.array_equal(np.asarray(back._margin_matrix(X)),
                          np.asarray(m._margin_matrix(X)))


def test_mojo_write_then_read(deep, tmp_path):
    from h2o3_tpu.mojo import export_mojo, read_mojo
    _, m, frame, X, _ = deep
    path = export_mojo(m, str(tmp_path / "airline.zip"))
    mojo = read_mojo(path)
    rows = np.asarray(X[:64], np.float64)
    rows[0, 5], rows[1, 4] = np.nan, 4000.0          # NA; past the domain
    want = np.asarray(m._predict_matrix(jnp.asarray(rows, jnp.float32)))
    got = np.stack([mojo.score(r) for r in rows])
    assert np.allclose(got[:, -1], want[:, 1], atol=1e-6)


def test_checkpoint_resume_mid_train(tmp_path):
    """An in-training checkpoint of a set-split train, resumed: the trees
    and the margin of the uninterrupted train, bit for bit."""
    cell = cell_for(4, ntrees=4)
    frame = train_enum.build_frame(cell["config"], SEED)
    whole, _, _ = train(cell, frame)
    params = {**cell["config"]["params"], "ntrees": 2,
              "in_training_checkpoints_dir": str(tmp_path),
              "in_training_checkpoints_tree_interval": 2}
    est = H2OGradientBoostingEstimator(**params)
    est.train(y="dep_delayed_15min", training_frame=frame)
    ckpts = sorted(p for p in os.listdir(tmp_path) if p.endswith(".zip"))
    assert ckpts, os.listdir(tmp_path)
    resumed = H2OGradientBoostingEstimator(**{
        **cell["config"]["params"],
        "checkpoint": os.path.join(str(tmp_path), ckpts[-1])})
    resumed.train(y="dep_delayed_15min", training_frame=frame)
    a, b = whole._save_arrays(), resumed.model._save_arrays()
    for k in ("feat", "is_split", "na_left", "value", "cat_set", "is_set"):
        assert np.array_equal(a[k], b[k]), k
    assert float(whole.training_metrics.logloss) == float(
        resumed.model.training_metrics.logloss)


@pytest.mark.parametrize("reader", ["contributions", "leaf_assignment", "h",
                                    "pojo", "rulefit_rules"])
def test_threshold_readers_refuse_a_set_split_model(deep, reader):
    _, m, frame, X, _ = deep
    with pytest.raises(NotImplementedError, match="sets of levels"):
        if reader == "contributions":
            m.predict_contributions(frame)
        elif reader == "leaf_assignment":
            m.predict_leaf_node_assignment(frame)
        elif reader == "h":
            m.h(frame, ["Origin", "Dest"])
        elif reader == "pojo":
            from h2o3_tpu.genmodel import pojo_source
            pojo_source(m)
        else:
            T.refuse_set_splits(m, "rule extraction")


def test_tree_endpoint_lists_the_levels(deep):
    from h2o3_tpu.api import server
    _, m, _, _, _ = deep
    from h2o3_tpu import dkv
    dkv.put(m.key, "model", m)
    out = server._tree_route({"model": m.key, "tree_number": "0"}, None) \
        if hasattr(server, "_tree_route") else None
    if out is None:
        pytest.skip("no tree route")
    root_is_set = bool(np.asarray(m._is_set)[0, 0])
    lv = out["levels"]
    assert lv[0] is None
    if root_is_set:
        kids = [out["left_children"][0], out["right_children"][0]]
        dom = len(m.cat_domains[out["features"][0]])
        assert sorted(lv[kids[0]] + lv[kids[1]]) == list(range(dom))
        assert out["thresholds"][0] == "NaN"
    json.dumps(out)


def test_multinomial_and_the_in_chunk_validation_walk():
    """K = 3 trees an iteration through the same grower, and a validation
    frame: the boost chunk's walk over packed codes (``predict_binned``,
    sets included) scores it as ``predict_raw_stacked`` scores the raw
    frame afterwards."""
    from h2o3_tpu.frame.vec import T_ENUM, Vec
    h2o.init()
    rng = np.random.default_rng(0)
    n = 3000
    c = rng.integers(0, 30, n)
    x = rng.normal(size=n).astype(np.float32)
    cls = rng.integers(0, 3, 30)
    y = np.where(rng.random(n) < 0.8, cls[c], rng.integers(0, 3, n))

    def frame(rows):
        return h2o.Frame(["c", "x", "y"], [
            Vec.from_numpy(c[rows], T_ENUM, [f"a{i}" for i in range(30)]),
            Vec.from_numpy(x[rows]),
            Vec.from_numpy(y[rows].astype(np.int32), T_ENUM, ["u", "v", "w"])])
    est = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1,
                                       packed_codes=True, min_rows=5,
                                       score_tree_interval=2)
    valid = frame(slice(0, 512))
    est.train(y="y", training_frame=frame(slice(None)),
              validation_frame=valid)
    m = est.model
    assert m._cat_set.shape == (12, 15, 1)            # 4 iterations x 3
    assert int(np.asarray(m._is_set).sum()) > 10
    in_chunk = float(m.validation_metrics.logloss)
    afterwards = float(m.model_performance(valid).logloss)
    assert in_chunk == pytest.approx(afterwards, rel=1e-6)
    assert in_chunk < 0.75                     # ordinal splits read 0.86
