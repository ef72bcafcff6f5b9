"""Device-resident train path (ISSUE 2): sketch parity, compile-count
regression guards, pipelined scoring semantics, and the no-full-X-fetch
contract.

- the device-side global sketch (ops/binning.bin_matrix_device) must
  produce BIT-IDENTICAL edges/codes to the host bin_matrix on numeric,
  categorical, NA, tied, and infinite inputs — it replicates np.quantile's
  float64 lerp on device-gathered rank neighbours;
- a warm train must trigger ZERO XLA compiles, and ntrees/sample-rate/
  learn-rate grid variants must reuse the bucket executables (traced
  rates + chunk-length buckets);
- interval scoring is pipelined (chunk k+1 dispatched before chunk k's
  scalars are fetched) — the scoring history cadence and the early-stop
  tree count must match the serial semantics exactly;
- the default train path never device_gets anything within 2x of the
  full X matrix (the old global-sketch path fetched all of X).
"""
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

from _compile_counter import count_compiles  # noqa: E402 — shared harness


# --------------------------------------------------- device sketch parity


def _pad(col, pad):
    out = np.full(pad, np.nan, np.float32)
    out[: len(col)] = col
    return out


def _assert_same_bins(got, want):
    """Two BinnedMatrix of one matrix: bit-equal edges, equal codes."""
    assert got.n_bins == want.n_bins
    for f, (a, b) in enumerate(zip(got.edges, want.edges)):
        assert a.dtype == b.dtype and np.array_equal(a, b), (f, a, b)
    assert np.array_equal(np.asarray(got.codes.rm), np.asarray(want.codes.rm))


def _parity_case(X, names, is_cat, nrow, nbins, nbins_cats, hist):
    import jax.numpy as jnp
    from h2o3_tpu.ops.binning import bin_matrix, bin_matrix_device
    bmh = bin_matrix(np.asarray(X), names, is_cat, nrow, nbins=nbins,
                     nbins_cats=nbins_cats, histogram_type=hist)
    bmd = bin_matrix_device(jnp.asarray(X), names, is_cat, nrow, nbins=nbins,
                            nbins_cats=nbins_cats, histogram_type=hist)
    _assert_same_bins(bmd, bmh)


@pytest.mark.parametrize("hist", ["quantiles_global", "uniform_adaptive"])
def test_device_sketch_edges_match_host(hist):
    rng = np.random.default_rng(7)
    n, pad = 3000, 3072
    X = np.stack([
        _pad(rng.normal(size=n).astype(np.float32), pad),        # numeric
        _pad(np.round(rng.normal(size=n) * 2).astype(np.float32),
             pad),                                               # heavy ties
        _pad(rng.integers(0, 5, n).astype(np.float32), pad),     # cat id bins
        _pad(rng.integers(0, 200, n).astype(np.float32), pad),   # wide cat
        _pad(rng.normal(size=n).astype(np.float32), pad),        # NA-heavy
        np.full(pad, np.nan, np.float32),                        # all-NA
        _pad(np.full(n, 3.25, np.float32), pad),                 # constant
    ], axis=1)
    X[rng.random(pad) < 0.3, 4] = np.nan
    X[11, 0] = np.inf
    X[12, 0] = -np.inf          # non-finite must not skew ranks
    names = list("abcdefg")
    is_cat = [False, False, True, True, False, False, False]
    _parity_case(X, names, is_cat, n, nbins=16, nbins_cats=64, hist=hist)


@pytest.fixture
def mesh_of():
    """``mesh_of(n)`` puts the suite on a mesh of n data shards (1: the
    one-device sketch) until the test ends."""
    import jax
    from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh
    old = current_mesh()
    yield lambda nd: set_mesh(make_mesh(n_data=nd,
                                        devices=jax.devices()[:nd]))
    set_mesh(old)


@pytest.fixture
def sorts(monkeypatch):
    """The blocks' shapes the sketch hands to its sort, one a call."""
    from h2o3_tpu.ops import binning
    seen, sort = [], binning._sort_finite

    def counted(X, nrow):
        seen.append(X.shape)
        return sort(X, nrow)

    counted.lower = sort.lower
    monkeypatch.setattr(binning, "_sort_finite", counted)
    return seen


def _sketch_frame(columns, n=3000, pad=3072, seed=36):
    """[pad, len(columns)] of the named column kinds with NaN sprinkled in
    and pad rows that would move every edge if they were read."""
    rng = np.random.default_rng(seed)
    make = {"numeric": lambda: rng.normal(size=pad) * 5.0,
            "enum": lambda: rng.integers(0, 40, pad),      # identity bins
            "wide_enum": lambda: rng.integers(0, 200, pad)}
    X = np.stack([make[c]() for c in columns], axis=1).astype(np.float32)
    X[rng.random(X.shape) < 0.05] = np.nan
    X[n:] = 1e9
    return X, [c != "numeric" for c in columns]


def _sketched(X, is_cat, n, hist, nbins=16, nbins_cats=64):
    """(the device sketch's BinnedMatrix, its sketch span) on the mesh in
    force."""
    import jax
    from h2o3_tpu import telemetry
    from h2o3_tpu.log import Profile
    from h2o3_tpu.ops.binning import bin_matrix_device
    from h2o3_tpu.parallel.mesh import data_sharding
    telemetry.clear_spans()
    bm = bin_matrix_device(jax.device_put(X, data_sharding()),
                           [f"c{i}" for i in range(X.shape[1])], is_cat, n,
                           nbins=nbins, nbins_cats=nbins_cats,
                           histogram_type=hist, prof=Profile())
    span, = [s for s in telemetry.finished_spans()
             if s.name.endswith("bin.sketch")]
    return bm, span


@pytest.mark.parametrize("columns", [
    ("numeric",) * 4, ("enum",) * 3, ("numeric", "enum", "numeric", "enum")],
    ids=["numerics", "identity_enums", "both"])
@pytest.mark.parametrize("hist", ["uniform_adaptive", "uniform"])
def test_one_device_sketch_sorts_nothing_where_no_edge_reads_a_rank(
        mesh_of, sorts, hist, columns):
    """Uniform numerics read a min and a max, an enum of at most
    nbins_cats levels its max: ``_rank_grids`` names no column, so the
    one-device sketch dispatches no sort (ISSUE 36), says
    ``ranked_features`` 0, and the program it does run holds none."""
    import jax.numpy as jnp
    from h2o3_tpu.ops import binning
    mesh_of(1)
    X, is_cat = _sketch_frame(columns)
    bm, span = _sketched(X, is_cat, 3000, hist)
    assert sorts == []
    assert (bm.sketch, bm.ranked_features) == ("device", 0)
    assert (span.attrs["where"], span.attrs["ranked_features"]) == (
        "device", 0)
    assert span.attrs["d2h_bytes"] == 3 * len(columns) * 4
    args = jnp.asarray(X), jnp.int32(3000)
    assert "stablehlo.sort" in binning._sort_finite.lower(*args).as_text()
    assert "stablehlo.sort" not in binning._device_extremes.lower(
        *args).as_text()
    want = binning.bin_matrix(X, list(columns), is_cat, 3000, nbins=16,
                              nbins_cats=64, histogram_type=hist)
    _assert_same_bins(bm, want)


@pytest.mark.parametrize("hist,ranked", [("uniform_adaptive", [2]),
                                         ("quantiles_global", [0, 2, 3])])
def test_one_device_sketch_sorts_exactly_the_columns_rank_grids_names(
        mesh_of, sorts, hist, ranked):
    """The mixed frame: an enum past nbins_cats beside uniform numerics
    sorts ONE column of four where the parent sorted all four; under
    quantile edges every column but the identity-bin enum. Every column's
    edges are the host rule's either way."""
    from h2o3_tpu.ops import binning
    mesh_of(1)
    columns = ("numeric", "enum", "wide_enum", "numeric")
    X, is_cat = _sketch_frame(columns)
    bm, span = _sketched(X, is_cat, 3000, hist)
    grids = binning._rank_grids(
        np.isfinite(X[:3000]).sum(0), np.nanmax(X[:3000], axis=0), is_cat,
        16, 64, hist == "uniform_adaptive")
    assert [f for f, g in enumerate(grids) if g is not None] == ranked
    assert sorts == [(X.shape[0], len(ranked))]
    assert bm.ranked_features == span.attrs["ranked_features"] == len(ranked)
    want = binning.bin_matrix(X, list(columns), is_cat, 3000, nbins=16,
                              nbins_cats=64, histogram_type=hist)
    _assert_same_bins(bm, want)


def _hard_columns(n=3000, pad=3072, seed=11):
    """The columns a min / max / count pass can get wrong: all-NA,
    constant, both infinities, heavy ties, NA-heavy, a column whose min is
    -0.0 (and one whose max is), an enum; pad rows past ``n`` hold values
    that would move every edge."""
    rng = np.random.default_rng(seed)
    neg0 = np.abs(rng.normal(size=n)).astype(np.float32)
    neg0[::7], neg0[3::7] = -0.0, 0.0             # min is -0.0 (or +0.0)
    X = np.stack([
        rng.normal(size=n), np.round(rng.normal(size=n) * 2),
        np.full(n, np.nan), np.full(n, 3.25), neg0, -neg0,
        np.where(rng.random(n) < 0.6, np.nan, rng.normal(size=n)),
        rng.integers(0, 9, n)], axis=1).astype(np.float32)
    X[11, 0], X[12, 0], X[13, 3] = np.inf, -np.inf, np.inf
    return (np.concatenate([X, np.full((pad - n, X.shape[1]), -1e9,
                                       np.float32)]),
            [False] * 7 + [True])


@pytest.mark.parametrize("hist", ["quantiles_global", "uniform_adaptive"])
def test_one_device_sketch_edges_of_the_hard_columns_are_the_host_rule_s(
        mesh_of, hist):
    """Bit-equal edges to ``_edges_host`` whichever way a column's min and
    max were found. The one pair of values that may differ in bits, -0.0
    and +0.0 (a reduction may return either zero, a sort puts -0.0
    first), gives the same ``linspace(lo, hi)[1:-1]``, the same ``lo ==
    hi`` test and the same ``int(fmax) + 1``: held here by a column whose
    min, and one whose max, is a zero of both signs."""
    from h2o3_tpu.ops import binning
    mesh_of(1)
    X, is_cat = _hard_columns()
    bm, span = _sketched(X, is_cat, 3000, hist)
    edges, n_bins = binning._edges_host(X, 3000, is_cat, 16, 64, hist)
    assert bm.n_bins == n_bins
    assert span.attrs["ranked_features"] == (
        0 if hist == "uniform_adaptive" else 6)    # all-NA: nothing to rank
    for f, (a, b) in enumerate(zip(bm.edges, edges)):
        assert a.dtype == b.dtype and np.array_equal(a, b), (f, a, b)
    assert len(bm.edges[2]) == 0                   # all-NA
    assert hist != "uniform_adaptive" or len(bm.edges[3]) == 0   # constant
    neg, pos = np.float32(-0.0), np.float32(0.0)
    assert np.signbit(neg) and not np.signbit(pos)
    for a, b in (((neg, 1.0), (pos, 1.0)), ((-1.0, neg), (-1.0, pos))):
        assert np.array_equal(np.linspace(*a, 17)[1:-1],
                              np.linspace(*b, 17)[1:-1])
    assert neg == pos and int(neg) + 1 == int(pos) + 1 == 1


@pytest.mark.parametrize("hist", ["uniform_adaptive", "uniform"])
def test_one_device_edges_of_the_hard_columns_are_the_mesh_s(mesh_of, hist):
    """One statement of the finite count / min / max rule serves one
    device and the 8-shard mesh: same edges, same codes, and 3·F numbers
    fetched on both."""
    X, is_cat = _hard_columns()
    got = {}
    for nd in (1, 8):
        mesh_of(nd)
        got[nd] = _sketched(X, is_cat, 3000, hist)
    (one, s1), (mesh, s8) = got[1], got[8]
    assert (s1.attrs["where"], s8.attrs["where"]) == ("device", "mesh")
    assert s1.attrs["ranked_features"] == s8.attrs["ranked_features"] == 0
    assert s1.attrs["d2h_bytes"] == s8.attrs["d2h_bytes"] == 3 * 8 * 4
    _assert_same_bins(one, mesh)


def test_multishard_accelerator_sketch_digitises_the_sharded_matrix(
        monkeypatch):
    """On an accelerator mesh with several data shards the edges come
    from a host copy, but the digitise must still see the SHARDED device
    matrix: handed the host copy it puts all of X, and the sort-sized
    temporaries, on the first chip (5.65 GB against 1.22 GB on the other
    three of a v5e 2x2 at 10M x 28, PR 22). Only a real accelerator takes
    this branch, so the test steers it here."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.ops import binning
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards
    mesh = current_mesh()
    assert n_data_shards(mesh) > 1
    rng = np.random.default_rng(3)
    n = 4096
    X = rng.normal(size=(n, 5)).astype(np.float32)
    X[rng.random(n) < 0.1, 2] = np.nan
    names, is_cat = list("abcde"), [False] * 5
    want = binning.bin_matrix(X, names, is_cat, n, nbins=14)

    seen = []
    digitize = binning.digitize_with_edges
    monkeypatch.setattr(binning, "digitize_with_edges",
                        lambda X, *a: seen.append(X) or digitize(X, *a))
    monkeypatch.setattr(jax, "default_backend", lambda: "not-the-cpu")
    Xd = jax.device_put(jnp.asarray(X), NamedSharding(mesh, P("data")))
    got = binning.bin_matrix_device(Xd, names, is_cat, n, nbins=14)

    assert isinstance(seen[0], jax.Array)
    assert len(seen[0].sharding.device_set) == n_data_shards(mesh)
    assert got.n_bins == want.n_bins
    assert all(np.array_equal(a, b) for a, b in zip(got.edges, want.edges))
    assert np.array_equal(np.asarray(got.codes.rm), np.asarray(want.codes.rm))


def test_device_sketch_trains_global_hist():
    rng = np.random.default_rng(1)
    n = 3000
    x = rng.normal(size=(n, 3)).astype(np.float32)
    y = x[:, 0] * 2 + rng.normal(size=n) * 0.1
    fr = h2o.Frame.from_numpy({"a": x[:, 0], "b": x[:, 1], "c": x[:, 2],
                               "y": y})
    gbm = H2OGradientBoostingEstimator(ntrees=20, max_depth=3, seed=1,
                                       learn_rate=0.3,
                                       histogram_type="quantiles_global",
                                       nbins=24)
    gbm.train(y="y", training_frame=fr)
    assert gbm.model.training_metrics.r2 > 0.9


def test_default_path_never_fetches_full_x(monkeypatch):
    """Acceptance bar: no device_get within 2x of the full X matrix on
    the default (non-scoring) train path — the sketch, score, and
    finalize fetches are all O(F·nbins) / O(trees) / scalars."""
    import jax
    rng = np.random.default_rng(2)
    n, F = 50_000, 8
    cols = {f"c{i}": rng.normal(size=n).astype(np.float32) for i in range(F)}
    cols["y"] = (cols["c0"] * 3 + rng.normal(size=n)).astype(np.float32)
    fr = h2o.Frame.from_numpy(cols)
    x_bytes = n * F * 4
    fetches = []
    real_get = jax.device_get

    def spy(tree):
        tot = 0
        for leaf in jax.tree.leaves(tree):
            tot += getattr(leaf, "nbytes", 0) or 0
        fetches.append(tot)
        return real_get(tree)

    monkeypatch.setattr(jax, "device_get", spy)
    gbm = H2OGradientBoostingEstimator(ntrees=8, max_depth=3, seed=3,
                                       histogram_type="quantiles_global",
                                       nbins=20)
    gbm.train(y="y", training_frame=fr)
    monkeypatch.undo()
    assert gbm.model.ntrees_built == 8
    assert fetches, "expected some scalar/summary fetches"
    assert max(fetches) < x_bytes // 2, \
        f"a device_get moved {max(fetches)} bytes (X is {x_bytes})"


# ------------------------------------------------ compile-count regression


def _small_frame(seed=5, n=4096, F=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = (X[:, 0] + rng.normal(size=n) > 0).astype(np.float32)
    cols = {f"c{i}": X[:, i] for i in range(F)}
    cols["y"] = y
    return h2o.Frame.from_numpy(cols)


def _train(fr, **kw):
    p = dict(ntrees=10, max_depth=3, seed=1, distribution="bernoulli",
             min_rows=1.0)
    p.update(kw)
    g = H2OGradientBoostingEstimator(**p)
    g.train(y="y", training_frame=fr)
    return g.model


def test_warm_train_zero_recompiles():
    fr = _small_frame()
    _train(fr)                       # cold: compiles everything
    events = []
    with count_compiles(events):
        m = _train(fr)               # identical warm run
    assert m.ntrees_built == 10
    assert len(events) == 0, f"warm train compiled {len(events)} modules"


def test_grid_variants_reuse_bucket_executables():
    """Chunk lengths round up to a bucket with the tail masked by the
    traced n_active, and sample/col/learn rates ride as traced scalars —
    so a grid variant whose bucket is warm compiles NOTHING."""
    fr = _small_frame(seed=6)
    _train(fr, ntrees=10)            # warms bucket {10}
    events = []
    with count_compiles(events):
        m = _train(fr, ntrees=9, sample_rate=0.7, learn_rate=0.05,
                   col_sample_rate=0.8)
    assert m.ntrees_built == 9       # bucket 10, one masked tree
    assert len(events) == 0, f"variant compiled {len(events)} modules"


def test_cold_compile_budget():
    """Time-to-first-model guard: a cold train must stay under a fixed
    compile-module budget (measured ~51 on this path; generous headroom
    for jaxlib drift — catching 2x regressions is the point)."""
    fr = _small_frame(seed=9, n=2560, F=4)
    events = []
    with count_compiles(events):
        _train(fr, ntrees=7, max_depth=2, distribution="gaussian")
    assert len(events) <= 90, f"cold train compiled {len(events)} modules"


# ------------------------------------------------------ pipelined scoring


def test_scoring_history_cadence_pipelined():
    fr = _small_frame(seed=8)
    m = _train(fr, ntrees=6, score_tree_interval=2)
    hist = [e["ntrees"] for e in m.scoring_history]
    assert hist == [2, 4, 6]
    assert m.ntrees_built == 6
    assert all(np.isfinite(e["deviance"]) for e in m.scoring_history)


def test_early_stop_discards_speculative_chunk():
    """With early stopping the pipeline dispatches one chunk ahead; a
    stop verdict must discard it — built trees end exactly at the last
    SCORED interval, like the serial loop."""
    rng = np.random.default_rng(3)
    n = 3000
    x = rng.normal(size=n).astype(np.float32)
    y = 2 * x + rng.normal(size=n).astype(np.float32) * 0.01
    fr = h2o.Frame.from_numpy({"x": x, "y": y})
    g = H2OGradientBoostingEstimator(ntrees=200, max_depth=3, learn_rate=0.3,
                                     stopping_rounds=2,
                                     stopping_tolerance=5e-2,
                                     score_tree_interval=5, seed=3)
    g.train(y="y", training_frame=fr)
    m = g.model
    assert m.ntrees_built < 200
    assert m.ntrees_built % 5 == 0
    assert m.scoring_history[-1]["ntrees"] == m.ntrees_built


def test_stopping_metric_auc_trains():
    """stopping_metric='auc' used to crash on an import of a kernel that
    no longer existed; it now early-stops on the device-sketch AUC."""
    fr = _small_frame(seed=12)
    m = _train(fr, ntrees=60, stopping_rounds=2, stopping_metric="auc",
               score_tree_interval=5, stopping_tolerance=0.5)
    assert m.ntrees_built <= 60
    assert any("auc" in e for e in m.scoring_history)
    aucs = [e["auc"] for e in m.scoring_history if "auc" in e]
    assert all(0.0 <= a <= 1.0 for a in aucs)


def test_auc_device_matches_exact_sweep():
    from h2o3_tpu.models.metrics import auc_device, make_binomial_metrics
    rng = np.random.default_rng(4)
    n = 20_000
    y = (rng.random(n) < 0.4).astype(np.float32)
    p = np.clip(0.4 * y + rng.random(n) * 0.8, 0, 1).astype(np.float32)
    w = np.ones(n, np.float32)
    exact = make_binomial_metrics(p, y, w).auc
    sketch = float(np.asarray(auc_device(p, y, w)))
    assert abs(exact - sketch) < 5e-3


# ------------------------------------------------- combinator compile cache


def _sum_shard(x):
    import jax.numpy as jnp
    return jnp.nansum(x)


def test_map_reduce_caches_named_fns_and_skips_lambdas():
    from h2o3_tpu.parallel.map_reduce import (_cacheable,
                                              _compiled_map_reduce,
                                              map_reduce)
    assert _cacheable(_sum_shard, "sum")
    assert not _cacheable(lambda x: x, "sum")        # identity-keyed: skip
    assert not _cacheable(_sum_shard, [1, 2])        # unhashable: skip

    def nested(x):
        return x
    assert not _cacheable(nested, "sum")             # per-call def: skip

    rng = np.random.default_rng(1)
    data = rng.normal(size=4096).astype(np.float32)
    fr = h2o.Frame.from_numpy({"c": data})
    v = fr.vec("c")
    before = _compiled_map_reduce.cache_info().hits
    r1 = float(map_reduce(_sum_shard, v.data))
    r2 = float(map_reduce(_sum_shard, v.data))       # cached callable
    assert _compiled_map_reduce.cache_info().hits > before
    assert abs(r1 - float(np.nansum(data))) < 1e-2
    assert r1 == r2
    # lambda path still works (uncached, the pre-cache behavior)
    r3 = float(map_reduce(lambda x: _sum_shard(x), v.data))
    assert abs(r3 - r1) < 1e-6


# ------------------------------------------------------- ingest grouping


def test_from_typed_column_groups_matches_from_typed_columns():
    from h2o3_tpu.frame.frame import Frame
    from h2o3_tpu.ingest.chunk import EncodedColumn
    from h2o3_tpu.frame.vec import T_ENUM, T_REAL, T_TIME
    rng = np.random.default_rng(10)
    n = 1000
    num = EncodedColumn(T_REAL, rng.normal(size=n))
    enum = EncodedColumn(T_ENUM, rng.integers(0, 3, n).astype(np.int32),
                         domain=["a", "b", "c"])
    ms = (np.datetime64("2020-01-01", "ms").astype(np.int64)
          + rng.integers(0, 10**9, n))
    tm = EncodedColumn(T_TIME, ms)
    names = ["n", "e", "t"]
    a = Frame.from_typed_columns(names, [num, enum, tm])
    pulled = []

    def groups():
        pulled.append("num")
        yield [(0, num), (2, tm)]
        pulled.append("enum")
        yield [(1, enum)]

    b = Frame.from_typed_column_groups(names, groups(), 3)
    assert pulled == ["num", "enum"]
    assert a.names == b.names
    for nm in names:
        va, vb = a.vec(nm), b.vec(nm)
        assert va.type == vb.type
        assert va.domain == vb.domain
        assert np.array_equal(va.to_numpy(), vb.to_numpy(), equal_nan=True)
