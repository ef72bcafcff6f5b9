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


def _parity_case(X, names, is_cat, nrow, nbins, nbins_cats, hist):
    import jax.numpy as jnp
    from h2o3_tpu.ops.binning import bin_matrix, bin_matrix_device
    bmh = bin_matrix(np.asarray(X), names, is_cat, nrow, nbins=nbins,
                     nbins_cats=nbins_cats, histogram_type=hist)
    bmd = bin_matrix_device(jnp.asarray(X), names, is_cat, nrow, nbins=nbins,
                            nbins_cats=nbins_cats, histogram_type=hist)
    assert bmh.n_bins == bmd.n_bins
    for f in range(len(names)):
        assert np.array_equal(bmh.edges[f], bmd.edges[f]), \
            (hist, names[f], bmh.edges[f], bmd.edges[f])
    assert np.array_equal(np.asarray(bmh.codes.rm), np.asarray(bmd.codes.rm))


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
