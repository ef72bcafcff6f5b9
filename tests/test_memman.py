"""Memory-pressure: budget, LRU spill, streaming GBM + GLM training
(water/Cleaner.java + MemoryManager.java analogs, SURVEY §7.1.7)."""
import os

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import memman


@pytest.fixture(autouse=True)
def _restore_budget():
    yield
    memman.reset()     # back to unlimited for other tests


def _frame(n=60_000, f=8, seed=0, classification=True):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, f)).astype(np.float32)
    logit = X[:, 0] - 0.7 * X[:, 1] + 0.4 * X[:, 2]
    cols = {f"x{i}": X[:, i] for i in range(f)}
    if classification:
        y = (rng.random(n) < 1 / (1 + np.exp(-logit)))
        cols["resp"] = np.array(["n", "y"], dtype=object)[y.astype(int)]
    else:
        cols["resp"] = (logit + 0.2 * rng.normal(size=n)).astype(np.float32)
    return h2o.Frame.from_numpy(cols)


def test_lru_spill_and_rematerialize():
    memman.reset(budget=1_000_000)      # ~1MB device budget
    vecs = []
    for i in range(8):
        v = h2o.Frame.from_numpy(
            {"c": np.arange(50_000, dtype=np.float64) + i}).vec("c")
        vecs.append(v)
    st = memman.manager().stats()
    assert st["spill_count"] > 0        # early vecs were evicted
    # spilled vec re-materializes transparently with exact values
    first = vecs[0]
    assert first._dev is None or True   # may or may not be the evictee
    got = np.asarray(first.to_numpy())
    assert got[1] == 1.0 and got[-1] == 49_999.0


def test_streaming_gbm_trains_beyond_budget():
    # budget ~0.5MB << 60k x 8 x 4B = 1.9MB design: forces X_host mode
    memman.reset(budget=500_000)
    fr = _frame(classification=True)
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    gbm = H2OGradientBoostingEstimator(ntrees=5, max_depth=3, nbins=16,
                                       seed=1, score_tree_interval=0)
    gbm.train(y="resp", training_frame=fr)
    m = gbm.model
    assert m.output.get("streamed") is True
    assert m.training_metrics.auc > 0.75
    # the model predicts densely like any other tree model
    memman.reset()
    pred = m.predict(fr)
    assert pred.nrow == fr.nrow


def test_streaming_glm_matches_dense():
    fr = _frame(n=40_000, classification=False, seed=3)
    from h2o3_tpu.models.glm import H2OGeneralizedLinearEstimator
    memman.reset()                       # dense reference fit
    dense = H2OGeneralizedLinearEstimator(family="gaussian", Lambda=[0.0])
    dense.train(y="resp", training_frame=fr)
    dense_coef = dense.model.coef()
    memman.reset(budget=400_000)         # force streaming
    st = H2OGeneralizedLinearEstimator(family="gaussian", Lambda=[0.0])
    st.train(y="resp", training_frame=fr)
    assert st.model.output.get("streamed") is True
    sc = st.model.coef()
    for k, v in dense_coef.items():
        assert abs(sc[k] - v) < 5e-3, (k, sc[k], v)


def test_cloud_memory_report():
    memman.reset(budget=123_456_789)
    from h2o3_tpu.api import schemas
    cloud = schemas.cloud_v3()
    node = cloud["nodes"][0]
    assert node.get("device_budget_bytes") == 123_456_789
    assert "spill_count" in node


def test_streaming_unsupported_algo_fails_fast():
    memman.reset(budget=300_000)
    fr = _frame(n=30_000, classification=True, seed=9)
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    drf = H2ORandomForestEstimator(ntrees=2, max_depth=3)
    with pytest.raises(RuntimeError, match="streaming"):
        drf.train(y="resp", training_frame=fr)


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self.device_kind, self._stats = platform, "TPU vX", stats

    def memory_stats(self):
        return self._stats


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 16 << 30}, 16 << 30),
    ("tpu", None, RuntimeError),          # a chip with no limit: refuse
    ("tpu", {"bytes_in_use": 1}, RuntimeError),
    ("cpu", None, 1 << 62),               # the CPU backend reports none
])
def test_default_budget_is_the_device_limit_or_an_error(
        monkeypatch, platform, stats, want):
    """An unlimited budget is the CPU backend's alone: on a TPU a missing
    bytes_limit raises instead of making every admission a guess."""
    import jax
    monkeypatch.delenv("H2O3_DEVICE_BUDGET_BYTES", raising=False)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeDevice(platform, stats)])
    if isinstance(want, int):
        assert memman._default_budget() == want
    else:
        with pytest.raises(want, match="bytes_limit"):
            memman._default_budget()


# ---------------- the budget is one device's, the arrays are row-sharded --


@pytest.mark.parametrize("per_shard,streams", [(True, False), (False, True)])
def test_a_table_that_fits_four_shards_and_not_one_stays_dense(per_shard,
                                                               streams):
    """Rows are split over the data axis, so a device holds a quarter of a
    design matrix on a four-shard mesh: held against ONE device's budget by
    its per-shard bytes it stays dense where the whole would be streamed.
    build_training_spec and the scheduler's estimate read the one rule
    (memman.per_shard)."""
    import jax
    from h2o3_tpu import sched
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.models.model_base import build_training_spec
    from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh
    old = current_mesh()
    set_mesh(make_mesh(n_data=4, devices=jax.devices()[:4]))
    try:
        fr = _frame(n=20_000)
        x_bytes = (fr.nrow + 256) * 8 * 4
        # half the matrix: a quarter of it is under 90% of this, all of it
        # is not
        mm = memman.reset(budget=x_bytes // 2, per_shard=per_shard)
        assert mm.per_shard(x_bytes) == (x_bytes // 4 if per_shard
                                         else x_bytes)
        assert mm.fits_device(x_bytes) is not streams
        spec = build_training_spec(fr, "resp")
        assert spec.stream is streams
        assert (spec.X is None) is streams
        est = sched.estimate_submission(
            H2OGradientBoostingEstimator(ntrees=2, max_depth=2), fr,
            y="resp")
        assert est.streamed is streams
        if not streams:
            # a device's share of the design, its working set and vectors
            # (a cached executable's cost may raise it, to 4x at most)
            share = mm.per_shard(
                int(x_bytes * 1.7) + (fr.nrow + 256) * 4 * 4)
            assert share <= est.bytes <= 4 * share
            assert est.bytes == share or est.source == "costmodel+shape"
    finally:
        set_mesh(old)


def test_per_shard_follows_the_mesh_and_an_unlimited_budget_divides_nothing():
    import jax
    from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh
    assert memman.reset().per_shard(1000) == 1000       # the CPU: unlimited
    old = current_mesh()
    try:
        mm = memman.reset(budget=10_000, per_shard=True)
        for nd in (1, 2, 8):
            set_mesh(make_mesh(n_data=nd, devices=jax.devices()[:nd]))
            assert mm.per_shard(1001) == -(-1001 // nd)
    finally:
        set_mesh(old)
    # a forced budget is held against whole arrays unless asked otherwise
    assert memman.reset(budget=10_000).per_shard(1001) == 1001
