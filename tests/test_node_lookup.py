"""``tree.node_lookup``: a per-row lookup into a per-tree node table done
by exact selection while the table is small (PR 30). It has to equal
``table[nid]`` bit for bit at every size and dtype the boost loop hands it,
leave a train's exported bytes as the gather left them, and keep the real
gather out of the binned chunk body at depth 6.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models import gbm, streaming, tree
from h2o3_tpu.models.tree import NODE_SELECT_MAX, node_lookup, node_lookup_form

ROWS = 4096
SIZES = [1, 3, 63, 64, 65, 127, 255, 1023, NODE_SELECT_MAX,
         NODE_SELECT_MAX + 1]


def _table(M, kind, rng):
    if kind == "float32":
        t = rng.standard_normal(M).astype(np.float32)
        # what a multiply by a one-hot or a sum would not carry through
        special = np.array([-0.0, np.inf, -np.inf, np.nan, 1e-45],
                           np.float32)
        where = rng.permutation(M)[:special.size]
        t[where] = special[:where.size]
        return t
    if kind == "bool":                       # is_split, na_left
        return rng.random(M) < 0.5
    return rng.integers(-2 ** 31, 2 ** 31 - 1, M,    # the packed word
                        dtype=np.int64).astype(np.int32)


def _bits(a):
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("kind", ["float32", "bool", "int32"])
@pytest.mark.parametrize("M", SIZES)
def test_equals_the_gather_bit_for_bit(M, kind):
    rng = np.random.default_rng(M)
    table = _table(M, kind, rng)
    nid = rng.integers(0, M, ROWS).astype(np.int32)
    nid[0], nid[-1] = 0, M - 1               # first and last entry
    got = jax.jit(node_lookup)(jnp.asarray(table), jnp.asarray(nid))
    assert got.dtype == table.dtype and got.shape == nid.shape
    np.testing.assert_array_equal(_bits(got), _bits(table[nid]))


@pytest.mark.parametrize("M,form", [(63, "select"), (127, "select"),
                                    (NODE_SELECT_MAX, "select"),
                                    (NODE_SELECT_MAX + 1, "gather"),
                                    (2 ** 17 - 1, "gather")])
def test_the_form_follows_the_tables_size_alone(M, form):
    assert node_lookup_form(M) == form
    jaxpr = str(jax.make_jaxpr(node_lookup)(
        jnp.zeros(M, jnp.float32), jnp.zeros(ROWS, jnp.int32)))
    assert ("gather" in jaxpr) == (form == "gather")


def _gathers_over_rows(jaxpr, rows):
    """(operand entries, indices shape) of every gather in the jaxpr, its
    sub-jaxprs included, whose indices run over the rows."""
    found = []

    def walk(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "gather":
                operand, indices = (v.aval for v in eqn.invars[:2])
                if indices.shape and indices.shape[0] == rows:
                    found.append((int(np.prod(operand.shape)),
                                  indices.shape))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("K,has_valid", [(1, False), (1, True), (3, False)])
def test_no_small_table_is_gathered_over_the_rows_at_depth_6(
        monkeypatch, K, has_valid):
    """The idiom's guard, whatever the platform: the binned chunk body at
    depth 6 (127 nodes; the level kernels as the chip runs them, here only
    traced) holds no gather over the rows from a table of up to
    NODE_SELECT_MAX entries. With the rule switched off the same walk
    finds the margin update's gather: the guard sees what it guards."""
    monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    F, W, chunk = 28, 32, 2
    cfg = tree.TreeConfig(max_depth=6, n_bins=20, n_features=F)

    def body(*operands):
        return gbm._gbm_chunk_body(
            *operands, cfg=cfg, K=K,
            dist_name="bernoulli" if K == 1 else "multinomial",
            tweedie_power=1.5, quantile_alpha=0.5,
            sample_rate_per_class=None, na_bin=W - 1, chunk=chunk,
            has_valid=has_valid, has_t=True, adaptive=False, binned=True,
            has_mono=False, has_sets=False, axis_name=None)

    f32 = jnp.float32
    margin = (ROWS,) if K == 1 else (ROWS, K)
    sds = jax.ShapeDtypeStruct
    operands = [sds((ROWS, F), jnp.int8), sds((F, ROWS), jnp.int8),
                sds(margin, f32), sds((ROWS,), f32), sds((ROWS,), f32),
                sds((ROWS, F), jnp.int8), sds(margin, f32),
                jax.random.key(0), sds((), f32), sds((), f32),
                sds((F,), f32), sds((F,), f32), sds((F,), f32),
                sds((F,), jnp.int32), sds((1, F), jnp.bool_),
                sds((), jnp.int32), sds((), jnp.int32), sds((), f32),
                sds((), f32), sds((), f32)]
    small = [g for g in _gathers_over_rows(jax.make_jaxpr(body)(*operands),
                                           ROWS)
             if g[0] <= NODE_SELECT_MAX]
    assert not small, small
    monkeypatch.setattr(tree, "NODE_SELECT_MAX", 0)
    # a new function object: the rule is read when the body is traced
    assert _gathers_over_rows(
        jax.make_jaxpr(lambda *a: body(*a))(*operands), ROWS)


def _export(make, monkeypatch):
    """The bytes a train exports, and its final margin."""
    rng = np.random.default_rng(30)
    X = rng.standard_normal((ROWS, 6)).astype(np.float32)
    X[rng.random(X.shape) < 0.03] = np.nan
    y = (np.nan_to_num(X[:, 0]) - 0.7 * np.nan_to_num(X[:, 1] * X[:, 2])
         + 0.3 * rng.standard_normal(ROWS)) > 0
    cols = {f"x{i}": X[:, i] for i in range(X.shape[1])}
    fr = h2o.Frame.from_numpy({**cols, "resp": np.where(y, "a", "b")})
    seen = {}
    real = gbm.H2OGradientBoostingEstimator._finalize

    def spy(self, spec, valid_spec, dist_name, f0, all_trees, bm, cfg, K,
            built, margin, *rest, **kw):
        seen["margin"] = np.asarray(margin)
        seen["trees"] = [jax.tree.map(np.asarray, t) for t, _ in all_trees]
        return real(self, spec, valid_spec, dist_name, f0, all_trees, bm, cfg,
                    K, built, margin, *rest, **kw)
    monkeypatch.setattr(gbm.H2OGradientBoostingEstimator, "_finalize", spy)
    est = make()
    est.train(y="resp", training_frame=fr)
    out = {"margin": seen["margin"]}
    # every array of the chunks' trees: feat, value, split_bin (binned) or
    # thr (adaptive), na_left, is_split, gain, node_w
    for key in seen["trees"][0]:
        out[key] = np.concatenate([t[key] for t in seen["trees"]])
    for key in ("_feat", "_thr", "_value"):
        out[key] = np.asarray(getattr(est.model, key))
    return est, out


def _fresh_programs():
    gbm._compiled_chunk.cache_clear()
    streaming._apply_leaf.clear_cache()
    jax.clear_caches()


@pytest.mark.parametrize("depth", [5, 6])
@pytest.mark.parametrize("algo", ["gbm", "xgboost"])
def test_a_train_exports_the_same_bytes_as_with_the_gather(
        monkeypatch, algo, depth):
    from h2o3_tpu.models.xgboost import H2OXGBoostEstimator

    def make():
        if algo == "gbm":
            return gbm.H2OGradientBoostingEstimator(
                ntrees=6, max_depth=depth, seed=30, distribution="bernoulli",
                min_rows=2, packed_codes=True)
        return H2OXGBoostEstimator(
            ntrees=6, max_depth=depth, seed=30, distribution="bernoulli",
            tree_method="hist", max_bins=64, packed_codes=True)

    _fresh_programs()
    est, selected = _export(make, monkeypatch)
    pc = est.model.output["packed_codes"]
    assert (pc["leaf_lookup"], pc["n_nodes"]) == (
        "select", 2 ** (depth + 1) - 1)
    monkeypatch.setattr(tree, "NODE_SELECT_MAX", 0)    # table[nid] again
    _fresh_programs()
    try:
        est, gathered = _export(make, monkeypatch)
        assert est.model.output["packed_codes"]["leaf_lookup"] == "gather"
    finally:
        _fresh_programs()
    for key, want in gathered.items():
        np.testing.assert_array_equal(_bits(selected[key]), _bits(want),
                                      err_msg=key)
