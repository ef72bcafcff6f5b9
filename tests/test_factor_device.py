"""A numeric Vec's factor made on the device (``frame/factor.py``).

``Vec.asfactor`` gives the domain and codes of the host formula, bit for
bit, on both paths: the range pass for a device payload of small integer
range, the host formula for every other column, for strings, host copies
and a column of fewer rows than ``factor.DEVICE_MIN_ROWS``. Each factor
counts its path in ``h2o3_factor_total{path}``; the range path keeps the
source's padded length and row sharding on a mesh, and a train on a
numeric 0/1 response grows the trees of a train on the same column
factored beforehand. The tests' columns are small, so the row floor is
held at 0 except where a case tests it.
"""
import jax
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import telemetry
from h2o3_tpu.frame import factor
from h2o3_tpu.frame.vec import ENUM_NA, T_ENUM, T_REAL, T_STR, Vec
from h2o3_tpu.parallel.mesh import (current_mesh, data_sharding, make_mesh,
                                     padded_len)

ROWS = 1000


@pytest.fixture(autouse=True)
def _telemetry_on():
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    yield
    telemetry.set_enabled(was)


@pytest.fixture(autouse=True)
def _any_rows_on_the_device(monkeypatch):
    monkeypatch.setattr(factor, "DEVICE_MIN_ROWS", 0)


def _host_formula(raw):
    """(domain, codes) of the host formula ``Vec.asfactor`` always had."""
    if raw.dtype.kind == "O":
        isna = np.array([x is None or (isinstance(x, float) and np.isnan(x))
                         or x == "" for x in raw])
        vals = np.array(["" if m else str(v) for v, m in zip(raw, isna)])
        domain = np.unique(vals[~isna])
        codes = np.searchsorted(domain, vals).astype(np.int32)
        codes[isna] = ENUM_NA
        return tuple(str(d) for d in domain), codes
    finite = np.isfinite(raw)
    vals = np.unique(raw[finite])
    domain = tuple(str(int(v)) if float(v).is_integer() else str(v)
                   for v in vals)
    codes = np.searchsorted(vals, raw).astype(np.int32)
    codes[~finite] = ENUM_NA
    return domain, codes


def _rng():
    return np.random.default_rng(40)


# name -> (values, how the Vec is made, the path that makes its factor)
CASES = {
    "binary_labels": (lambda: _rng().integers(0, 2, ROWS), "device",
                      "device_range"),
    "class_ids": (lambda: _rng().integers(0, 10, ROWS), "device",
                  "device_range"),
    "negative_ints": (lambda: _rng().integers(-50, -3, ROWS), "device",
                      "device_range"),
    "range_just_over_the_bound": (
        lambda: np.r_[np.arange(factor.RANGE_MAX + 1),
                      _rng().integers(0, factor.RANGE_MAX + 1, ROWS)],
        "device", "host"),
    "non_integral": (lambda: _rng().normal(size=ROWS), "device", "host"),
    "infinities": (lambda: np.where(_rng().random(ROWS) < 0.2, np.inf, np.where(
        _rng().random(ROWS) < 0.2, -np.inf, _rng().integers(0, 3, ROWS))),
        "device", "device_range"),
    "signed_zeros": (lambda: np.tile([0.0, -0.0, 1.0, -0.0, 2.0], 50),
                     "device", "device_range"),
    "signed_zeros_beside_a_fraction": (
        lambda: np.tile([0.0, -0.0, 0.5, -0.0, -1.5], 50), "device", "host"),
    "all_na": (lambda: np.full(ROWS, np.nan), "device", "device_range"),
    "constant": (lambda: np.full(ROWS, 7.0), "device", "device_range"),
    "integral_past_2_24": (
        lambda: np.r_[[2.0 ** 25, 2.0 ** 25 + 2, -3.0], _rng().integers(
            0, 5, ROWS)], "device", "host"),
    "fewer_rows_than_the_device_takes": (
        lambda: _rng().integers(0, 2, ROWS), "device_few_rows", "host"),
    "exact_host_copy": (
        lambda: np.r_[[2 ** 25 + 1, 7], _rng().integers(0, 5, ROWS)],
        "host_copy", "host"),
    "strings": (lambda: np.array(["b", None, "a", "", "c", "a"] * 40,
                                 dtype=object), "strings", "host"),
}


def _with_nas(values):
    """A twentieth of the rows NA, where the case is numeric."""
    if values.dtype.kind == "O":
        return values
    out = np.asarray(values, dtype=np.float64).copy()
    out[_rng().random(len(out)) < 0.05] = np.nan
    return out


def _vec(values, how, mesh):
    n = len(values)
    if how == "strings":
        return Vec.from_numpy(values, vtype=T_STR, mesh=mesh)
    if how == "host_copy":
        vec = Vec.from_numpy(values, mesh=mesh)
        assert vec.host_data is not None
        return vec
    # device-only, as the benchmark's label: NaN pad rows, no host copy
    padded = np.full(padded_len(n, mesh), np.nan, np.float32)
    padded[:n] = values
    return Vec(jax.device_put(padded, data_sharding(mesh)), n, T_REAL)


def _count(path):
    return telemetry.registry().value("h2o3_factor_total", {"path": path})


@pytest.mark.parametrize("devices", [1, 8], ids=["one_device", "mesh8"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_factor_is_the_host_formulas_bit_for_bit(case, devices,
                                                     monkeypatch):
    make, how, want_path = CASES[case]
    values = make()
    if how.startswith("device"):
        values = _with_nas(values)
    if how == "device_few_rows":
        monkeypatch.setattr(factor, "DEVICE_MIN_ROWS", len(values) + 1)
    mesh = make_mesh(n_data=devices, devices=jax.devices()[:devices])
    vec = _vec(values, how, mesh)
    want_domain, want_codes = _host_formula(
        values if how == "strings" else vec.to_numpy())
    before = _count(want_path)
    out, path = vec.factor()
    assert path == want_path
    assert _count(want_path) == before + 1
    assert out.type == T_ENUM and out.nrow == vec.nrow
    assert out.domain == want_domain
    codes = np.asarray(out.data)
    np.testing.assert_array_equal(codes[:vec.nrow], want_codes)
    assert (codes[vec.nrow:] == ENUM_NA).all()
    assert vec.asfactor().domain == want_domain
    if path == "device_range":
        # the source's padded length and row sharding, on one device or 8
        assert out.data.shape == vec.data.shape
        assert out.data.sharding.is_equivalent_to(vec.data.sharding, 1)
        assert len(out.data.sharding.device_set) == devices


def test_an_enum_is_its_own_factor_and_counts_nothing():
    vec = Vec.from_numpy(np.array([0, 1, 1]), T_ENUM, ["n", "y"])
    before = {p: _count(p) for p in ("device_range", "host")}
    assert vec.factor() == (vec, "none")
    assert {p: _count(p) for p in before} == before


def test_a_train_on_a_numeric_label_grows_the_trees_of_one_factored_before():
    """A GBM on a numeric 0/1 response (the benchmark's label: device-only,
    factored by the range pass inside ``train.spec``) against one on the
    same column factored by the host formula beforehand: the same
    response domain and bit-identical trees, and ``train.spec`` names the
    path (``none`` for the enum response)."""
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    n = 4096
    rng = np.random.default_rng(40)
    X = rng.normal(size=(n, 4)).astype(np.float32)
    y = (X[:, 0] + X[:, 1] * X[:, 2] + 0.3 * rng.normal(size=n) > 0)
    y = y.astype(np.float32)
    y[::97] = np.nan
    names = ["a", "b", "c", "d", "y"]
    feats = [Vec.from_numpy(X[:, j]) for j in range(4)]
    numeric = _vec(y, "device", current_mesh())
    domain, codes = _host_formula(y)
    models, paths = [], []
    for label in (numeric, Vec.from_numpy(codes, T_ENUM, domain)):
        est = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1,
                                           distribution="bernoulli")
        telemetry.clear_spans()
        est.train(y="y", training_frame=h2o.Frame(names, feats + [label]))
        models.append(est.model)
        paths += [s.attrs["response_factor"]
                  for s in telemetry.finished_spans() if s.name == "train.spec"]
    assert paths == ["device_range", "none"]
    a, b = models
    assert tuple(a.response_domain) == tuple(b.response_domain) == (
        "0", "1")
    for name in ("_feat", "_thr", "_na_left", "_is_split", "_value",
                 "_node_w"):
        np.testing.assert_array_equal(np.asarray(getattr(a, name)),
                                      np.asarray(getattr(b, name)),
                                      err_msg=name)
