"""ADVICE r5 satellites (ISSUE 15): gamma canonical default link +
re-audited solver guards, reference-orientation DL initial weights,
parse_xls empty-sheet/malformed-archive errors."""
import io
import zipfile

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu.models.glm import (H2OGeneralizedLinearEstimator,
                                 _make_family)


def _gamma_frame(n=2500, seed=0):
    rng = np.random.default_rng(seed)
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    mu = 1.0 / np.clip(0.8 + 0.25 * x1 - 0.2 * x2, 0.2, None)
    y = rng.gamma(6.0, mu / 6.0)
    return h2o.Frame.from_numpy({"x1": x1, "x2": x2, "y": y})


def test_gamma_default_link_is_inverse():
    """GLMModel.java:803: gamma's default link is the canonical
    inverse, not log."""
    assert _make_family("gamma", {}).link_name == "inverse"
    # explicit links still honored
    assert _make_family("gamma", {"link": "log"}).link_name == "log"


def test_gamma_default_trains_guarded():
    """gamma at its (new) inverse default must converge — the halving
    guard keeps IRLS steps from pushing eta <= 0 (mu out of domain)."""
    fr = _gamma_frame()
    glm = H2OGeneralizedLinearEstimator(family="gamma", Lambda=[0.0],
                                        standardize=False)
    glm.train(y="y", training_frame=fr)
    coefs = glm.model.coef()
    assert all(np.isfinite(v) for v in coefs.values()), coefs
    pred = np.asarray(glm.model.predict(fr).vec("predict").to_numpy())
    assert np.all(np.isfinite(pred)) and np.all(pred > 0)
    assert glm.model.residual_deviance < glm.model.null_deviance


def test_gamma_lbfgs_guard_rekeyed():
    """_nll_mean's gamma closed form assumes LOG link: with the default
    now inverse, solver=L_BFGS must fall back to IRLSM at the default
    (same coefficients as an explicit IRLSM run) instead of silently
    optimizing the wrong objective — and still take L-BFGS at
    link=log (matching IRLSM's log-link fit)."""
    fr = _gamma_frame(seed=3)
    irlsm = H2OGeneralizedLinearEstimator(
        family="gamma", Lambda=[0.0], standardize=False, solver="IRLSM")
    irlsm.train(y="y", training_frame=fr)
    lbfgs = H2OGeneralizedLinearEstimator(
        family="gamma", Lambda=[0.0], standardize=False, solver="L_BFGS")
    lbfgs.train(y="y", training_frame=fr)
    ca, cb = irlsm.model.coef(), lbfgs.model.coef()
    for k in ca:
        assert abs(ca[k] - cb[k]) < 1e-6, (k, ca[k], cb[k])
    # log link: the closed form applies; L-BFGS matches IRLSM closely
    il = H2OGeneralizedLinearEstimator(
        family="gamma", link="log", Lambda=[0.0], standardize=False,
        solver="IRLSM")
    il.train(y="y", training_frame=fr)
    ll = H2OGeneralizedLinearEstimator(
        family="gamma", link="log", Lambda=[0.0], standardize=False,
        solver="L_BFGS")
    ll.train(y="y", training_frame=fr)
    for k in il.model.coef():
        assert abs(il.model.coef()[k] - ll.model.coef()[k]) < 5e-3, k


def test_gamma_streaming_guard_rekeyed(monkeypatch):
    """The guardless streamed IRLS loop only takes monotone-safe links:
    gamma's inverse default must fail fast there, gamma+log streams."""
    from h2o3_tpu import memman
    fr = _gamma_frame(n=6000, seed=4)
    # a forced budget, held against whole arrays (one shard) as
    # memman.reset(budget=...) holds one
    monkeypatch.setattr(memman.manager(), "budget", 60_000)
    monkeypatch.setattr(memman.manager(), "shards", 1)
    bad = H2OGeneralizedLinearEstimator(family="gamma", alpha=[0.0],
                                        Lambda=[0.0])
    with pytest.raises(RuntimeError, match="monotone-safe"):
        bad.train(y="y", training_frame=fr)
    ok = H2OGeneralizedLinearEstimator(family="gamma", link="log",
                                       alpha=[0.0], Lambda=[0.0])
    ok.train(y="y", training_frame=fr)
    assert all(np.isfinite(v) for v in ok.model.coef().values())


# ---------------- deeplearning initial-weights orientation --------------


def _dl_frame(n=400, seed=1):
    rng = np.random.default_rng(seed)
    x1, x2 = rng.normal(size=n), rng.normal(size=n)
    y = np.where(x1 + 0.5 * x2 > 0, "p", "q")
    return h2o.Frame.from_numpy({"x1": x1, "x2": x2, "y": y})


def test_dl_initial_weights_reference_orientation():
    """The reference supplies [out, in] matrices (hex/deeplearning
    Neurons): both orientations of the same non-square matrix must
    yield the SAME model."""
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator
    fr = _dl_frame()
    rng = np.random.default_rng(7)
    W0 = rng.normal(size=(2, 5)).astype(np.float32)   # [in=2, out=5]
    kw = dict(hidden=[5], epochs=1, seed=11, rate=0.05)
    native = H2ODeepLearningEstimator(initial_weights=[W0, None], **kw)
    native.train(y="y", training_frame=fr)
    ref = H2ODeepLearningEstimator(initial_weights=[W0.T, None], **kw)
    ref.train(y="y", training_frame=fr)
    pa = np.asarray(native.model.predict(fr).vec("pp").to_numpy())
    pb = np.asarray(ref.model.predict(fr).vec("pp").to_numpy())
    np.testing.assert_array_equal(pa, pb)


def test_dl_initial_weights_shape_error_names_convention():
    from h2o3_tpu.models.deeplearning import H2ODeepLearningEstimator
    fr = _dl_frame()
    est = H2ODeepLearningEstimator(
        hidden=[5], epochs=1,
        initial_weights=[np.zeros((3, 4), np.float32), None])
    with pytest.raises(RuntimeError, match=r"\[out, in\]"):
        est.train(y="y", training_frame=fr)


# ---------------- parse_xls error routing -------------------------------


def _xlsx_bytes(sheet_xml: str, shared_xml: str = None) -> bytes:
    ns = "http://schemas.openxmlformats.org/spreadsheetml/2006/main"
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w") as z:
        z.writestr("xl/worksheets/sheet1.xml",
                   f'<worksheet xmlns="{ns}"><sheetData>'
                   f"{sheet_xml}</sheetData></worksheet>")
        if shared_xml is not None:
            z.writestr("xl/sharedStrings.xml",
                       f'<sst xmlns="{ns}">{shared_xml}</sst>')
    return buf.getvalue()


def test_parse_xls_all_empty_rows_is_empty_sheet(tmp_path):
    from h2o3_tpu.ingest.formats import parse_xls
    p = tmp_path / "empty_rows.xlsx"
    p.write_bytes(_xlsx_bytes("<row/><row/><row/>"))
    with pytest.raises(ValueError, match="empty sheet"):
        parse_xls(str(p))


def test_parse_xls_malformed_shared_string_index(tmp_path):
    from h2o3_tpu.ingest.formats import parse_xls
    # index 5 points past a 1-entry shared-string table
    bad = ('<row><c r="A1" t="s"><v>5</v></c></row>'
           '<row><c r="A2"><v>1</v></c></row>')
    p = tmp_path / "bad_sst.xlsx"
    p.write_bytes(_xlsx_bytes(bad, shared_xml="<si><t>h</t></si>"))
    with pytest.raises(ValueError, match="malformed xlsx"):
        parse_xls(str(p))
    # non-integer index routes through the same error
    bad2 = '<row><c r="A1" t="s"><v>zz</v></c></row>'
    p2 = tmp_path / "bad_sst2.xlsx"
    p2.write_bytes(_xlsx_bytes(bad2, shared_xml="<si><t>h</t></si>"))
    with pytest.raises(ValueError, match="malformed xlsx"):
        parse_xls(str(p2))
