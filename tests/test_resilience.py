"""Fault-tolerant pipelines (ISSUE 6): deterministic fault injection,
retry/backoff, checkpoint/resume bit-parity, OOM graceful degradation,
the serve circuit breaker and job supervision.

Every test configures faults explicitly and clears them on exit (the
autouse fixture makes a leaked spec impossible); the no-op guard
asserts the unset path stays checked-no-op, the same method as the
PR-4 telemetry overhead guard."""
import os
import statistics
import time

import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import dkv, faults, resilience, serve, telemetry
from h2o3_tpu.estimators import (H2OGradientBoostingEstimator,
                                 H2ORandomForestEstimator)


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.configure(None)
    yield
    faults.configure(None)
    serve.shutdown_all()


def _reg_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    cols = {"x1": rng.normal(size=n), "x2": rng.normal(size=n),
            "x3": rng.normal(size=n)}
    cols["y"] = cols["x1"] * 2.0 - cols["x2"] + rng.normal(size=n) * 0.1
    return h2o.Frame.from_numpy(cols)


def _cls_frame(n=400, seed=0):
    rng = np.random.default_rng(seed)
    cols = {"x1": rng.normal(size=n), "x2": rng.normal(size=n)}
    cols["y"] = np.array(["no", "yes"], dtype=object)[
        (cols["x1"] + rng.normal(size=n) * 0.3 > 0).astype(int)]
    return h2o.Frame.from_numpy(cols)


def _tree_arrays(model):
    import jax
    return {k: np.asarray(jax.device_get(getattr(model, k)))
            for k in ("_feat", "_thr", "_na_left", "_is_split", "_value")}


def _assert_trees_equal(a, b):
    ta, tb = _tree_arrays(a), _tree_arrays(b)
    for k in ta:
        assert ta[k].shape == tb[k].shape, k
        assert (ta[k] == tb[k]).all(), f"{k} differs"
    assert float(np.asarray(a.f0).reshape(-1)[0]) == \
        float(np.asarray(b.f0).reshape(-1)[0])


# --------------------------------------------------- spec + gating

def test_fault_spec_parsing_and_determinism():
    faults.configure("h2d:every=3:exc=Unavailable:times=2,"
                     "execute@train:every=1:exc=ResourceExhausted:after=5")
    rules = faults.describe()
    assert rules[0]["site"] == "h2d" and rules[0]["every"] == 3
    assert rules[0]["times"] == 2 and rules[0]["exc"] == "Unavailable"
    assert rules[1]["pipeline"] == "train" and rules[1]["after"] == 5
    # deterministic: 3rd and 6th checks fire, then the rule exhausts
    fired = []
    for i in range(12):
        try:
            faults.check("h2d")
            fired.append(False)
        except faults.Unavailable:
            fired.append(True)
    assert fired == [False, False, True, False, False, True] + [False] * 6
    with pytest.raises(ValueError):
        faults.configure("h2d:bogus_option=1")
    faults.configure(None)
    assert faults.ACTIVE is None and faults.spec() is None


def test_fault_hooks_checked_noop_when_unset():
    """The overhead contract (same method as the telemetry ns-budget
    guard): with no spec configured the call-site gate is one module
    attribute load + branch, and even an unguarded check() returns
    immediately."""
    faults.configure(None)
    N = 20_000

    def per_call_ns():
        t0 = time.perf_counter_ns()
        for _ in range(N):
            if faults.ACTIVE:
                faults.check("h2d")
        return (time.perf_counter_ns() - t0) / N

    gate_ns = statistics.median(per_call_ns() for _ in range(5))
    assert gate_ns < 2_000, f"unset fault gate too slow: {gate_ns:.0f}ns"


# --------------------------------------------------- fault matrix

def test_ingest_h2d_fault_recovers():
    """ingest × h2d: every chunk upload hiccup retries with backoff and
    the parse still produces correct data."""
    before = telemetry.registry().value("h2o3_retry_total",
                                        {"site": "h2d"})
    faults.configure("h2d:every=3:exc=Unavailable:times=3")
    fr = _reg_frame(n=600, seed=3)
    assert fr.nrow == 600
    col = fr.vec("x1").to_numpy()
    assert np.isfinite(col).all()
    after = telemetry.registry().value("h2o3_retry_total",
                                       {"site": "h2d"})
    assert after > before, "no retry was recorded"


def test_train_transient_fault_retries_bit_identical():
    """train × {compile, execute}: transient faults retry and the final
    model is BIT-identical to the fault-free run."""
    fr = _reg_frame()
    a = H2OGradientBoostingEstimator(ntrees=6, max_depth=3, seed=7)
    a.train(y="y", training_frame=fr)
    for site in ("compile", "execute"):
        faults.configure(f"{site}@train:every=1:times=2:exc=Unavailable")
        b = H2OGradientBoostingEstimator(ntrees=6, max_depth=3, seed=7)
        b.train(y="y", training_frame=fr)
        faults.configure(None)
        _assert_trees_equal(a.model, b.model)
    assert telemetry.registry().value(
        "h2o3_retry_total", {"site": "train.execute"}) > 0
    # recovery events are visible on /metrics
    text = telemetry.prometheus_text()
    assert "h2o3_retry_total" in text
    assert "h2o3_fault_injected_total" in text


def test_train_collective_fault_retries_on_multishard_mesh():
    """train × collective (ISSUE 7): a transient ICI failure on the
    per-level histogram-psum seam retries via resilience.retry_transient
    and the model stays bit-identical to the fault-free run. The
    ``collective`` site only arms when the mesh has >1 data shard — the
    suite's 8-virtual-device mesh qualifies."""
    import jax
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards
    if n_data_shards(current_mesh()) < 2:
        pytest.skip("needs a multi-data-shard mesh")
    fr = _reg_frame(seed=5)
    a = H2OGradientBoostingEstimator(ntrees=6, max_depth=3, seed=7)
    a.train(y="y", training_frame=fr)
    before = telemetry.registry().value("h2o3_retry_total",
                                        {"site": "train.execute"})
    faults.configure("collective@train:every=1:times=2:exc=Unavailable")
    b = H2OGradientBoostingEstimator(ntrees=6, max_depth=3, seed=7)
    b.train(y="y", training_frame=fr)
    faults.configure(None)
    _assert_trees_equal(a.model, b.model)
    after = telemetry.registry().value("h2o3_retry_total",
                                       {"site": "train.execute"})
    assert after > before, "collective fault did not exercise the retry"
    # on a SINGLE-shard mesh the collective site never fires (there is
    # no ICI to fail): same spec, single-device mesh, zero injections
    from h2o3_tpu.parallel.mesh import make_mesh, set_mesh
    old = current_mesh()
    set_mesh(make_mesh(n_data=1, devices=jax.devices()[:1]))
    try:
        faults.configure("collective@train:every=1:exc=Unavailable")
        fr1 = _reg_frame(seed=5)
        c = H2OGradientBoostingEstimator(ntrees=3, max_depth=3, seed=7)
        c.train(y="y", training_frame=fr1)
        assert faults.fired_total() == 0
    finally:
        faults.configure(None)
        set_mesh(old)


def test_serve_transient_fault_single_retry():
    """serve × execute: one transient device failure recovers via the
    single in-batch retry — the client never sees it and the circuit
    stays closed."""
    fr = _cls_frame()
    m = H2OGradientBoostingEstimator(ntrees=3, max_depth=2, seed=1)
    m.train(y="y", training_frame=fr)
    dkv.put("res_m_retry", "model", m.model)
    dep = serve.deploy("res_m_retry", max_delay_ms=1.0)
    try:
        faults.configure("execute@serve:key=res_m_retry:every=1:times=1"
                         ":exc=Unavailable")
        out = dep.predict_rows([{"x1": 0.5, "x2": -0.2}])
        assert out[0]["label"] in ("no", "yes")
        assert dep.stats.retries == 1
        assert dep.breaker.state == "closed"
    finally:
        serve.undeploy("res_m_retry")
        dkv.remove("res_m_retry")


# --------------------------------------------------- OOM degradation

def test_oom_degrades_dense_to_streamed():
    """A device OOM mid-train degrades to the streamed resident-window
    path (warn + h2o3_degrade_total) and the train COMPLETES."""
    fr = _reg_frame()
    before = telemetry.registry().value("h2o3_degrade_total",
                                        {"algo": "gbm"})
    faults.configure("execute@train:every=1:times=1:exc=ResourceExhausted")
    est = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=5)
    est.train(y="y", training_frame=fr)
    model = est.model
    assert model.output.get("streamed") is True
    assert model.ntrees_built == 4
    assert np.isfinite(model.training_metrics.mse)
    after = telemetry.registry().value("h2o3_degrade_total",
                                       {"algo": "gbm"})
    assert after == before + 1
    # degraded model still predicts
    pred = model.predict(fr).vec("predict").to_numpy()
    assert np.isfinite(pred).all()


def test_oom_without_streamed_fallback_reraises():
    """Configs the streamed path cannot take (multinomial) surface the
    ORIGINAL OOM instead of a confusing NotImplementedError."""
    rng = np.random.default_rng(2)
    cols = {"x1": rng.normal(size=300), "x2": rng.normal(size=300)}
    cols["y"] = np.array(["a", "b", "c"], dtype=object)[
        rng.integers(0, 3, 300)]
    fr = h2o.Frame.from_numpy(cols)
    faults.configure("execute@train:every=1:times=1:exc=ResourceExhausted")
    est = H2OGradientBoostingEstimator(ntrees=3, max_depth=2, seed=5)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        est.train(y="y", training_frame=fr)


# --------------------------------------------------- checkpoint/resume

def test_gbm_mid_train_kill_then_resume_bit_identical(tmp_path):
    """The acceptance scenario: transient faults every Nth H2D PLUS one
    mid-train kill — training fails, the in-training checkpoint holds
    the committed prefix, and resuming from it yields a model
    BIT-identical to the fault-free run."""
    fr = _reg_frame()
    kw = dict(ntrees=9, max_depth=3, seed=11, learn_rate=0.2)
    a = H2OGradientBoostingEstimator(**kw)
    a.train(y="y", training_frame=fr)

    ckdir = str(tmp_path / "ckpts")
    # kill the 3rd chunk dispatch (after=2 execute checks pass first);
    # chunks are 3 trees (tree_interval), so trees 1-6 commit
    faults.configure("execute@train:every=1:after=2:times=1:exc=Fatal")
    b = H2OGradientBoostingEstimator(
        in_training_checkpoints_dir=ckdir,
        in_training_checkpoints_tree_interval=3, **kw)
    with pytest.raises(RuntimeError, match="FATAL"):
        b.train(y="y", training_frame=fr)
    faults.configure(None)
    ckpts = sorted(os.listdir(ckdir))
    assert ckpts, "mid-train kill left no checkpoint"
    # a KILLED train keeps its DKV entry (that is the recovery state);
    # clean it here so the module teardown stays tidy
    killed_keys = [k for k in dkv.keys("model") if k.endswith("_ckpt")]
    assert killed_keys, "killed train left no DKV checkpoint"
    for k in killed_keys:
        dkv.remove(k)
    latest = os.path.join(ckdir, ckpts[-1])

    # resume: total ntrees unchanged; also inject a transient H2D fault
    # so the resume itself exercises the retry path
    faults.configure("h2d:every=5:times=1:exc=Unavailable")
    c = H2OGradientBoostingEstimator(checkpoint=latest, **kw)
    c.train(y="y", training_frame=fr)
    _assert_trees_equal(a.model, c.model)
    # predictions bit-match too
    pa = a.model.predict(fr).vec("predict").to_numpy()
    pc = c.model.predict(fr).vec("predict").to_numpy()
    assert (np.asarray(pa) == np.asarray(pc)).all()


def test_gbm_in_training_checkpoints_lifecycle(tmp_path):
    """Checkpoints land on disk at the tree_interval cadence with
    resume state attached; the transient DKV <key>_ckpt entry is
    dropped once the train COMPLETES (the finished model supersedes
    it — no phantom partial models accumulate in the store)."""
    fr = _reg_frame()
    ckdir = str(tmp_path / "dk")
    est = H2OGradientBoostingEstimator(
        ntrees=6, max_depth=2, seed=3,
        in_training_checkpoints_dir=ckdir,
        in_training_checkpoints_tree_interval=2)
    est.train(y="y", training_frame=fr)
    files = sorted(os.listdir(ckdir))
    assert [f for f in files if f.endswith("_t2.zip")]
    assert [f for f in files if f.endswith("_t6.zip")]
    # a completed train leaves no DKV checkpoint entry behind
    assert dkv.get_opt(f"{est.model.key}_ckpt") is None
    # the durable artifact carries the resume state
    ck = h2o.load_model(os.path.join(
        ckdir, [f for f in files if f.endswith("_t2.zip")][0]))
    assert ck.ntrees_built == 2
    assert getattr(ck, "_resume_margin", None) is not None
    assert getattr(ck, "_resume_sig", None) is not None
    # continue-on-DIFFERENT-data: the stale margin must NOT be reused
    # (signature mismatch → recompute from trees, train still works)
    fr2 = _reg_frame(n=fr.nrow, seed=99)
    res = H2OGradientBoostingEstimator(ntrees=4, max_depth=2, seed=3,
                                       checkpoint=ck)
    res.train(y="y", training_frame=fr2)
    assert res.model.ntrees_built == 4


def test_drf_checkpoint_resume_bit_identical(tmp_path):
    fr = _cls_frame()
    kw = dict(ntrees=8, max_depth=4, seed=5)
    a = H2ORandomForestEstimator(**kw)
    a.train(y="y", training_frame=fr)
    ckdir = str(tmp_path / "drf")
    b = H2ORandomForestEstimator(
        in_training_checkpoints_dir=ckdir,
        in_training_checkpoints_tree_interval=3, **kw)
    b.train(y="y", training_frame=fr)
    _assert_drf_equal(a.model, b.model)
    ck = [f for f in sorted(os.listdir(ckdir)) if "_t6" in f][0]
    c = H2ORandomForestEstimator(checkpoint=os.path.join(ckdir, ck), **kw)
    c.train(y="y", training_frame=fr)
    _assert_drf_equal(a.model, c.model)
    # resumed OOB accumulators → identical training (OOB) metrics
    assert a.model.training_metrics.auc == c.model.training_metrics.auc
    dkv.remove(f"{b.model.key}_ckpt")


def _assert_drf_equal(a, b):
    import jax
    for k in ("_feat", "_thr", "_value", "_is_split", "_na_left"):
        ea = np.asarray(jax.device_get(getattr(a, k)))
        eb = np.asarray(jax.device_get(getattr(b, k)))
        assert ea.shape == eb.shape and (ea == eb).all(), k


def test_checkpoint_params_are_real_not_compat():
    """The three fault-tolerance params moved out of the accepted-then-
    ignored warn inventory (the VERDICT-r5 blocker class)."""
    from h2o3_tpu.models.compat_params import COMPAT_PARAMS
    for p in ("checkpoint", "in_training_checkpoints_dir",
              "in_training_checkpoints_tree_interval"):
        assert p not in COMPAT_PARAMS.get("gbm", {}), p
    assert "checkpoint" not in COMPAT_PARAMS.get("drf", {})
    # and they are real defaults on the builders
    from h2o3_tpu.models.drf import DRF_DEFAULTS
    from h2o3_tpu.models.gbm import GBM_DEFAULTS
    assert "checkpoint" in GBM_DEFAULTS and "checkpoint" in DRF_DEFAULTS
    assert "in_training_checkpoints_dir" in GBM_DEFAULTS


def test_checkpoint_validation_rejects_mismatch(tmp_path):
    fr = _reg_frame()
    a = H2OGradientBoostingEstimator(ntrees=4, max_depth=3, seed=1)
    a.train(y="y", training_frame=fr)
    path = h2o.save_model(a.model, str(tmp_path), force=True)
    # ntrees must exceed the checkpoint's
    with pytest.raises(RuntimeError, match="must exceed"):
        H2OGradientBoostingEstimator(
            ntrees=4, max_depth=3, seed=1, checkpoint=path
        ).train(y="y", training_frame=fr)
    with pytest.raises(RuntimeError, match="max_depth"):
        H2OGradientBoostingEstimator(
            ntrees=8, max_depth=4, seed=1, checkpoint=path
        ).train(y="y", training_frame=fr)


# --------------------------------------------------- serve circuit

def test_circuit_breaker_open_halfopen_close_lifecycle():
    """Persistent device failure → open (fast 503 + Retry-After) while a
    healthy deployment keeps serving; clearing the fault → half-open
    probe → closed."""
    fr = _cls_frame()
    m = H2OGradientBoostingEstimator(ntrees=3, max_depth=2, seed=1)
    m.train(y="y", training_frame=fr)
    dkv.put("cb_sick", "model", m.model)
    dkv.put("cb_ok", "model", m.model)
    sick = serve.deploy("cb_sick", circuit_failures=2,
                        circuit_open_ms=250, max_delay_ms=1.0)
    ok = serve.deploy("cb_ok", max_delay_ms=1.0)
    row = {"x1": 0.5, "x2": -0.2}
    try:
        faults.configure("execute@serve:key=cb_sick:every=1:exc=Internal")
        opened = False
        for _ in range(6):
            try:
                sick.predict_rows([row], timeout_ms=500)
            except serve.ServeCircuitOpenError as e:
                opened = True
                assert e.retry_after_s > 0
                assert serve.ServeCircuitOpenError.http_status == 503
                break
            except Exception:   # noqa: BLE001 — device errors expected
                pass
        assert opened and sick.breaker.state == "open"
        # open = FAST failure: no queueing, sub-tick latency
        t0 = time.perf_counter()
        with pytest.raises(serve.ServeCircuitOpenError):
            sick.predict_rows([row], timeout_ms=5000)
        assert time.perf_counter() - t0 < 0.1
        # the healthy deployment is untouched by its neighbor's faults
        assert ok.predict_rows([row])[0]["label"] in ("no", "yes")
        assert ok.breaker.state == "closed"
        # health is visible in /3/Serve/stats
        snap = serve.stats()["models"]
        assert snap["cb_sick"]["circuit"]["state"] == "open"
        assert snap["cb_sick"]["circuit"]["open_count"] == 1
        assert snap["cb_ok"]["circuit"]["state"] == "closed"
        # and on the metrics surface (2 = open)
        assert sick.stats._reg.value("h2o3_circuit_state",
                                     {"model": "cb_sick"}) == 2
        # fault clears → cooldown expiry admits a probe that closes it
        faults.configure(None)
        time.sleep(0.3)
        assert sick.predict_rows([row])[0]["label"] in ("no", "yes")
        assert sick.breaker.state == "closed"
    finally:
        serve.undeploy("cb_sick")
        serve.undeploy("cb_ok")
        dkv.remove("cb_sick")
        dkv.remove("cb_ok")


def test_circuit_halfopen_failed_probe_reopens():
    from h2o3_tpu.serve.circuit import CircuitBreaker
    cb = CircuitBreaker(model="probe_t", failure_threshold=2,
                        open_secs=0.05)
    cb.record_failure()
    cb.record_failure()
    assert cb.state == "open"
    assert cb.allow_request() is not None          # still cooling down
    time.sleep(0.06)
    assert cb.allow_request() is None              # the probe
    assert cb.state == "half_open"
    assert cb.allow_request() is not None          # probe in flight
    cb.record_failure()                            # probe fails
    assert cb.state == "open"
    time.sleep(0.06)
    assert cb.allow_request() is None
    cb.record_success()
    assert cb.state == "closed"


# --------------------------------------------------- deploy error path

def test_failed_deploy_releases_pin_model_stays_deletable():
    """Satellite regression: a deploy that fails AFTER
    dkv.get_and_read_lock must release its pin — the model stays
    deletable; a failed RE-deploy over a live deployment keeps the
    live pin."""
    fr = _reg_frame()
    m = H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1)
    m.train(y="y", training_frame=fr)
    dkv.put("pin_m", "model", m.model)
    try:
        with pytest.raises(ValueError, match="max_batch"):
            serve.deploy("pin_m", max_batch=10 ** 6)
        dkv.check_unlocked("pin_m")        # raises if the pin leaked
        # live deployment: failed re-deploy keeps the existing pin
        serve.deploy("pin_m")
        with pytest.raises(ValueError, match="max_batch"):
            serve.deploy("pin_m", max_batch=10 ** 6)
        with pytest.raises(dkv.KeyLockedError):
            dkv.check_unlocked("pin_m")
        serve.undeploy("pin_m")
        dkv.check_unlocked("pin_m")
        assert dkv.remove("pin_m")
    finally:
        serve.undeploy("pin_m")
        dkv.remove("pin_m")


# --------------------------------------------------- job supervision

def test_job_structured_failure_info():
    from h2o3_tpu import jobs
    from h2o3_tpu.api import schemas

    def boom(job):
        with telemetry.span("train.unit_test"):
            raise ValueError("synthetic failure for structured info")

    j = jobs.Job("structured failure probe")
    j.run(boom)
    assert j.status == jobs.FAILED
    assert j.exception_type == "ValueError"
    assert "synthetic failure" in j.exception_msg
    # the INNERMOST span the exception unwound through is the stage
    assert j.failed_stage == "train.unit_test"
    body = schemas.job_v3(j)
    assert body["exception_type"] == "ValueError"
    assert "synthetic failure" in body["exception_msg"]
    assert body["failed_stage"] == "train.unit_test"
    assert body["status"] == "FAILED"
    assert "stalled" in body and "failed_stage" in body


def test_watchdog_enforces_max_runtime(monkeypatch):
    from h2o3_tpu import jobs
    monkeypatch.setenv("H2O3_JOB_WATCH_TICK", "0.05")
    j = jobs.Job("runaway", max_runtime_secs=0.15)

    def loop(job):
        while not job.cancel_requested:
            time.sleep(0.02)
        return "stopped"

    j.run(loop, background=True)
    j._thread.join(3.0)
    assert j.cancel_requested
    assert j.status == jobs.CANCELLED
    assert "max_runtime_secs" in (j.cancel_reason or "")


def test_watchdog_marks_stalled_jobs(monkeypatch):
    from h2o3_tpu import jobs
    monkeypatch.setenv("H2O3_JOB_WATCH_TICK", "0.05")
    j = jobs.Job("staller", stall_timeout_secs=0.1)
    done = []

    def body(job):
        time.sleep(0.4)            # no progress heartbeats
        for _ in range(5):         # heartbeats resume
            job.set_progress(0.9)
            time.sleep(0.02)
        done.append(True)

    j.run(body, background=True)
    deadline = time.time() + 2.0
    saw_stall = False
    while time.time() < deadline and not saw_stall:
        saw_stall = j.stalled
        time.sleep(0.02)
    assert saw_stall, "watchdog never marked the silent job stalled"
    j._thread.join(3.0)
    assert done and j.status == jobs.DONE
    assert not j.stalled           # cleared when the heartbeat resumed


def test_streamed_train_cancel_propagates(monkeypatch):
    """Cancel lands between streamed tree levels via the
    StreamedChunks.cancel_check hook and the job finalizes as
    CANCELLED with the committed trees."""
    from h2o3_tpu import memman
    fr = _reg_frame(n=1200, seed=4)
    # force streaming: tiny device budget, held against whole arrays
    monkeypatch.setattr(memman.manager(), "budget", 60_000)
    monkeypatch.setattr(memman.manager(), "shards", 1)
    est = H2OGradientBoostingEstimator(ntrees=50, max_depth=3, seed=2)
    est.train(y="y", training_frame=fr, background=True)
    est.job.cancel()
    # scheduler-run jobs own no thread — join() waits on the terminal
    # latch (and raises only on FAILED)
    est.job.join(30.0)
    assert est.job.status in ("CANCELLED", "DONE")


# --------------------------------------------------- persist retries

def test_persist_load_model_retries_flaky_read(tmp_path):
    fr = _reg_frame()
    est = H2OGradientBoostingEstimator(ntrees=2, max_depth=2, seed=1)
    est.train(y="y", training_frame=fr)
    path = h2o.save_model(est.model, str(tmp_path), force=True)
    faults.configure("persist:every=1:times=1:exc=IOError")
    m = h2o.load_model(path)       # first attempt faults, retry loads
    assert m.ntrees_built == 2
    assert telemetry.registry().value(
        "h2o3_retry_total", {"site": "persist.load_model"}) > 0


def test_persist_uri_download_retries(monkeypatch, tmp_path):
    """localize() retries a flaky remote download through the shared
    backoff helper."""
    from h2o3_tpu.ingest import persist_uri
    monkeypatch.setattr(persist_uri, "_CACHE_DIR", str(tmp_path))
    calls = {"n": 0}

    def flaky_urlretrieve(uri, tmp):
        calls["n"] += 1
        if calls["n"] == 1:
            raise ConnectionResetError("connection reset by peer")
        with open(tmp, "w") as f:
            f.write("a,b\n1,2\n")

    monkeypatch.setattr(persist_uri.urllib.request, "urlretrieve",
                        flaky_urlretrieve)
    out = persist_uri.localize("http://unit.test/flaky.csv")
    assert os.path.exists(out) and calls["n"] == 2
    with open(out) as f:
        assert f.read().startswith("a,b")


def test_compressed_ingest_decompress_retries(tmp_path):
    """The ``decompress`` fault seam: a transient storage hiccup on the
    compressed-ingest read retries through the shared backoff and the
    import still succeeds bit-for-bit."""
    from h2o3_tpu.ingest.compress import gzip_compress_members
    csv = "a,b\n" + "".join(f"{i},{i * 0.5}\n" for i in range(200))
    gz = tmp_path / "t.csv.gz"
    gz.write_bytes(gzip_compress_members(csv.encode(), member_bytes=256))
    faults.configure("decompress@ingest:every=1:times=1:exc=IOError")
    fr = h2o.import_file(str(gz))       # first attempt faults, retry wins
    assert fr.nrow == 200
    assert np.asarray(fr.vec("b").to_numpy()).reshape(-1)[3] == 1.5
    assert telemetry.registry().value(
        "h2o3_retry_total", {"site": "ingest.decompress"}) > 0
    assert faults.fired_total() == 1


def test_transient_classification():
    assert resilience.is_transient(faults.Unavailable("UNAVAILABLE: x"))
    assert resilience.is_transient(RuntimeError("INTERNAL: device halt"))
    assert not resilience.is_transient(
        faults.ResourceExhausted("RESOURCE_EXHAUSTED"))
    assert not resilience.is_transient(faults.Fatal("FATAL"))
    assert resilience.is_oom(RuntimeError("RESOURCE_EXHAUSTED: oom"))
    assert resilience.is_transient_io(IOError("disk hiccup"))
    assert not resilience.is_transient_io(FileNotFoundError("gone"))


def test_retry_transient_backoff_and_counters():
    calls = {"n": 0}
    sleeps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise faults.Unavailable("UNAVAILABLE: injected")
        return "ok"

    out = resilience.retry_transient(flaky, site="unit.test",
                                     sleep=sleeps.append)
    assert out == "ok" and calls["n"] == 3 and len(sleeps) == 2
    assert telemetry.registry().value(
        "h2o3_retry_total", {"site": "unit.test"}) == 2
    # non-transient propagates immediately
    with pytest.raises(faults.Fatal):
        resilience.retry_transient(
            lambda: (_ for _ in ()).throw(faults.Fatal("FATAL")),
            site="unit.test2", sleep=sleeps.append)


# --------------------------------------------------- REST surface

def test_faults_rest_roundtrip():
    from h2o3_tpu.api.server import H2OApiServer
    srv = H2OApiServer(port=0)
    srv.start()
    try:
        import json
        import urllib.request
        base = f"http://127.0.0.1:{srv.port}"

        def call(method, path, data=None):
            req = urllib.request.Request(base + path, method=method,
                                         data=data)
            with urllib.request.urlopen(req) as r:
                return json.loads(r.read())

        out = call("POST", "/3/Faults?spec=h2d:every=9:exc=Unavailable")
        assert out["spec"].startswith("h2d:every=9")
        assert out["rules"][0]["every"] == 9
        out = call("GET", "/3/Faults")
        assert out["rules"][0]["site"] == "h2d"
        out = call("DELETE", "/3/Faults")
        assert out["spec"] is None
        assert faults.ACTIVE is None
    finally:
        srv.stop()
