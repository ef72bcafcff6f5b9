"""Performance accounting plane (ISSUE 11).

Five layers:

1. **Cost capture**: ``cost_analysis`` flops agree with the analytic
   count for a known matmul, the scan ``scale=`` contract multiplies a
   loop body correctly, and the per-key cache never re-lowers.
2. **Roofline math**: MFU lands in (0, 1] under explicit peak
   overrides, ``peak_source``/``informational`` provenance is honest,
   and the regime classification follows the ridge point.
3. **Train/serve wiring**: GBM/DRF trains carry
   ``model.output["perf"]`` roofline points computed from executable
   costs x measured loop time; warm retrains report IDENTICAL
   executable costs without re-lowering; deployments expose a ``perf``
   block; ``GET /3/Telemetry/perf`` serves the summary.
4. **Cluster merge**: the new ``h2o3_achieved_*`` counters sum across
   process snapshots and the ``h2o3_mfu`` gauge gets process labels —
   the PR-8 plane carries the accounting with zero special cases.
5. **The bench-trajectory gate** (tools/perf_gate.py): passes the
   checked-in BENCH_r* history (the tier-1 CI wiring), fails a
   synthetic regressed round, tolerates in-band noise, and skips
   cleanly below two rounds.

Plus the standing contract: ``H2O3_TELEMETRY=0`` keeps every producer
a checked ns-budget no-op.
"""
import json
import os
import statistics
import subprocess
import sys
import time

import numpy as np
import pytest

import h2o3_tpu as h2o  # noqa: F401 — installs the shard_map shim
from h2o3_tpu import telemetry
from h2o3_tpu.telemetry import costmodel
from h2o3_tpu.telemetry import snapshot as telesnap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))
import perf_gate  # noqa: E402

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


def _frame(n=6000, F=5, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = X[:, 0] - 0.5 * X[:, 1]
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["y"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                         "a", "b")
    return h2o.Frame.from_numpy(cols)


def _train(fr, **kw):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    params = dict(ntrees=6, max_depth=3, seed=2, nbins=16,
                  score_tree_interval=0, stopping_rounds=0)
    params.update(kw)
    g = H2OGradientBoostingEstimator(**params)
    g.train(y="y", training_frame=fr)
    return g.model


# ------------------------------------------------------- cost capture

def test_cost_analysis_matches_analytic_matmul():
    """flops from the lowered program within tolerance of 2*M*K*N for a
    plain matmul — the accounting is grounded in the same numbers a
    hand roofline model would use."""
    import jax
    M, K, N = 256, 128, 64
    f = jax.jit(lambda a, b: a @ b)
    a = np.ones((M, K), np.float32)
    b = np.ones((K, N), np.float32)
    cost = costmodel.lowered_cost(lambda: f.lower(a, b))
    assert cost is not None
    analytic = 2.0 * M * K * N
    assert abs(cost.flops - analytic) / analytic < 0.05, cost
    # the operands + output must cross HBM at least once
    assert cost.bytes >= (M * K + K * N + M * N) * 4


def test_scan_scale_multiplies_body_cost():
    """HLO cost analysis counts a scan body ONCE; scale= restores the
    executed trip count (the GBM chunk contract)."""
    import jax
    import jax.numpy as jnp
    T = 7
    M = 64

    def step(c, _):
        return c @ c * 0.5, ()

    def prog(c):
        out, _ = jax.lax.scan(step, c, jnp.arange(T))
        return out

    f = jax.jit(prog)
    c0 = np.eye(M, dtype=np.float32)
    one = costmodel.lowered_cost(lambda: f.lower(c0))
    scaled = costmodel.lowered_cost(lambda: f.lower(c0), scale=T)
    body = 2.0 * M * M * M
    # unscaled ~= one body; scaled ~= T bodies
    assert body * 0.9 < one.flops < body * 1.5, one
    assert abs(scaled.flops - T * one.flops) < 1e-6


def test_executable_cost_caches_and_never_relowers():
    calls = [0]

    def lower():
        import jax
        calls[0] += 1
        return jax.jit(lambda x: x * 2.0).lower(np.ones(8, np.float32))

    key = ("test.cache", 8)
    c1 = costmodel.executable_cost(key, lower)
    c2 = costmodel.executable_cost(key, lower)
    assert calls[0] == 1
    assert c1 == c2 and c1 is not None


# ------------------------------------------------------ roofline math

def test_mfu_in_unit_interval_with_peak_overrides(monkeypatch):
    monkeypatch.setenv("H2O3_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("H2O3_PEAK_BYTES_PER_S", "1e12")
    peaks = costmodel.device_peaks()
    assert peaks["peak_source"] == "override"
    pt = costmodel.roofline_point(flops=1e12, bytes_=1e10, seconds=0.5,
                                  peaks=peaks)
    assert 0.0 < pt["mfu"] <= 1.0
    assert pt["arith_intensity"] == 100.0
    # AI 100 >= ridge 1e15/1e12 = 1000? no: 1e15/1e12 = 1000 -> memory
    assert pt["ridge_intensity"] == 1000.0
    assert pt["roofline_regime"] == "memory-bound"
    pt2 = costmodel.roofline_point(flops=1e13, bytes_=1e9, seconds=0.5,
                                   peaks=peaks)
    assert pt2["roofline_regime"] == "compute-bound"


def test_peak_provenance_is_honest(monkeypatch):
    monkeypatch.delenv("H2O3_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("H2O3_PEAK_BYTES_PER_S", raising=False)
    peaks = costmodel.device_peaks()
    import jax
    if jax.default_backend() == "tpu":
        assert peaks["flops_source"] in ("table", "nominal")
    else:
        # CPU backend: nominal peaks, flagged informational — a
        # CPU-virtual MFU must never read as a utilization claim
        assert peaks["flops_source"] == "nominal"
        assert peaks["informational"] is True
    monkeypatch.setenv("H2O3_PEAK_FLOPS", "not_a_number")
    assert costmodel.device_peaks()["flops_source"] != "override"


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("TPU v9 imaginary", False)])
def test_unknown_tpu_kind_is_an_error_not_a_nominal_peak(monkeypatch, kind,
                                                         known):
    """Made-up peaks are the CPU backend's alone: a TPU whose kind has no
    table row raises, unless both peaks are overridden."""
    import jax

    class Dev:
        platform, device_kind = "tpu", kind
    monkeypatch.delenv("H2O3_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("H2O3_PEAK_BYTES_PER_S", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    if known:
        peaks = costmodel.device_peaks()
        assert peaks["flops_source"] == peaks["bytes_source"] == "table"
        assert peaks["informational"] is False
        return
    with pytest.raises(LookupError, match="TPU v9 imaginary"):
        costmodel.device_peaks()
    monkeypatch.setenv("H2O3_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("H2O3_PEAK_BYTES_PER_S", "1e12")
    assert costmodel.device_peaks()["peak_source"] == "override"


# ----------------------------------------------------- train wiring

def test_gbm_perf_output_and_warm_cost_identity(monkeypatch):
    """model.output['perf'] carries a cost_analysis-grounded roofline
    point, and a warm (zero-recompile) retrain reports the IDENTICAL
    executable cost without re-lowering anything."""
    monkeypatch.setenv("H2O3_PEAK_FLOPS", "1e18")   # MFU <= 1 anywhere
    monkeypatch.setenv("H2O3_PEAK_BYTES_PER_S", "1e15")
    fr = _frame()
    m1 = _train(fr)
    perf1 = m1.output.get("perf")
    assert perf1, "trained GBM carries no perf block"
    pt = perf1["train"]
    assert pt["flops_total"] > 0 and pt["bytes_total"] > 0
    assert pt["device_seconds"] > 0
    assert 0.0 < pt["mfu"] <= 1.0
    assert pt["roofline_regime"] in ("compute-bound", "memory-bound")
    assert pt["peak_source"] == "override"
    assert "loop" in perf1["phases"]
    # warm retrain: same config -> same cached executable -> identical
    # cost, no new lowering (the cost cache does not grow)
    cache0 = costmodel.cost_cache_size()
    m2 = _train(fr)
    assert costmodel.cost_cache_size() == cache0, \
        "warm retrain re-lowered an executable for cost capture"
    pt2 = m2.output["perf"]["train"]
    assert pt2["flops_total"] == pt["flops_total"]
    assert pt2["bytes_total"] == pt["bytes_total"]


def test_drf_perf_output():
    from h2o3_tpu.models.drf import H2ORandomForestEstimator
    fr = _frame(seed=3)
    d = H2ORandomForestEstimator(ntrees=5, max_depth=3, seed=4)
    d.train(y="y", training_frame=fr)
    pt = (d.model.output.get("perf") or {}).get("train")
    assert pt and pt["flops_total"] > 0 and pt["device_seconds"] > 0


def test_streamed_gbm_perf_output():
    """The memory-pressure path accounts its level kernels (coverage
    noted honestly — routing/leaf-apply are not costed)."""
    from h2o3_tpu import memman
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    rng = np.random.default_rng(5)
    n, F = 12_000, 6
    X = rng.normal(size=(n, F)).astype(np.float32)
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(X[:, 0] > 0, "y", "n")
    try:
        memman.reset(budget=int(2.2 * n * F * 4))
        fr = h2o.Frame.from_numpy(cols)
        gbm = H2OGradientBoostingEstimator(
            ntrees=3, max_depth=3, nbins=16, seed=3,
            score_tree_interval=0, stopping_rounds=0)
        gbm.train(y="resp", training_frame=fr)
        m = gbm.model
        assert m.output.get("streamed")
        pt = (m.output.get("perf") or {}).get("train")
        assert pt and pt["flops_total"] > 0
        assert pt.get("note") == "level-histogram kernels only"
        assert "levels" in m.output["perf"]["phases"]
    finally:
        memman.reset()


# ----------------------------------------------------- serve + REST

def test_serve_perf_block_and_rest_endpoint():
    import urllib.request

    from h2o3_tpu import serve
    from h2o3_tpu.api import server as apisrv
    fr = _frame(n=4000, seed=7)
    model = _train(fr, ntrees=4)
    model.key = "perf_acct_gbm"
    dep = serve.deploy(model.key, model=model, max_batch=64,
                       max_delay_ms=0.5)
    srv = apisrv.start_server(port=0)
    try:
        names = [f"f{i}" for i in range(5)]
        rows = [{nm: float(i) for nm in names} for i in range(200)]
        for s in range(0, 200, 40):
            dep.predict_rows(rows[s:s + 40])
        deadline = time.time() + 10
        while time.time() < deadline:
            perf = dep.perf_snapshot()
            if perf is not None and perf["executions"] >= 1:
                break
            time.sleep(0.05)
        assert perf is not None
        assert perf["flops_total"] > 0 and perf["device_seconds"] > 0
        assert perf["mfu"] is not None
        base = f"http://127.0.0.1:{srv.port}"
        st = json.loads(urllib.request.urlopen(
            base + "/3/Serve/stats", timeout=30).read())
        assert st["models"]["perf_acct_gbm"]["perf"]["flops_total"] > 0
        ts = json.loads(urllib.request.urlopen(
            base + "/3/Telemetry/perf", timeout=30).read())
        assert ts["__meta"]["schema_name"] == "TelemetryPerfV3"
        assert "serve" in ts["phases"]
        assert "train.loop" in ts["phases"]
        assert ts["peak"]["peak_source"] in ("table", "override",
                                             "nominal")
    finally:
        srv.stop()
        serve.undeploy(model.key)


# ----------------------------------------------------- cluster merge

def _perf_snapshot(pid, flops, mfu):
    return {
        "version": 1, "time": time.time(), "enabled": True,
        "process": {"pid": pid},
        "samples": [
            {"name": "h2o3_achieved_flops_total", "kind": "counter",
             "labels": {"phase": "train.loop"}, "help": "",
             "value": flops},
            {"name": "h2o3_device_seconds_total", "kind": "counter",
             "labels": {"phase": "train.loop"}, "help": "",
             "value": 1.0},
            {"name": "h2o3_mfu", "kind": "gauge",
             "labels": {"phase": "train.loop"}, "help": "",
             "value": mfu},
        ],
        "spans": [],
    }


def test_perf_metrics_merge_across_processes():
    """The new counters ride the PR-8 snapshot plane: flops sum into
    ONE series; the per-process MFU gauges keep their identity under a
    process label (an average of MFUs would be a lie — shards can run
    different phases)."""
    merged = telesnap.merge_snapshots([
        _perf_snapshot(11, 5e9, 0.25), _perf_snapshot(22, 7e9, 0.35)])
    by = {}
    for m in merged:
        by.setdefault(m["name"], []).append(m)
    (fl,) = by["h2o3_achieved_flops_total"]
    assert fl["value"] == 12e9
    assert fl["labels"] == {"phase": "train.loop"}
    gs = by["h2o3_mfu"]
    assert len(gs) == 2
    assert {g["labels"]["process"] for g in gs} == {"11@?", "22@?"}
    assert sorted(g["value"] for g in gs) == [0.25, 0.35]


# ------------------------------------------------- disabled = no-op

def test_disabled_telemetry_keeps_accounting_a_noop():
    telemetry.set_enabled(False)
    try:
        assert costmodel.accumulator("train.loop") is None

        def exploding_lower():
            raise AssertionError("lower() ran under H2O3_TELEMETRY=0")

        assert costmodel.executable_cost(("off",), exploding_lower) is None
        assert costmodel.lowered_cost(exploding_lower) is None
        costmodel.record("train.loop", costmodel.Cost(1e9, 1e9),
                         seconds=1.0)      # must not touch the registry
        assert costmodel.summary()["enabled"] is False

        N = 20_000

        def per_call_ns():
            t0 = time.perf_counter_ns()
            for _ in range(N):
                costmodel.record("train.loop", None)
            return (time.perf_counter_ns() - t0) / N

        ns = statistics.median(per_call_ns() for _ in range(5))
        assert ns < 5_000, f"disabled record not a no-op: {ns:.0f}ns"
    finally:
        telemetry.set_enabled(True)


# ------------------------------------------------------ perf gate

def _write_rounds(tmp_path, values, extra=None):
    for i, v in enumerate(values, start=1):
        rec = {"metric": "gbm_hist_training_throughput", "value": v,
               "unit": "rows/sec/chip", "vs_baseline": v / 25e6}
        if extra:
            rec.update(extra[i - 1])
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(
            json.dumps({"n": i, "parsed": rec}))
    return str(tmp_path)


def test_perf_gate_passes_improving_trajectory(tmp_path):
    rep = perf_gate.run(_write_rounds(tmp_path, [1e6, 2e6, 3e6]))
    assert rep["ok"] and not rep["skipped"]
    assert rep["metrics"]["value"]["checked"]


def test_perf_gate_fails_regressed_round(tmp_path):
    rep = perf_gate.run(_write_rounds(tmp_path, [1e6, 3e6, 2e6]))
    assert not rep["ok"]
    v = rep["violations"][0]
    assert v["metric"] == "value" and v["round"] == 3
    assert v["best"] == 3e6


def test_perf_gate_noise_band_tolerates_small_dips(tmp_path):
    # 5% dip inside the 10% band: not a regression
    rep = perf_gate.run(_write_rounds(tmp_path, [1e6, 2e6, 1.9e6]))
    assert rep["ok"], rep["violations"]
    # the ratchet anchors on the BEST round, not the previous one: two
    # consecutive in-band dips that compound past the band DO fail
    rep = perf_gate.run(_write_rounds(tmp_path,
                                      [1e6, 2e6, 1.9e6, 1.75e6]))
    assert not rep["ok"]


def test_perf_gate_lower_is_better_metrics(tmp_path):
    d = _write_rounds(tmp_path, [1e6, 2e6, 3e6], extra=[
        {"serve": {"p50_ms": 2.0}},
        {"serve": {"p50_ms": 1.5}},
        {"serve": {"p50_ms": 4.0}},   # latency doubled off best: fail
    ])
    rep = perf_gate.run(d)
    assert not rep["ok"]
    assert any(v["metric"] == "serve.p50_ms" for v in rep["violations"])


def test_perf_gate_skips_below_two_rounds(tmp_path):
    rep = perf_gate.run(str(tmp_path))
    assert rep["ok"] and rep["skipped"]
    rep = perf_gate.run(_write_rounds(tmp_path, [1e6]))
    assert rep["ok"] and rep["skipped"]


def test_perf_gate_excludes_informational_rounds(tmp_path):
    """An off-TPU smoke round (informational: true) must neither fail
    the hardware ratchet with its tiny CPU numbers nor become a fake
    'best' — it is excluded and listed (ISSUE 12)."""
    d = _write_rounds(tmp_path, [1e6, 2e6, 5e3, 3e6], extra=[
        {}, {}, {"informational": True, "backend": "cpu"}, {}])
    rep = perf_gate.run(d)
    assert rep["ok"], rep["violations"]
    assert rep["informational_rounds"] == ["BENCH_r03.json"]
    assert rep["metrics"]["value"]["points"] == 3
    # the per-point peak-provenance flag must NOT exclude a round: it
    # also fires on real TPUs missing from the peak table, and dropping
    # those would let hardware regressions slip the ratchet
    d2 = _write_rounds(tmp_path, [1e6, 2e6, 4e3], extra=[
        {}, {}, {"train.perf_informational": True}])
    rep2 = perf_gate.run(d2)
    assert not rep2["ok"] and rep2["violations"][0]["round"] == 3


def test_perf_gate_repo_trajectory_tier1():
    """The CI wiring (satellite): the checked-in BENCH_r*.json history
    must pass the gate on every tier-1 run. Skips cleanly when fewer
    than two rounds are checked in."""
    rounds = perf_gate.load_rounds(REPO)
    if len(rounds) < 2:
        pytest.skip("fewer than two checked-in bench rounds")
    rep = perf_gate.run(REPO)
    assert rep["ok"], (
        "checked-in bench trajectory regressed:\n"
        + "\n".join(str(v) for v in rep["violations"]))


def test_perf_gate_cli_json_and_exit_codes(tmp_path):
    tool = os.path.join(REPO, "tools", "perf_gate.py")
    good = _write_rounds(tmp_path, [1e6, 2e6])
    r = subprocess.run([sys.executable, tool, "--dir", good, "--json"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert json.loads(r.stdout)["ok"] is True
    bad_dir = tmp_path / "bad"
    bad_dir.mkdir()
    _write_rounds(bad_dir, [3e6, 1e6])
    r = subprocess.run([sys.executable, tool, "--dir", str(bad_dir),
                        "--json"],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 1
    rep = json.loads(r.stdout)
    assert rep["ok"] is False and rep["violations"]
