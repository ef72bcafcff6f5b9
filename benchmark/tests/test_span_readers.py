"""The readers of the program's own spans: ``span_ring`` against a ring built
by hand, ``trace_idle_in_span`` against the hand arithmetic of
``fixtures/spans.xplane.txt``, and the rehearsal line of each cell, which
carries every metric that reads a span (all but the one that needs a device
plane)."""
import json
import os
import subprocess
import sys
import time

import pytest

from harness import loader
from harness import trace_reduce as tr
from harness.readers import Reading, span_ring, trace_idle_in_span

FIXTURES = os.path.join(loader.BENCH_DIR, "fixtures")
NEW = {"h2o_defaults.train": {"queue_s", "spec_s", "sketch_s", "digitize_s",
                              "pack_s", "train_other_s"},
       "h2o_defaults.score": {"score_adapt_s", "score_dispatch_s",
                              "score_fetch_s", "score_frame_s",
                              "jit_host_s.score", "traces_in_window.score"}}
NEEDS_A_DEVICE_PLANE = {"dispatch_idle_s.score"}


def reading(steps, trace=None):
    return Reading(config={}, peaks=None, chips=1, step_span="bench.predict",
                   steps=steps, trace=trace)


@pytest.fixture
def ring():
    """Warm-up predict, a good step, a step that raised, a good step."""
    from h2o3_tpu import telemetry
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.clear_spans()
    now = time.time()

    def predict(dispatch, jit, fetch, **attrs):
        root = telemetry.record_span("score.predict", now, 9.0, **attrs)
        d = telemetry.record_span("score.dispatch", now, dispatch, parent=root)
        for name, seconds, n in jit:   # two levels under the root
            telemetry.record_span(name, now, seconds, parent=d, n=n)
        telemetry.record_span("score.fetch", now, fetch, parent=root)

    predict(100.0, [("jit.trace", 50.0, 900), ("jit.build", 40.0, 1)], 100.0)
    predict(0.5, [("jit.trace", 0.1, 137), ("jit.lower", 0.2, 3)], 1.0)
    predict(30.0, [("jit.trace", 20.0, 500)], 0.0, error=True)
    predict(0.7, [("jit.trace", 0.1, 139), ("jit.load", 0.3, 1)], 2.0)
    telemetry.record_span("jit.trace", now, 7.0, n=1)   # under no predict
    yield
    telemetry.clear_spans()
    telemetry.set_enabled(was)


def test_span_ring_reads_the_windows_steps_and_all_their_descendants(ring):
    r = reading(steps=2)

    def read(span, what):
        return span_ring.read(r, "score.predict", span, what)

    assert read("score.dispatch", "seconds") == pytest.approx(0.6)
    assert read("score.fetch", "seconds") == pytest.approx(1.5)
    assert read("jit.*", "seconds") == pytest.approx((0.3 + 0.4) / 2)
    # a folded span stands for the n events it holds, a plain one for 1
    assert read("jit.trace", "count") == pytest.approx((137 + 139) / 2)
    assert read("score.fetch", "count") == 1
    assert read("jit.build", "count") == 0          # the warm-up's only
    assert read("no.such.span", "seconds") == 0
    # the step that raised is no step of the window, marked or missing
    assert read("jit.trace", "seconds") == pytest.approx(0.1)
    # one step: the newest sound root alone
    assert span_ring.read(reading(steps=1), "score.predict", "jit.load",
                          "count") == 1


def test_span_ring_reads_nothing_without_roots_or_steps(ring):
    assert span_ring.read(reading(steps=2), "no.such.root", "jit.*",
                          "seconds") is None
    assert span_ring.read(reading(steps=0), "score.predict", "jit.*",
                          "seconds") is None
    # three sound roots in the ring: four steps cannot all be there
    assert span_ring.read(reading(steps=4), "score.predict", "jit.*",
                          "seconds") is None
    with pytest.raises(ValueError):
        span_ring.read(reading(steps=2), "score.predict", "jit.*", "mean")


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(FIXTURES, "spans.xplane.pb"))


def test_the_new_binary_fixture_is_its_text(trace):
    from jax.profiler import ProfileData
    with open(os.path.join(FIXTURES, "spans.xplane.txt")) as f:
        assert tr.from_profile(ProfileData.from_text_proto(f.read())) == trace
    assert trace.window == pytest.approx((0.010, 0.110))


@pytest.mark.parametrize("span, idle_per_step", [
    ("score.dispatch", 0.007), ("score.fetch", 0.003),
    ("score.frame", 0.005), ("score.adapt", 0.001)])
def test_idle_inside_a_span_is_the_fixtures_arithmetic(trace, span,
                                                       idle_per_step):
    assert trace_idle_in_span.read(reading(2, trace), span) \
        == pytest.approx(idle_per_step)


def test_idle_inside_a_span_reads_nothing_where_nothing_is(trace):
    assert trace_idle_in_span.read(reading(2, trace), "no.such.span") is None
    assert trace_idle_in_span.read(reading(2, None), "score.dispatch") is None
    hostonly = tr.Trace(host=trace.host, window=trace.window)
    assert trace_idle_in_span.read(reading(2, hostonly),
                                   "score.dispatch") is None
    # the old fixture's program annotates nothing of its own
    old = tr.load(os.path.join(FIXTURES, "synthetic.xplane.pb"))
    r = Reading(config={}, peaks=None, chips=1, step_span="bench.train",
                steps=2, trace=old)
    assert trace_idle_in_span.read(r, "score.dispatch") is None


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_carries_every_new_metric_but_the_devices(cell):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(loader.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 27), "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=loader.REPO_DIR, env=env,
        capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    metrics = json.loads(p.stdout.strip().splitlines()[-1])["metrics"]
    assert NEW[cell] <= set(metrics), sorted(metrics)
    assert not NEEDS_A_DEVICE_PLANE & set(metrics)
    for name in NEW[cell]:
        assert metrics[name]["value"] >= 0, name
    declared = {m["name"]: m for m in loader.load_benchmark()["per_layer"]}
    for name in NEW[cell] | NEEDS_A_DEVICE_PLANE:
        assert name in declared
    for name in NEW[cell]:
        assert declared[name]["workloads"] == [cell]
        assert metrics[name]["unit"] == declared[name]["unit"]
