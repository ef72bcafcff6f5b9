"""The reduction on the hand-made trace: every value below is worked out by
hand in ``fixtures/synthetic.xplane.txt``."""
import os

import pytest

from harness import trace_reduce as tr
from harness.readers import Reading
from harness.readers import (trace_busy_per_step, trace_idle,
                             trace_pattern_roofline)

FIXTURES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures")


@pytest.fixture(scope="module")
def trace():
    return tr.load(os.path.join(FIXTURES, "synthetic.xplane.pb"))


def test_binary_fixture_is_the_text_fixture(trace):
    from jax.profiler import ProfileData
    with open(os.path.join(FIXTURES, "synthetic.xplane.txt")) as f:
        text = tr.from_profile(ProfileData.from_text_proto(f.read()))
    assert text == trace


def test_window_and_busy_union(trace):
    assert trace.window == pytest.approx((0.010, 0.110))
    s = tr.summary(trace, chips=1)
    assert s["window_s"] == pytest.approx(0.100)
    # the while holds its body: a union, not a sum (a sum would give 0.094)
    assert s["busy_s"] == pytest.approx(0.066)


def test_idle_share_and_busy_per_step(trace):
    r = Reading(config={}, peaks=None, chips=1, step_span="bench.train",
                steps=2, elapsed=0.1, trace=trace)
    assert trace_idle.read(r) == pytest.approx(34.0)
    assert trace_busy_per_step.read(r) == pytest.approx(0.033)


def test_pattern_time_reads_metadata_not_only_names(trace):
    lo, hi = trace.window
    seconds, n = tr.pattern_seconds(trace.devices[0], "body_kernel_a", lo, hi)
    assert (n, seconds) == (3, pytest.approx(0.036))
    assert tr.pattern_seconds(trace.devices[0], "no_such_kernel", lo, hi) \
        == (0, 0)


def test_roofline_only_where_the_events_are_the_counted_calls(trace, capsys):
    """One tree of depth 0 is one kernel call a train: ``fusion.7`` runs
    once in each of the two steps, ``body_kernel_a`` three times in all."""
    from harness import peaks
    from harness.loader import read_json
    cfg = read_json("configs", "gbm_h2o_defaults.json")
    cfg["params"].update(max_depth=0, ntrees=1)
    r = Reading(config=cfg, peaks=peaks.of("TPU v5 lite"), chips=1,
                step_span="bench.train", steps=2, elapsed=0.1, trace=trace)
    least = cfg["data"]["rows"] * (1 + 4 + 4 + 12) / 819e9
    assert trace_pattern_roofline.read(r, "fusion.7", "gbm.levels") \
        == pytest.approx(100.0 * least * 2 / 0.018)
    assert trace_pattern_roofline.read(r, "body_kernel_a", "gbm.levels") is None
    assert (r.notes["gbm.levels.events"],
            r.notes["gbm.levels.events_expected"]) == (3, 2)
    assert "finds 3 events in 2 steps" in capsys.readouterr().err
    assert trace_pattern_roofline.read(r, "no_such_kernel", "gbm.levels") is None


def test_gap_attribution_and_top_ops(trace):
    s = tr.summary(trace, chips=1)["breakdown"]
    gaps = dict(map(tuple, s["idle_gaps"]))
    assert gaps == {"host.prepare": pytest.approx(0.022),
                    "bench.train": pytest.approx(0.012)}
    ops = dict(map(tuple, s["device_ops"]))
    assert "while.1" not in ops            # a container, not work of its own
    assert ops == {"custom-call.4": pytest.approx(0.036),
                   "fusion.7": pytest.approx(0.018),
                   "sort.3": pytest.approx(0.010)}


def test_a_trace_without_a_device_plane_reads_nothing():
    r = Reading(config={}, peaks=None, chips=1, step_span="bench.train",
                trace=tr.Trace(window=(0.0, 1.0)))
    assert trace_idle.read(r) is None
    assert trace_busy_per_step.read(r) is None
