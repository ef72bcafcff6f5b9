"""``counts/xgb.py`` against hand arithmetic, the pattern that finds the
int16 level kernels on the op line, and the reader of their device time."""
import copy
import os
import re

import pytest

from harness import counts, peaks
from harness import trace_reduce as tr
from harness.loader import read_json
from harness.readers import Reading, trace_pattern_seconds

V5E = peaks.of("TPU v5 lite")
ROWS = 10_000_000


def config(**params):
    cfg = read_json("configs", "xgb_h2o_hist_higgs.json")
    cfg["params"].update(params)
    return cfg


def totals(phases):
    return sum(p["bytes"] for p in phases), sum(p["flops"] for p in phases)


def test_one_level_is_76_bytes_and_84_adds_a_row():
    level = counts.phases("xgb.levels", config())[0]
    assert level["bytes"] == ROWS * (28 * 2 + 4 + 12 + 4) == ROWS * 76
    assert level["flops"] == ROWS * 3 * 28 == ROWS * 84
    # 0.93 ms at 819 GB/s
    assert peaks.least_seconds([level], V5E) == (
        pytest.approx(ROWS * 76 / 819e9), "bandwidth")


def test_levels_and_whole_train_by_hand():
    phases = counts.phases("xgb.levels", config())
    assert len(phases) == 50 * 7                    # one phase a kernel call
    b, f = totals(phases)
    assert b == 50 * ROWS * (6 * 76 + (2 + 4 + 4 + 12))
    assert f == 50 * ROWS * (6 * 84 + 3)
    # sketch 28*4, digitise 28*(4+2), 50 x (20 + 6*76 + 22 + 12), metrics 12
    b, f = totals(counts.phases("xgb.train", config()))
    assert b == ROWS * (112 + 168 + 50 * (20 + 456 + 22 + 12) + 12)
    # 253 edges: 8 compares a value in the sketch and in the digitise
    assert f == ROWS * (2 * 28 * 8 + 50 * (10 + 6 * 84 + 3 + 1) + 10)


def test_count_is_gbms_at_max_bins_and_ignores_the_kernel():
    # 255 bins and the missing value are 256 values: one byte a code
    narrow = counts.phases("xgb.levels", config(max_bins=255))[0]
    assert narrow["bytes"] == ROWS * (28 + 20)
    cfg = config()
    as_gbm = copy.deepcopy(cfg)
    as_gbm["params"]["nbins"] = 256
    other = copy.deepcopy(cfg)
    other["expect"] = {"W": 256, "level_kernel": "some_future_kernel"}
    for count in ("levels", "train"):
        assert counts.phases("xgb." + count, cfg) == \
            counts.phases("gbm." + count, as_gbm) == \
            counts.phases("xgb." + count, other)
    with pytest.raises(KeyError, match="no count 'score'"):
        counts.phases("xgb.score", cfg)


HLO = ('%{name} = (s32[1,10002432]{{1,0:T(1,128)}}, f32[96,7168]{{1,0:T(8,128)}}) '
       'custom-call({dt}[28,10002432]{{1,0:T(8,128)(4,1)}} %p0, s32[1,10002432] %p1), '
       'custom_call_target="tpu_custom_call", operand_layout_constraints={{}}')


@pytest.mark.parametrize("name,dt,found", [
    ("binned_level_tpu_t.45", "s16", True),
    ("binned_level_tpu_t", "s16", True),
    ("binned_level_tpu_t_f16r4096.3", "s16", False),  # no such body
    ("binned_route_only_tpu_t.9", "s16", True),
    ("binned_level_tpu_t.45", "s8", False),          # the defaults' cell
    ("binned_level_tpu_stripe.2", "s16", False),
    ("fusion.16", "s16", False)])
def test_pattern_names_the_int16_level_and_route_kernels(name, dt, found):
    for metric in ("level_kernel_roofline.xgb_hist", "level_kernel_s.xgb_hist"):
        rx = re.compile(read_json("metrics", metric + ".json")[
            "arguments"]["pattern"])
        assert bool(rx.search(HLO.format(name=name, dt=dt))) is found


def test_pattern_seconds_a_step_on_the_hand_made_trace():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "fixtures", "synthetic.xplane.pb")
    r = Reading(config={}, peaks=None, chips=1, step_span="bench.train",
                steps=2, elapsed=0.1, trace=tr.load(path))
    # kernel A: 8 + 8 + 20 ms in two steps
    assert trace_pattern_seconds.read(r, "body_kernel_a") == \
        pytest.approx(0.018)
    assert trace_pattern_seconds.read(r, "no_such_kernel") is None
    r.trace = None
    assert trace_pattern_seconds.read(r, "body_kernel_a") is None
