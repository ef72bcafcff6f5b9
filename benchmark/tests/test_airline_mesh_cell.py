"""The four-chip cell ``airline_whole.train_4chip`` at a size a test run can
hold (4,096 rows over four virtual devices, kernels interpreted): the mesh
generator bit-equal to ``airline_shaped``, the mesh reference equal to
``reference/gbm_enum.py``, ``counts/gbm_enum_mesh.py`` against hand
arithmetic, the cell's rehearsal line, the sound run by the cell's own limits
and each control making ``correct`` false.

``split_regret`` is held to ``DEEP_REGRET`` here and to the cell's limit on
the chip, for the reason ``test_airline_cell.py`` gives (nodes of 20-100 rows
whose one-row levels tie). Run by hand (``benchmark/MESH_CELLS.md``); the
repository's ``conftest.py`` gives the process eight virtual devices and the
cell takes the first four."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from cellrun import SEED, decide, one_step
from harness import counts, loader, peaks
from harness.checks.gbm_enum_train_follow import data_layout
from harness.generators import airline_shaped, airline_shaped_mesh
from harness.reference import gbm_enum as one
from harness.reference import gbm_enum_mesh as ref

CELL = "airline_whole.train_4chip"
DEEP_REGRET = 0.25
ROWS = 123_534_969
CHIP_ROWS = -(-ROWS // 4)


@pytest.fixture(scope="module")
def trained():
    return one_step(CELL)


def limits_here(cell):
    return {**cell["check"]["limits"], "split_regret": DEEP_REGRET}


def over(cell, numbers):
    lim = limits_here(cell)
    return {n for n, v in numbers.items() if not v <= lim[n]}


def config():
    return loader.read_json("configs", "gbm_airline_whole_table.json")


# ------------------------------------------------------------ generator


@pytest.mark.parametrize("seed,rows,padded", [
    (7, 4096, 4096),                        # one block, every chip a slice
    (2**31 + 5, 3_000_001, 3_000_032)])     # blocks astride the chips' ranges
def test_the_mesh_generator_makes_airline_shapeds_table(seed, rows, padded):
    X1, y1 = airline_shaped.make(seed, rows, padded)
    X4, y4 = airline_shaped_mesh.make(seed, rows, padded, devices=4)
    assert len(X4.sharding.device_set) == 4
    assert X4.sharding.shard_shape(X4.shape) == (padded // 4, 8)
    assert np.array_equal(np.asarray(X1), np.asarray(X4), equal_nan=True)
    assert np.array_equal(np.asarray(y1), np.asarray(y4), equal_nan=True)


# ------------------------------------------------------------ reference


def rows_in_order(a, per_chip=None):
    """[blocks, B, ...] -> the real rows in the table's order: a chip's
    pad rows lie at the end of its own blocks."""
    a = np.asarray(a)
    if per_chip is None:
        return a.reshape((-1,) + a.shape[2:])
    chips = a.reshape((4, -1) + a.shape[2:])
    return chips[:, :per_chip].reshape((-1,) + a.shape[2:])


def test_the_mesh_reference_is_the_one_chip_reference(trained):
    """Rows, edges and codes equal bit for bit; the scorer's margins and
    log-loss, a followed tree's exact sums and its gains to the rounding of
    float32 sums added in another order."""
    cell, product, _ = trained
    lay = data_layout(cell["config"])
    rows, padded = product["rows"], product["padded"]
    X1, y1, w1 = one.make_rows(airline_shaped, SEED, rows, padded, 8)
    X4, y4, w4 = ref.make_rows(airline_shaped_mesh, SEED, rows, padded, 8, 4)
    per = padded // 4
    assert len(X4.sharding.device_set) == 4
    for a, b in ((X1, X4), (y1, y4), (w1, w4)):
        assert np.array_equal(rows_in_order(a)[:padded],
                              rows_in_order(b, per), equal_nan=True)
    e1, e4 = one.uniform_edges(X1, lay), ref.uniform_edges(X4, lay)
    assert all((a is None and b is None) or np.array_equal(a, b)
               for a, b in zip(e1, e4))
    c1, c4 = one.digitize(X1, e1, lay), ref.digitize(X4, e4, lay)
    assert np.array_equal(rows_in_order(c1)[:padded], rows_in_order(c4, per))
    model = product["model"]
    depth = int(model["max_depth"])
    f0 = float(np.asarray(model["f0"]).reshape(-1)[0])
    tabs = one.pack_tree_table(model)
    m1, ll1 = one.score(X1, y1, w1, *tabs, f0, depth, stops=(4,))
    m4, ll4 = ref.score(X4, y4, w4, *tabs, f0, depth, stops=(4,))
    np.testing.assert_allclose(np.asarray(ll4), np.asarray(ll1), rtol=2e-6)
    for a, b in zip(m1, m4):
        np.testing.assert_array_equal(rows_in_order(a)[:padded],
                                      rows_in_order(b, per))
    tree = {k: np.asarray(model[k][4]) for k in
            ("feat", "thr", "na_left", "is_split", "value", "node_w",
             "cat_set", "is_set")}
    follow = (depth, lay, 10.0, 1e-5)
    s1 = one.follow_tree(X1, c1, one.grad_hess(m1[0], y1, w1), tree, *follow,
                         ordinal=True)
    s4 = ref.follow_tree(X4, c4, ref.grad_hess(m4[0], y4, w4), tree, *follow,
                         ordinal=True)
    assert np.array_equal(np.isnan(s1["totals"]), np.isnan(s4["totals"]))
    np.testing.assert_array_equal(s4["totals"][:, 2], s1["totals"][:, 2])
    # a node's G is a sum of hundreds of addends of either sign: to the
    # rounding of the addends, not of the sum
    np.testing.assert_allclose(s4["totals"], s1["totals"], rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(s4["own_gain"], s1["own_gain"], rtol=1e-3,
                               atol=1e-4)
    # the best gain on offer turns on the ORDER of an enum's levels by G/H:
    # in a node of 20-100 rows many one-row levels tie, sums that differ
    # in their last place order them apart and min_rows cuts inside the
    # tie group (test_airline_cell.py's DEEP_REGRET): all but a few nodes
    for k in ("best_gain", "ordinal_gain"):
        apart = ~np.isclose(s4[k], s1[k], rtol=1e-3, atol=1e-4,
                            equal_nan=True)
        assert apart.mean() < 0.005, (k, apart.sum())


# --------------------------------------------------------------- counts


def test_a_level_is_one_chips_share_36_bytes_and_24_adds_a_row():
    level = counts.phases("gbm_enum_mesh.levels", config())[0]
    assert CHIP_ROWS == 30_883_743
    assert level["bytes"] == CHIP_ROWS * (8 * 2 + 4 + 12 + 4) == CHIP_ROWS * 36
    assert level["flops"] == CHIP_ROWS * 3 * 8
    assert peaks.least_seconds([level], peaks.of("TPU v5 lite")) == (
        pytest.approx(CHIP_ROWS * 36 / 819e9), "bandwidth")


def test_levels_and_whole_train_by_hand():
    phases = counts.phases("gbm_enum_mesh.levels", config())
    assert len(phases) == 10 * 11                   # one phase a kernel call
    assert sum(p["bytes"] for p in phases) == 10 * CHIP_ROWS * (
        10 * 36 + (2 + 4 + 4 + 12))
    train = counts.phases("gbm_enum_mesh.train", config())
    assert (train[0]["bytes"], train[0]["flops"]) == (CHIP_ROWS * 32,
                                                      CHIP_ROWS * 4)
    assert (train[1]["bytes"], train[1]["flops"]) == (CHIP_ROWS * 48,
                                                      CHIP_ROWS * 14)
    assert sum(p["bytes"] for p in train) == CHIP_ROWS * (
        32 + 48 + 10 * (20 + 10 * 36 + 22 + 12) + 12)
    # the all-reduce is a phase of its own and adds nothing to HBM bytes:
    # 880 lanes (872 bins and 8 NA lanes), one child a previous-level node
    # (1 + 1 + 2 + ... + 256 = 512 nodes a tree), (g, h, w) in float32,
    # and 1,024 leaves' totals
    ar = train[-1]
    assert (ar["name"], ar["bytes"], ar["flops"]) == ("all_reduce", 0, 0)
    assert ar["ici_bytes"] == 10 * 4 * (3 * 512 * 880 + 3 * 1024)


def test_the_share_is_a_quarter_of_the_one_chip_count():
    """The whole table's count against one chip's peak would read four
    times too high: the count divides by ``deployment.n_data``."""
    whole = {**config(), "deployment": {"n_data": 1}}
    for name in ("gbm_enum_mesh.levels", "gbm_enum_mesh.train"):
        a = sum(p["bytes"] for p in counts.phases(name, config()))
        b = sum(p["bytes"] for p in counts.phases(name, whole))
        assert a * 4 == pytest.approx(b, rel=1e-7)
    one_chip = sum(p["bytes"] for p in counts.phases("gbm_enum.levels",
                                                     whole))
    assert one_chip == sum(p["bytes"] for p in counts.phases(
        "gbm_enum_mesh.levels", whole))


# ------------------------------------------------------------ the cell


def test_rehearsal_line_on_four_virtual_devices():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, os.path.join(loader.BENCH_DIR, "run.py"),
         "--workload", CELL, "--seed", str(2**31 + 35), "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=loader.REPO_DIR, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["device"]["count"] == 4
    pc = line["run"]["info"]["packed_codes"]
    assert (pc["enabled"], pc["W"], pc["dtype"], pc["lanes"],
            pc["lane_layout"], pc["set_features"]) == (
        True, 304, "int16", 896, "ragged", 6)
    assert (pc["n_data"], pc["n_model"], pc["sketch"]) == (4, 1, "mesh")
    # float32 histograms at 1,024 rows a shard build both children
    assert pc["psum_bytes"] == 10 * 4 * (3 * (1 + 2 * 511) * 896 + 3 * 1024)
    assert {"loop_s", "sketch_s", "digitize_s", "pack_s", "queue_s",
            "compiles_in_window.train", "set_split_share.airline_gbm",
            "train_d2h_mb.airline_whole"} <= set(line["metrics"])
    # the trees of a train (sets' words among them), never the table
    assert line["metrics"]["train_d2h_mb.airline_whole"]["value"] < 2
    assert not {m for m in line["metrics"] if m.startswith(
        ("level_kernel", "collective_s", "train_mfu"))}
    assert set(line["compared"]) == set(
        loader.read_json("workloads", CELL + ".json")["limits"])


def test_sound_run_holds_the_cells_limits(trained):
    cell, product, ok = trained
    _, compared = decide(cell, product, ok)
    assert ok
    numbers = {n: c["value"] for n, c in compared.items()}
    assert not over(cell, numbers), compared
    assert product["model"]["is_set"].sum() > 100


@pytest.mark.parametrize("control,must_fail", [
    ("fp8", "node_value_gap"), ("fp8", "leaf_gap"),
    ("half_batch", "cover_gap"), ("half_batch", "edge_gap"),
    ("bin_off_by_one", "split_regret"), ("last_step_dropped", "logloss_gap"),
    ("ordinal_sets", "split_regret")])
def test_controls_are_not_correct(trained, control, must_fail):
    cell, product, _ = trained
    check = loader.plugin("checks", cell["check"]["check"])
    numbers = check.run(cell, product, SEED, control=control)
    assert must_fail in over(cell, numbers), numbers


@pytest.mark.parametrize("key,value", [("n_data", 2), ("sketch", "host")])
def test_a_train_off_the_expected_mesh_path_is_a_failed_step(
        monkeypatch, key, value):
    """Another layout than the deployment's, or edges made from a host
    copy: the runner's step refuses the train."""
    from harness.runners import train_enum_mesh
    real = train_enum_mesh.train_enum.step

    def elsewhere(state):
        ok = real(state)
        out = state.model.output
        (out["spmd"] if key == "n_data" else out["packed_codes"])[key] = value
        return ok
    monkeypatch.setattr(train_enum_mesh.train_enum, "step", elsewhere)
    with pytest.raises(RuntimeError, match=f"warm-up train: {key}"):
        one_step(CELL)


def test_a_program_without_the_mesh_sketch_is_refused_before_a_row_is_made(
        monkeypatch):
    from h2o3_tpu.ops import binning
    from harness.runners import train_enum_mesh
    monkeypatch.delattr(binning, "_mesh_sketch_edges")
    monkeypatch.setattr(train_enum_mesh, "build_frame", lambda *a: 1 / 0)
    with pytest.raises(RuntimeError, match="host copy"):
        one_step(CELL)
