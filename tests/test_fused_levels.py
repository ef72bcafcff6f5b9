"""Multi-level fused tree growth contracts (ISSUE 17).

The streamed binned driver grows L consecutive levels per host
round-trip (``H2O3_LEVELS_PER_PASS``; auto = VMEM-budgeted, 1 = the
exact old per-level path), with a single-chunk window fused into ONE
jitted dispatch. The contracts:

- bit-parity matrix at ``histogram_precision=float32``: multi-level
  trees are bit-identical to the per-level path on the dense, streamed
  and sharded drivers, for GBM and DRF (DRF's dense chunk body already
  traces its whole loop into one executable, so the knob is a no-op
  there by construction — asserted anyway so a future L-windowed DRF
  inherits the contract);
- warm retrain of a fused streamed model compiles 0 XLA modules;
- PR-15 chunk-commit contract survives fusion: a pending cancel or
  preempt clamps the next window to ONE level (the cooperative yield
  lands at the next level boundary, not L levels later), and the
  clamping itself never changes the trees;
- the W=16 stripe-packed one-hot kernel is element-identical to the
  ``binned_level_xla`` scatter reference in interpret mode.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import memman
from h2o3_tpu.models import tree as tree_mod
from h2o3_tpu.models.drf import H2ORandomForestEstimator
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.tree import levels_per_pass
from h2o3_tpu.ops.binning import stripe_pair_codes
from h2o3_tpu.ops.hist_adaptive import (binned_level_tpu_stripe,
                                        binned_level_xla, stripe_supported)
from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compile_counter import count_compiles  # noqa: E402 — shared harness


# ------------------------------------------------ knob resolution


def test_levels_per_pass_resolution(monkeypatch):
    monkeypatch.setenv("H2O3_LEVELS_PER_PASS", "1")
    assert levels_per_pass(6, 28, 16) == 1
    monkeypatch.setenv("H2O3_LEVELS_PER_PASS", "3")
    assert levels_per_pass(6, 28, 16) == 3
    monkeypatch.setenv("H2O3_LEVELS_PER_PASS", "9")   # clamped to depth
    assert levels_per_pass(6, 28, 16) == 6
    monkeypatch.delenv("H2O3_LEVELS_PER_PASS")
    auto = levels_per_pass(6, 28, 16)
    assert 1 <= auto <= 4
    # the VMEM budget bites: a deep window over an absurd F x W product
    # must shrink L rather than provision an unschedulable histogram set
    assert levels_per_pass(14, 60_000, 32) == 1


# ------------------------------------------------ parity matrix

_COMMON = dict(ntrees=3, max_depth=4, nbins=16, seed=7, min_rows=2.0,
               histogram_precision="float32", packed_codes=True,
               score_tree_interval=0, stopping_rounds=0)


def _frame(n=6000, F=6, seed=5):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                            "y", "n")
    return cols, n * F * 4


def _assert_same_trees(a, b):
    np.testing.assert_array_equal(np.asarray(a._feat), np.asarray(b._feat))
    np.testing.assert_array_equal(np.asarray(a._thr), np.asarray(b._thr))
    np.testing.assert_array_equal(np.asarray(a._value),
                                  np.asarray(b._value))


def _train(est_cls, cols, monkeypatch, L=None, budget=None, mesh=None,
           **over):
    if L is None:
        monkeypatch.delenv("H2O3_LEVELS_PER_PASS", raising=False)
    else:
        monkeypatch.setenv("H2O3_LEVELS_PER_PASS", str(L))
    params = dict(_COMMON, **over)
    if est_cls is H2OGradientBoostingEstimator:
        params.setdefault("distribution", "bernoulli")
    old_mesh = current_mesh()
    try:
        if mesh is not None:
            set_mesh(mesh)
        if budget is not None:
            memman.reset(budget=budget)
        fr = h2o.Frame.from_numpy(cols)
        est = est_cls(**params)
        est.train(y="resp", training_frame=fr)
        return est.model
    finally:
        if budget is not None:
            memman.reset()
        if mesh is not None:
            set_mesh(old_mesh)


def test_dense_multi_level_parity_gbm_drf(monkeypatch):
    """Dense drivers: the L knob must be a no-op (the chunk body already
    fuses the whole level loop), so L=1 and auto are bit-identical."""
    cols, _ = _frame()
    for cls in (H2OGradientBoostingEstimator, H2ORandomForestEstimator):
        m1 = _train(cls, cols, monkeypatch, L=1)
        mA = _train(cls, cols, monkeypatch, L=None)
        assert m1.output["levels_per_dispatch"] == _COMMON["max_depth"]
        _assert_same_trees(m1, mA)


def test_streamed_fused_parity_and_zero_recompile(monkeypatch):
    """Streamed single-chunk driver on one device: the fused L-level
    window is bit-identical to the per-level path at f32, and a warm
    retrain of the fused model compiles 0 XLA modules."""
    cols, x_bytes = _frame()
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    budget = int(2.2 * x_bytes)
    m1 = _train(H2OGradientBoostingEstimator, cols, monkeypatch, L=1,
                budget=budget, mesh=mesh1)
    mA = _train(H2OGradientBoostingEstimator, cols, monkeypatch, L=None,
                budget=budget, mesh=mesh1)
    assert m1.output.get("streamed") and mA.output.get("streamed")
    assert m1.output["levels_per_dispatch"] == 1
    assert mA.output["levels_per_dispatch"] == levels_per_pass(
        _COMMON["max_depth"], len(cols) - 1, 16)
    assert mA.output["levels_per_dispatch"] > 1
    _assert_same_trees(m1, mA)
    # warm retrain of the fused configuration: every (chunk shape,
    # window) executable is already cached — 0 compiles
    compiles = []
    with count_compiles(compiles):
        mW = _train(H2OGradientBoostingEstimator, cols, monkeypatch,
                    L=None, budget=budget, mesh=mesh1)
    assert compiles == [], compiles
    _assert_same_trees(mA, mW)


@pytest.mark.slow  # multi-second streamed trains (transfer-budget tier)
@pytest.mark.skipif(len(jax.devices()) < 8,
                    reason="needs the 8-virtual-device test mesh")
def test_sharded_multi_level_parity(monkeypatch):
    """The parity matrix's sharded column: dense GBM/DRF on the (4,2)
    mesh and the streamed driver on the default sharded mesh are
    bit-identical between L=1 and the fused default."""
    cols, x_bytes = _frame()
    mesh = make_mesh(n_data=4, n_model=2)
    for cls in (H2OGradientBoostingEstimator, H2ORandomForestEstimator):
        m1 = _train(cls, cols, monkeypatch, L=1, mesh=mesh)
        mA = _train(cls, cols, monkeypatch, L=None, mesh=mesh)
        _assert_same_trees(m1, mA)
    budget = int(2.2 * x_bytes)
    s1 = _train(H2OGradientBoostingEstimator, cols, monkeypatch, L=1,
                budget=budget)
    sA = _train(H2OGradientBoostingEstimator, cols, monkeypatch, L=None,
                budget=budget)
    assert s1.output.get("streamed") and sA.output.get("streamed")
    _assert_same_trees(s1, sA)


# ------------------------------------------------ chunk-commit contract


def test_pending_interrupt_clamps_window_to_level_boundary(monkeypatch):
    """PR-15 chunk-commit contract through the fused driver: with a
    cancel/preempt pending, every window clamps to ONE level (the
    fused executable is never dispatched — the cooperative yield lands
    at the next level boundary), and clamping never changes the trees."""
    cols, x_bytes = _frame()
    mesh1 = make_mesh(n_data=1, devices=jax.devices()[:1])
    budget = int(2.2 * x_bytes)
    real_win = tree_mod._fused_binned_window
    calls = []

    def spy(*a, **k):
        calls.append(a)
        return real_win(*a, **k)

    monkeypatch.setattr(tree_mod, "_fused_binned_window", spy)
    base = _train(H2OGradientBoostingEstimator, cols, monkeypatch,
                  L=None, budget=budget, mesh=mesh1)
    assert base.output.get("streamed")
    assert calls, "fused window unused — streamed config regressed"
    calls.clear()
    from h2o3_tpu.models.streaming import StreamedChunks
    monkeypatch.setattr(StreamedChunks, "interrupt_pending",
                        lambda self: True)
    clamped = _train(H2OGradientBoostingEstimator, cols, monkeypatch,
                     L=None, budget=budget, mesh=mesh1)
    assert clamped.output.get("streamed")
    assert calls == [], "pending interrupt must clamp Lw to 1"
    _assert_same_trees(base, clamped)


def test_interrupt_pending_polls_both_checks():
    from h2o3_tpu.models.streaming import StreamedChunks
    ch = object.__new__(StreamedChunks)
    ch.cancel_check = None
    ch.interrupt_check = None
    assert not StreamedChunks.interrupt_pending(ch)
    ch.interrupt_check = lambda: True        # preempt pending
    assert StreamedChunks.interrupt_pending(ch)
    ch.interrupt_check = None
    ch.cancel_check = lambda: True           # cancel pending
    assert StreamedChunks.interrupt_pending(ch)


# ------------------------------------------------ stripe kernel parity


def test_stripe_kernel_bit_parity_interpret():
    """W=16 stripe-packed one-hot (two features per 32-lane stripe) is
    element-identical to the binned_level_xla scatter reference —
    routing, NA lane, histogram mass — including an ODD feature count
    (the all-NA pad feature's columns are sliced away)."""
    W, N = 16, 4
    for F in (7, 8):
        rng = np.random.default_rng(F)
        rows = 2048
        codes = rng.integers(0, W - 1, size=(rows, F)).astype(np.int32)
        codes[rng.random((rows, F)) < 0.07] = W - 1      # NA lane
        n_prev, base = N // 2, N - 1
        nid = (base - n_prev
               + rng.integers(0, n_prev, rows)).astype(np.int32)
        g = rng.integers(-8, 9, rows).astype(np.float32)  # exact f32 sums
        ghw = jnp.asarray(np.stack([g, np.ones(rows, np.float32),
                                    np.ones(rows, np.float32)]))
        tables = (jnp.asarray(rng.integers(0, F, n_prev)
                              .astype(np.float32)),
                  jnp.asarray(rng.integers(1, W - 1, n_prev)
                              .astype(np.float32)),
                  jnp.asarray((rng.random(n_prev) < 0.5)
                              .astype(np.float32)),
                  # one parent builds its left child, one its right
                  jnp.asarray([1.0, 2.0]))
        ct = jnp.asarray(codes.T.astype(np.int8))
        nid_s, hist_s = binned_level_tpu_stripe(
            stripe_pair_codes(ct, W), jnp.asarray(nid), ghw, tables,
            n_prev, base, W, tile=1024, interpret=True,
            mxu_dtype=jnp.float32, F=F)
        nid_x, hist_x = binned_level_xla(
            jnp.asarray(codes), jnp.asarray(nid), ghw, tables,
            n_prev, base, W)
        assert hist_x.shape == (3, n_prev, F, W)
        np.testing.assert_array_equal(np.asarray(nid_s),
                                      np.asarray(nid_x))
        np.testing.assert_array_equal(np.asarray(hist_s),
                                      np.asarray(hist_x))


def test_stripe_supported_env_override(monkeypatch):
    monkeypatch.setenv("H2O3_STRIPE", "0")
    assert not stripe_supported()
    monkeypatch.setenv("H2O3_STRIPE", "1")
    assert stripe_supported()
