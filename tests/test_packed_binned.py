"""Packed binned-feature compute (ISSUE 12): int8/int16 bin codes
through the fused binned level kernel, end to end.

Covers the acceptance contract on CPU:
- interpret-mode BIT parity of the binned pallas kernel vs the scatter
  XLA reference (integer ghw mass makes every histogram sum exact, so
  the comparison is equality, not allclose);
- grow_tree_binned vs the existing global-sketch grow_tree: identical
  splits when both run float32-exact on the same codes;
- end-to-end GBM packed vs unpacked under histogram_precision=float32:
  bit-identical split structure (sharded through the suite's virtual
  mesh like every other train);
- hot-loop bytes: the binned level's lowered cost_analysis moves >= 2x
  fewer bytes than the f32 adaptive level at the same shape;
- zero-recompile warm retrain + streamed packed parity and code-sized
  H2D accounting.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import h2o3_tpu as h2o
from h2o3_tpu import memman
from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
from h2o3_tpu.models.tree import (TreeConfig, binned_feasible, grow_tree,
                                  grow_tree_binned, packed_codes_requested)
from h2o3_tpu.ops.binning import (_edges_host, bin_matrix,
                                  digitize_codes_host, pack_codes,
                                  pack_codes_for)
from h2o3_tpu.ops.hist_adaptive import (binned_level_plan,
                                        binned_level_tpu_t,
                                        binned_level_xla,
                                        binned_route_only_tpu_t,
                                        binned_route_only_xla, code_dtype,
                                        pick_W)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _compile_counter import count_compiles  # noqa: E402 — shared harness


# ------------------------------------------------ kernel-level parity


def _kernel_inputs(rows=4096, F=7, W=16, N=4, seed=0, int_ghw=True):
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, W - 1, size=(rows, F)).astype(np.int32)
    codes[rng.random((rows, F)) < 0.07] = W - 1          # NA lane
    n_prev, base = N // 2, N - 1
    nid = (base - n_prev + rng.integers(0, n_prev, rows)).astype(np.int32)
    if int_ghw:
        # integer mass: every f32 histogram sum is exact regardless of
        # accumulation order -> BIT parity between matmul and scatter
        g = rng.integers(-8, 9, rows).astype(np.float32)
    else:
        g = rng.normal(size=rows).astype(np.float32)
    ghw = np.stack([g, np.ones(rows, np.float32),
                    np.ones(rows, np.float32)])
    tables = (jnp.asarray(rng.integers(0, F, n_prev).astype(np.float32)),
              jnp.asarray(rng.integers(1, W - 1, n_prev)
                          .astype(np.float32)),
              jnp.asarray((rng.random(n_prev) < 0.5).astype(np.float32)),
              # ``can``: 0 no split, 1 / 2 the left / right child is built
              jnp.asarray(rng.integers(0, 3, n_prev).astype(np.float32)))
    ct = jnp.asarray(codes.T.astype(np.int8 if W <= 128 else np.int16))
    return (codes, ct, jnp.asarray(nid), jnp.asarray(ghw), tables,
            n_prev, N, base)


def test_binned_level_bit_parity_interpret():
    codes, ct, nid, ghw, tables, n_prev, N, base = _kernel_inputs()
    W = 16
    nid_t, hist_t = binned_level_tpu_t(
        ct, nid, ghw, tables, n_prev, base, W, tile=1024,
        interpret=True, mxu_dtype=jnp.float32)
    nid_x, hist_x = binned_level_xla(
        jnp.asarray(codes), nid, ghw, tables, n_prev, base, W)
    np.testing.assert_array_equal(np.asarray(nid_t), np.asarray(nid_x))
    np.testing.assert_array_equal(np.asarray(hist_t), np.asarray(hist_x))


def test_binned_level_float_ghw_close_interpret():
    codes, ct, nid, ghw, tables, n_prev, N, base = _kernel_inputs(
        seed=3, int_ghw=False)
    W = 16
    nid_t, hist_t = binned_level_tpu_t(
        ct, nid, ghw, tables, n_prev, base, W, tile=1024,
        interpret=True, mxu_dtype=jnp.float32)
    nid_x, hist_x = binned_level_xla(
        jnp.asarray(codes), nid, ghw, tables, n_prev, base, W)
    np.testing.assert_array_equal(np.asarray(nid_t), np.asarray(nid_x))
    np.testing.assert_allclose(np.asarray(hist_t), np.asarray(hist_x),
                               rtol=1e-5, atol=1e-4)


def test_binned_route_only_bit_parity_interpret():
    codes, ct, nid, _ghw, tables, n_prev, _N, base = _kernel_inputs(seed=5)
    r_t = binned_route_only_tpu_t(ct, nid, tables, n_prev, base, 16,
                                  tile=1024, interpret=True)
    r_x = binned_route_only_xla(jnp.asarray(codes), nid, tables, n_prev,
                                base, 16)
    np.testing.assert_array_equal(np.asarray(r_t), np.asarray(r_x))


def test_code_dtype_and_feasibility():
    assert code_dtype(16) == jnp.int8
    assert code_dtype(128) == jnp.int8
    assert code_dtype(256) == jnp.int16
    assert binned_feasible(14, 28, 6)
    assert not binned_feasible(300, 28, 6)       # past the lane cap


# ------------------------------- W=256, int16 codes (XGBoost hist)


def _wide_inputs(F, N, rows=1536, pad_rows=512, seed=0):
    """int16 codes with the NA lane at W=256, integer g (every f32 sum
    exact), all-NA pad rows with no mass, ``N`` nodes at their level."""
    W = 256
    rng = np.random.default_rng(seed + 31 * F + N)
    codes = rng.integers(0, 254, size=(rows + pad_rows, F)).astype(np.int16)
    codes[rng.random(codes.shape) < 0.05] = W - 1
    codes[rows:] = W - 1
    n_prev, base = N // 2, N - 1
    nid = (base - n_prev + rng.integers(0, max(n_prev, 1), rows + pad_rows)
           ).astype(np.int32)
    nid[rows:] = 0                                   # pads sit at the root
    ghw = np.stack([rng.integers(-8, 9, rows + pad_rows),
                    rng.integers(1, 4, rows + pad_rows),
                    np.ones(rows + pad_rows)]).astype(np.float32)
    ghw[:, rows:] = 0.0
    n = max(n_prev, 1)
    tables = (jnp.asarray(rng.integers(0, F, n).astype(np.float32)),
              jnp.asarray(rng.integers(1, 254, n).astype(np.float32)),
              jnp.asarray((rng.random(n) < 0.5).astype(np.float32)),
              jnp.asarray(rng.integers(0, 3, n).astype(np.float32)))
    return (jnp.asarray(codes), jnp.asarray(codes.T), jnp.asarray(nid),
            jnp.asarray(ghw), tables, n_prev, base)


@pytest.mark.parametrize("N", [1, 2, 4, 8, 16, 32])
@pytest.mark.parametrize("F", [28, 5, 1])
def test_level_equals_scatter_at_w256_int16(F, N):
    """254 bins and the NA lane in 256 lanes of int16 codes: bit-equal
    histograms and node ids to the scatter reference at every level of a
    depth-6 tree, pad rows carrying nothing."""
    codes, ct, nid, ghw, tables, n_prev, base = _wide_inputs(F, N)
    assert ct.dtype == code_dtype(256) == jnp.int16
    nid_t, hist_t = binned_level_tpu_t(
        ct, nid, ghw, tables, n_prev, base, 256, tile=512,
        interpret=True, mxu_dtype=jnp.float32)
    nid_x, hist_x = binned_level_xla(codes, nid, ghw, tables, n_prev,
                                     base, 256)
    np.testing.assert_array_equal(np.asarray(nid_t), np.asarray(nid_x))
    np.testing.assert_array_equal(np.asarray(hist_t), np.asarray(hist_x))
    # one child a splitting parent (the root's own rows at the root), and
    # nothing of the pad rows
    assert hist_x.shape == (3, max(n_prev, 1), F, 256)
    can = np.asarray(tables[3])
    built = 2 * (base - n_prev + np.arange(max(n_prev, 1))) + 1 + (can > 1.5)
    in_built = (np.isin(np.asarray(nid_x)[:1536], built[can > 0.5])
                if n_prev else np.ones(1536, bool))
    assert float(hist_x[2].sum()) == float(in_built.sum()) * F


@pytest.mark.parametrize("F", [28, 5, 1])
def test_route_only_and_leaf_totals_at_int16_w256(F):
    from h2o3_tpu.models.tree import _segment_totals
    N = 64
    codes, ct, nid, ghw, tables, n_prev, base = _wide_inputs(F, N, seed=3)
    tables = tables[:3] + (jnp.ones(n_prev, jnp.float32),)  # all split
    r_t = binned_route_only_tpu_t(ct, nid, tables, n_prev, base, 256,
                                  tile=512, interpret=True)
    r_x = binned_route_only_xla(codes, nid, tables, n_prev, base, 256)
    np.testing.assert_array_equal(np.asarray(r_t), np.asarray(r_x))
    tot = []
    for routed in (r_t, r_x):
        local = routed - base
        inside = (local >= 0) & (local < N)
        tot.append(_segment_totals(jnp.clip(local, 0, N - 1), inside,
                                   ghw[0], ghw[1], ghw[2], N))
    for a, b in zip(*tot):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert float(tot[0][2].sum()) == 1536.0          # pad rows carry nothing


@pytest.mark.parametrize("W,F,method,kernel,tile", [
    (256, 28, "pallas", "binned_level_tpu_t", 8192),
    (32, 28, "pallas", "binned_level_tpu_t", 8192),
    (16, 1, "pallas", "binned_level_tpu_t", 8192),
    (256, 28, "scatter", "binned_level_xla", 0)])
def test_level_plan_names_what_the_dispatch_runs(monkeypatch, W, F, method,
                                                 kernel, tile):
    from h2o3_tpu.ops import hist_adaptive as ha
    monkeypatch.setattr(ha, "TILE", 8192)        # the chip's, whatever the env
    assert binned_level_plan(W, F, method) == {
        "kernel": kernel, "feature_block": F, "row_tile": tile,
        "lanes": F * W, "lane_layout": "uniform"}
    # per-feature lane widths (a frame with set features) run the one
    # transposed body, whatever W is, on the lanes the widths sum to
    ragged = binned_level_plan(W, 2, method, widths=(W, 8))
    assert (ragged["lanes"], ragged["lane_layout"]) == (W + 8, "ragged")
    assert ragged["kernel"] == ("binned_level_xla" if method == "scatter"
                                else "binned_level_tpu_t")
    assert kernel == ha.binned_level_kernel(W, F, method)


@pytest.mark.parametrize("n_bins,F,depth,want", [
    (254, 28, 6, True),      # 2 x [96, 7168] f32 = 5.5 MB
    (254, 28, 10, True),     # 88 MB
    (254, 28, 11, False),    # 176 MB
    (254, 300, 7, False),    # 118 MB
    (254, 300, 6, True),     # 59 MB
    (30, 28, 13, True),      # W=32: 88 MB
    (30, 28, 14, False)])
def test_feasible_counts_both_accumulators(n_bins, F, depth, want):
    """Scratch and output block [3 * 2^(D-1), F * W] f32 against 96 MiB:
    the count that held on the chip at F=28, W=256, depth 6 (PERF.md,
    PR 29: Mosaic streams the one-hot and never holds it whole)."""
    assert binned_feasible(n_bins, F, depth) is want


# ------------------------------------------- grower vs grow_tree parity


def _binned_setup(n=2560, F=5, nbins=14, seed=2, na_frac=0.0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    if na_frac:
        X[rng.random((n, F)) < na_frac] = np.nan
    bm = bin_matrix(X, [f"f{i}" for i in range(F)], [False] * F, n,
                    nbins=nbins)
    pc = pack_codes(bm)
    y = (np.nan_to_num(X[:, 0]) + 0.5 * np.nan_to_num(X[:, 1])
         > 0).astype(np.float32)
    g = jnp.asarray(0.5 - y)
    h = jnp.full(n, 0.25, jnp.float32)
    w = jnp.ones(n, jnp.float32)
    return bm, pc, g, h, w


@pytest.mark.parametrize("na_frac", [0.0, 0.2])
def test_grow_tree_binned_matches_grow_tree_f32(na_frac):
    """Same codes, exact f32 histograms: the packed grower and the
    existing global-sketch grower pick identical splits — INCLUDING on
    NA-heavy data, because _find_splits masks the packed layout's
    empty lanes (max_bin) so both paths scan the identical candidate
    grid."""
    bm, pc, g, h, w = _binned_setup(na_frac=na_frac)
    cfg = TreeConfig(max_depth=3, n_bins=bm.n_bins, n_features=5,
                     min_rows=2.0, histogram_precision="float32")
    col_mask = jnp.ones(5, bool)
    t_old, nid_old = grow_tree(bm.codes.rm, g, h, w, cfg, col_mask)
    t_new, nid_new = grow_tree_binned(pc.rm, g, h, w, cfg, col_mask,
                                      ct=pc.t)
    np.testing.assert_array_equal(np.asarray(t_old["feat"]),
                                  np.asarray(t_new["feat"]))
    np.testing.assert_array_equal(np.asarray(t_old["is_split"]),
                                  np.asarray(t_new["is_split"]))
    live = np.asarray(t_old["is_split"])
    np.testing.assert_array_equal(np.asarray(t_old["split_bin"])[live],
                                  np.asarray(t_new["split_bin"])[live])
    np.testing.assert_array_equal(np.asarray(t_old["na_left"])[live],
                                  np.asarray(t_new["na_left"])[live])
    np.testing.assert_array_equal(np.asarray(nid_old), np.asarray(nid_new))
    np.testing.assert_array_equal(np.asarray(t_old["value"]),
                                  np.asarray(t_new["value"]))


def test_grow_tree_binned_interpret_matches_scatter():
    """Pallas (interpret) vs scatter through the GROWER, with NAs: the
    packed path must be bit-identical to its own reference."""
    bm, pc, g, h, w = _binned_setup(na_frac=0.05, seed=9)
    cfg = TreeConfig(max_depth=3, n_bins=bm.n_bins, n_features=5,
                     min_rows=2.0, histogram_precision="float32")
    col_mask = jnp.ones(5, bool)
    t_sc, nid_sc = grow_tree_binned(pc.rm, g, h, w, cfg, col_mask,
                                    ct=None)
    os.environ["H2O3_PALLAS_INTERPRET"] = "1"
    try:
        # single-device transposed view: outside shard_map, the mesh-
        # sharded pack (per-shard padding) would misalign row indexing
        from h2o3_tpu.ops.binning import _pack_t_single
        from h2o3_tpu.ops.hist_adaptive import TILE
        ct = _pack_t_single(pc.rm, W=pc.W, tile=TILE)
        t_pl, nid_pl = grow_tree_binned(pc.rm, g, h, w, cfg, col_mask,
                                        ct=ct)
    finally:
        del os.environ["H2O3_PALLAS_INTERPRET"]
    for k in ("feat", "split_bin", "na_left", "is_split"):
        np.testing.assert_array_equal(np.asarray(t_sc[k]),
                                      np.asarray(t_pl[k]), err_msg=k)
    np.testing.assert_array_equal(np.asarray(nid_sc), np.asarray(nid_pl))


# ------------- one child a parent, the sibling by subtraction (ISSUE 34)


def _int_case(rows, F, n_bins, W, seed, dtype):
    """Codes with NAs and small-integer g, h, w: every histogram sum is
    exact in bf16 and in f32, whatever the order of its additions."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(0, n_bins, size=(rows, F))
    codes[rng.random((rows, F)) < 0.06] = W - 1
    g = rng.integers(-4, 5, rows).astype(np.float32)
    h = rng.integers(1, 4, rows).astype(np.float32)
    w = rng.integers(1, 3, rows).astype(np.float32)
    return (jnp.asarray(codes.astype(dtype)), jnp.asarray(g), jnp.asarray(h),
            jnp.asarray(w))


@pytest.mark.parametrize("n_bins,dtype,method", [
    (20, np.int8, "scatter"), (20, np.int8, "pallas"),
    (254, np.int16, "scatter"), (254, np.int16, "pallas")],
    ids=["w32-int8-scatter", "w32-int8-kernel", "w256-int16-scatter",
         "w256-int16-kernel"])
def test_smaller_child_levels_grow_the_direct_formulations_tree(
        monkeypatch, n_bins, dtype, method):
    """Exact arithmetic: the grower that accumulates each parent's smaller
    child and derives its sibling gives, bit for bit, the tree of the
    formulation that builds every node of a level directly (the oracle,
    tests/_direct_levels.py); bf16 addends, NAs, depth 5, and parents that
    do not split."""
    from _direct_levels import assert_same_tree, grow_direct
    monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    F, W, depth = 3, pick_W(n_bins), 5
    codes, g, h, w = _int_case(1800, F, n_bins, W, seed=n_bins, dtype=dtype)
    cfg = TreeConfig(max_depth=depth, n_bins=n_bins, n_features=F,
                     min_rows=200.0, min_split_improvement=0.0,
                     hist_method=method, histogram_precision="bfloat16")
    col_mask = jnp.ones(F, bool)
    tree, nid = grow_tree_binned(codes, g, h, w, cfg, col_mask)
    want, want_nid = grow_direct(codes, g, h, w, cfg, col_mask)
    assert_same_tree(tree, nid, want, want_nid, depth)


def _skewed_levels(rows=200_000, small=10, seed=4):
    """Codes whose feature 0 routes three levels down one chain: the root
    splits at bin 1 (``small`` rows left), its right child at bin 2 (40%
    left), that one's right child at bin 3 (45% left); bf16-valued g, h of
    one magnitude, w = 1; a second feature of three bins for the cells."""
    rng = np.random.default_rng(seed)
    route = 1 + (rng.random(rows) > 0.4) * (1 + (rng.random(rows) > 0.45))
    codes = np.stack([np.where(np.arange(rows) < small, 0, route),
                      rng.integers(0, 3, rows)], axis=1).astype(np.int8)
    ghw = np.stack([rng.uniform(-1, 1, rows), rng.uniform(0.2, 0.3, rows),
                    np.ones(rows)]).astype(np.float32)
    ghw = np.asarray(jnp.asarray(ghw).astype(jnp.bfloat16).astype(jnp.float32))
    return codes, ghw


def _derived_levels(codes, ghw, builds, W=16):
    """The levels' histograms [3, N, 2, W] and the rows' node ids, level by
    level down the chain of :func:`_skewed_levels`: level d's splitting
    node is the last of level d - 1 and ``builds[d - 1]`` its ``can``
    entry (which child is built); the sibling is parent - built."""
    from h2o3_tpu.models.tree import sibling_level_hist
    from h2o3_tpu.ops.hist_adaptive import binned_level_xla
    rows = len(codes)
    z = jnp.zeros(1, jnp.float32)
    nid, hist = binned_level_xla(jnp.asarray(codes), jnp.zeros(rows, jnp.int32),
                                 jnp.asarray(ghw), (z, z, z, z), 0, 0, W)
    out = []
    for d, build in enumerate(builds, start=1):
        n_prev = 2 ** (d - 1)
        last = jnp.zeros(n_prev, jnp.float32).at[-1]
        tables = (jnp.zeros(n_prev), last.set(float(d)), jnp.zeros(n_prev),
                  last.set(build))
        nid, built = binned_level_xla(jnp.asarray(codes), nid,
                                      jnp.asarray(ghw), tables, n_prev,
                                      2 ** d - 1, W)
        hist = sibling_level_hist(built[None], hist, tables)
        assert hist.shape == (3, 2 ** d, 2, W)
        out.append((np.asarray(hist, np.float64), np.asarray(nid)))
    return out


def _cells_within(hist, nid, node, codes, ghw, n_anc, k):
    """Every cell of ``node`` (feature 1) within the bound
    ops/hist_adaptive.py states for k derived levels running below an
    ancestor of ``n_anc`` rows: 3 * 2^k * sqrt(n_anc) * 2^-24 *
    sum_own|a| of the float64 sum of its addends."""
    base = 2 ** int(np.log2(node + 1)) - 1
    ok = True
    for b in range(3):
        cell = (nid == node) & (codes[:, 1] == b)
        a = ghw[:, cell].astype(np.float64)
        err = np.abs(hist[:, node - base, 1, b] - a.sum(axis=1))
        ok &= bool((err <= 3 * 2 ** k * n_anc ** 0.5 * 2.0 ** -24
                    * np.abs(a).sum(axis=1)).all())
    return ok


@pytest.mark.parametrize("built", [1.0, 2.0], ids=["smaller-built",
                                                   "larger-built"])
def test_a_derived_child_is_within_the_stated_bound_only_when_it_is_larger(
        built):
    """One derived level below a 200,000-row root: with the 10-row child
    built and its 199,990-row sibling derived, both are within the stated
    bound (k = 0 built, k = 1 derived); with the larger child built, the
    10-row child inherits the root's absolute error and fails it: why the
    grower builds the child with the smaller w."""
    codes, ghw = _skewed_levels()
    (hist, nid), = _derived_levels(codes, ghw, [built])
    assert [(nid == n).sum() for n in (1, 2)] == [10, len(codes) - 10]
    ok = all(_cells_within(hist, nid, 1 + child, codes, ghw, len(codes),
                           k=int(child == (built == 1.0)))
             for child in (0, 1))
    assert ok is (built == 1.0)


def test_three_derived_levels_running_stay_within_the_stated_bound():
    """The larger child three levels running: node 14 is the root less
    three built siblings, never built itself. Its cells, and every other
    node's on the way, hold the bound of their own chain length k below
    the last built ancestor (the root): an absolute error that does not
    shrink with the node, so 2^k of a direct build's relative to its own
    sums and no more, because each derived level keeps at least half."""
    codes, ghw = _skewed_levels()
    levels = _derived_levels(codes, ghw, [1.0, 1.0, 1.0])
    sizes = []
    for d, (hist, nid) in enumerate(levels, start=1):
        derived, small = 2 ** (d + 1) - 2, 2 ** (d + 1) - 3
        sizes.append(((nid == small).sum(), (nid == derived).sum()))
        assert sizes[-1][0] < sizes[-1][1]
        assert _cells_within(hist, nid, derived, codes, ghw, len(codes), k=d)
        assert _cells_within(hist, nid, small, codes, ghw, len(codes), k=0)
    assert sizes[0][0] == 10 and sizes[-1][1] > len(codes) // 4


# -------------------------------------------------- hot-loop bytes drop


def test_binned_level_bytes_accessed_drop():
    """The acceptance lever, measurable off-TPU: the binned level
    kernel's per-level HBM-side operands (what its cost_analysis
    reports on TPU — pl.CostEstimate counts exactly these) total >= 2x
    fewer bytes than the f32 adaptive level's at the same (rows, F)
    shape. Asserted from the ACTUAL pallas entry-point operands, plus
    the declared CostEstimates staying consistent with them."""
    import functools

    from h2o3_tpu.ops import hist_adaptive as ha

    rows, F, W, N = 8192, 28, 16, 8
    rng = np.random.default_rng(0)
    ct = jnp.asarray(rng.integers(0, W - 1, (F, rows)).astype(np.int8))
    xt = jnp.asarray(rng.normal(size=(F, rows)).astype(np.float32))
    nid = jnp.zeros(rows, jnp.int32)
    ghw = jnp.ones((3, rows), jnp.float32)
    t1 = jnp.zeros(max(N // 2, 1), jnp.float32)
    tables = (t1, t1, t1, t1)
    lo = jnp.zeros((N, F), jnp.float32)
    inv = jnp.ones((N, F), jnp.float32)
    base = N - 1

    captured = {}
    real_call = ha.pl.pallas_call

    def spy(kern, **kw):
        name = kern.func.__name__       # functools.partial of the kernel
        ce = kw.get("cost_estimate")

        def runner(*operands):
            captured[name] = (
                sum(int(o.size) * jnp.dtype(o.dtype).itemsize
                    for o in operands),
                ce.bytes_accessed if ce is not None else None)
            return real_call(kern, **kw)(*operands)
        return runner

    ha.pl.pallas_call = spy
    try:
        ha.binned_level_tpu_t(ct, nid, ghw, tables, N // 2, base, W,
                              tile=1024, interpret=True,
                              mxu_dtype=jnp.float32)
        ha.adaptive_level_tpu_t(xt, nid, ghw, tables, lo, inv, N // 2, N,
                                base, W, tile=1024, interpret=True,
                                mxu_dtype=jnp.float32)
    finally:
        ha.pl.pallas_call = real_call
    b_bytes, b_ce = captured["_kernel_bt"]
    a_bytes, _ = captured["_kernel_t"]
    assert a_bytes / b_bytes >= 2.0, (a_bytes, b_bytes)
    # the declared CostEstimate is dominated by (and consistent with)
    # the feature operand: codes itemsize, not 4
    assert b_ce == rows * F * 1 + rows * 16


# ------------------------------------------------------- end to end


def _frame(n=5120, F=6, seed=5, na=False):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, F)).astype(np.float32)
    if na:
        X[rng.random((n, F)) < 0.04] = np.nan
    logit = (np.nan_to_num(X[:, 0]) * 1.2 - np.nan_to_num(X[:, 1])
             + 0.4 * np.nan_to_num(X[:, 2]))
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                            "y", "n")
    return h2o.Frame.from_numpy(cols)


_COMMON = dict(ntrees=5, max_depth=4, nbins=14, seed=3, min_rows=1.0,
               histogram_type="quantiles_global",
               histogram_precision="float32",
               score_tree_interval=0, stopping_rounds=0)


def test_packed_gbm_matches_unpacked_f32():
    """histogram_precision=float32: packed and unpacked trains produce
    BIT-identical split structure (and matching metrics) — through the
    estimator, i.e. sharded exactly like every train in this suite."""
    fr = _frame()
    m1 = H2OGradientBoostingEstimator(packed_codes=True, **_COMMON)
    m1.train(y="resp", training_frame=fr)
    m2 = H2OGradientBoostingEstimator(packed_codes=False, **_COMMON)
    m2.train(y="resp", training_frame=fr)
    assert m1.model.output["packed_codes"]["enabled"]
    assert m1.model.output["packed_codes"]["bytes_per_value"] == 1
    assert not m2.model.output["packed_codes"]["enabled"]
    np.testing.assert_array_equal(np.asarray(m1.model._feat),
                                  np.asarray(m2.model._feat))
    np.testing.assert_array_equal(np.asarray(m1.model._thr),
                                  np.asarray(m2.model._thr))
    np.testing.assert_array_equal(np.asarray(m1.model._na_left),
                                  np.asarray(m2.model._na_left))
    # DEEPEST leaf values bit-equal (both paths end in the same exact
    # segment-totals tail); interior node values may differ in ulps —
    # grow_tree's sibling-subtraction (right = parent - left) vs the
    # binned kernel's direct build round differently on non-dyadic
    # gradients
    v1 = np.asarray(m1.model._value)
    v2 = np.asarray(m2.model._value)
    baseD = 2 ** _COMMON["max_depth"] - 1
    np.testing.assert_array_equal(v1[:, baseD:], v2[:, baseD:])
    np.testing.assert_allclose(v1, v2, rtol=1e-4, atol=1e-6)
    assert (m1.model.training_metrics.auc
            == pytest.approx(m2.model.training_metrics.auc, abs=1e-9))


def test_packed_gbm_with_nas_and_validation():
    """NA routing through the reserved W-1 bin, and the validation walk
    over packed codes: trains, scores, and the valid metrics are sane."""
    fr = _frame(na=True)
    vr = _frame(n=2048, seed=11, na=True)
    est = H2OGradientBoostingEstimator(packed_codes=True, **_COMMON)
    est.train(y="resp", training_frame=fr, validation_frame=vr)
    assert est.model.output["packed_codes"]["enabled"]
    assert 0.5 < est.model.training_metrics.auc <= 1.0
    assert 0.4 < est.model.validation_metrics.auc <= 1.0
    pred = np.asarray(est.model.predict(fr).vec(1).to_numpy())
    assert np.isfinite(pred[: fr.nrow]).all()


def test_packed_validation_codes_convention():
    """pack_codes_for shares the training sketch and the W-1 NA lane."""
    rng = np.random.default_rng(1)
    X = rng.normal(size=(500, 3)).astype(np.float32)
    bm = bin_matrix(X, ["a", "b", "c"], [False] * 3, 500, nbins=14)
    pc = pack_codes(bm)
    Xv = rng.normal(size=(100, 3)).astype(np.float32)
    Xv[0, 0] = np.nan
    vc = np.asarray(pack_codes_for(jnp.asarray(Xv), bm, pc.W))
    assert vc.dtype == np.int8
    assert vc[0, 0] == pc.W - 1
    assert vc[1:, :].max() < bm.n_bins


def test_packed_warm_retrain_zero_recompiles():
    """The packed path must keep the zero-recompile contract: bin,
    pack, and chunk executables all reuse on an identical retrain."""
    fr = _frame(seed=8)
    est = H2OGradientBoostingEstimator(packed_codes=True, **_COMMON)
    est.train(y="resp", training_frame=fr)
    events = []
    with count_compiles(events):
        est2 = H2OGradientBoostingEstimator(packed_codes=True, **_COMMON)
        est2.train(y="resp", training_frame=fr)
    assert est2.model.ntrees_built == 5
    assert len(events) == 0, f"warm packed train compiled {len(events)}"


# --------------------------------------------------------- streamed


@pytest.mark.slow  # multi-second streamed trains ride the established
                   # slow tier (test_transfer_budget.py precedent)
def test_streamed_packed_matches_dense_and_moves_codes():
    """Forced memory-pressure train with packing on: bit-identical
    split structure to the dense packed train, resident-window H2D
    sized by CODE bytes (not f32), and the once-per-tree contract."""
    rng = np.random.default_rng(7)
    n, F = 30000, 8
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = X[:, 0] - 0.6 * X[:, 1]
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                            "y", "n")
    common = dict(ntrees=4, max_depth=4, nbins=16, seed=3, min_rows=1.0,
                  histogram_precision="float32", score_tree_interval=0,
                  stopping_rounds=0)
    fr = h2o.Frame.from_numpy(cols)
    dense = H2OGradientBoostingEstimator(packed_codes=True, **common)
    dense.train(y="resp", training_frame=fr)
    x_bytes = n * F * 4
    try:
        memman.reset(budget=int(2.2 * x_bytes))
        fr2 = h2o.Frame.from_numpy(cols)
        est = H2OGradientBoostingEstimator(packed_codes=True, **common)
        est.train(y="resp", training_frame=fr2)
        m = est.model
    finally:
        memman.reset()
    assert m.output.get("streamed")
    assert m.output["packed_codes"]["enabled"]
    sp = m.output["stream_profile"]
    assert sp["packed_codes"] and sp["x_itemsize"] == 1
    # resident window = codes + y/w/margin f32 vectors, NOT f32 X
    assert sp["h2d_resident_bytes"] <= n * F * 1 + 3 * 4 * n + 4096
    assert sp["h2d_bytes_per_tree"] <= 1.1 * sp["device_footprint_bytes"]
    np.testing.assert_array_equal(np.asarray(dense.model._feat),
                                  np.asarray(m._feat))
    np.testing.assert_array_equal(np.asarray(dense.model._thr),
                                  np.asarray(m._thr))


def test_host_sketch_matches_bin_matrix_and_device_digitise():
    """The host sketch used by the streamed packed path produces the
    same edges as bin_matrix, and codes that BIT-match the device
    digitise (modulo the NA remap) — including +inf values, which must
    land in the shared inf-padded lane like digitize_with_edges, not
    the per-feature top bin."""
    rng = np.random.default_rng(3)
    X = rng.normal(size=(2000, 4)).astype(np.float32)
    X[rng.random((2000, 4)) < 0.05] = np.nan
    X[5, 1] = np.inf
    # a near-constant column -> short edge list (the +inf divergence
    # case: its edges are shorter than the widest feature's)
    X[:, 3] = 1.0
    X[7, 3] = np.inf
    bm = bin_matrix(X, list("abcd"), [False] * 4, 2000, nbins=14)
    edges, n_bins = _edges_host(X, 2000, [False] * 4, 14, 1024,
                                "quantiles_global")
    assert n_bins == bm.n_bins
    for e1, e2 in zip(edges, bm.edges):
        np.testing.assert_array_equal(e1, e2)
    codes, W = digitize_codes_host(X, edges, n_bins)
    dev = np.asarray(bm.codes.rm).astype(np.int32)
    host = codes.astype(np.int32)
    na = np.isnan(X)
    assert (host[na] == W - 1).all()
    np.testing.assert_array_equal(host[~na], dev[~na])


# ------------------------------------ the device digitise is a count


def _emat(edges):
    """The inf-padded edge matrix ``digitize_with_edges`` builds."""
    out = np.full((len(edges), max(max(map(len, edges)), 1)), np.inf,
                  np.float32)
    for f, e in enumerate(edges):
        out[f, : len(e)] = e
    return out


def _searchsorted_truth(X, edges, nbins):
    em = _emat(edges)
    want = np.stack([np.searchsorted(em[f], X[:, f], side="right")
                     for f in range(X.shape[1])], axis=1)
    return np.where(np.isnan(X), nbins, want)


# (widest feature's edge count, nbins): 32/33 and 64/65 straddle the
# edge block of the count; nbins < 256 gives uint8 codes, above int32
_DIGITISE_CASES = [(0, 20), (1, 20), (13, 14), (13, 20), (19, 20),
                   (19, 300), (32, 40), (33, 40), (64, 255), (65, 70),
                   (254, 255), (254, 1024), (1023, 1024)]


@pytest.mark.parametrize("max_e,nbins", _DIGITISE_CASES)
def test_device_digitise_equals_numpy_searchsorted(max_e, nbins):
    """Every element of the device digitise equals numpy's
    ``searchsorted(side="right")`` on the inf-padded edges with NaN ->
    nbins: ragged edge counts (the widest, none, one, half), NaN, +-inf,
    signed zeros, values exactly on edges, and a row count no tile
    divides."""
    from h2o3_tpu.ops.binning import digitize_with_edges
    rng = np.random.default_rng(100 + max_e)
    rows = 1003
    counts = [max_e, 0, min(1, max_e), max_e // 2, max_e]
    edges = [np.unique(rng.normal(size=4 * n + 4).astype(np.float32))[:n]
             for n in counts]
    assert [len(e) for e in edges] == counts
    X = rng.normal(size=(rows, len(edges))).astype(np.float32)
    X[rng.random(X.shape) < 0.05] = np.nan
    X[3], X[4], X[5], X[6] = np.inf, -np.inf, 0.0, -0.0
    for f, e in enumerate(edges):           # ties: values ON an edge
        if len(e):
            X[10:10 + min(len(e), 200), f] = e[:200]
    got = np.asarray(digitize_with_edges(X, edges, nbins))
    assert got.dtype == (np.uint8 if nbins < 256 else np.int32)
    np.testing.assert_array_equal(got, _searchsorted_truth(X, edges, nbins))
    assert (got[3] == max(max_e, 1)).all()  # +inf: the shared pad lane


@pytest.mark.parametrize("nbins", [20, 300])
@pytest.mark.parametrize("value,codes", [
    (np.nan, None),                 # NA bin on every feature
    (np.inf, [3, 3, 3]),            # every pad lane counts: lane max_e
    (-np.inf, [0, 0, 0]),
    (2.0, [2, 0, 0]),               # on an edge: ties go right
    (np.nextafter(np.float32(2.0), np.float32(0)), [1, 0, 0]),
    (5.0, [3, 0, 1]),
    (-0.0, [0, 0, 0]),
])
def test_device_digitise_by_hand(value, codes, nbins):
    """Edges [1, 2, 3], none, [5]: the codes written out by hand."""
    from h2o3_tpu.ops.binning import digitize_with_edges
    edges = [np.array([1, 2, 3], np.float32), np.empty(0, np.float32),
             np.array([5], np.float32)]
    X = np.full((7, 3), value, np.float32)
    got = np.asarray(digitize_with_edges(X, edges, nbins))
    want = [nbins] * 3 if codes is None else codes
    assert got.dtype == (np.uint8 if nbins < 256 else np.int32)
    np.testing.assert_array_equal(got, np.tile(want, (7, 1)))


def test_device_digitise_is_one_program_without_gather_or_loop():
    """The guard against a silent return to a gather loop
    (``jnp.searchsorted``'s default method) on a JAX upgrade: at the
    default shape class (28 features, 19 edges, uint8) the compiled
    digitise holds no ``while`` and no ``gather``; a whole
    ``digitize_with_edges`` call is ONE executable from the f32 matrix
    to the codes (no eager select or cast after it), and a second call
    with the same shapes compiles and traces nothing."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.ops import binning
    rng = np.random.default_rng(28)
    rows, F, E = 4099, 28, 19
    X = jnp.asarray(rng.normal(size=(rows, F)).astype(np.float32))
    edges = [np.sort(rng.normal(size=E).astype(np.float32))
             for _ in range(F)]
    first = []
    with count_compiles(first):
        cold = binning.digitize_with_edges(X, edges, 20)
    assert len(first) == 1, f"digitise ran {len(first)} programs"
    hlo = binning._digitize.lower(
        X, jnp.asarray(_emat(edges)), np.int32(20),
        dtype=jnp.uint8).compile().as_text()
    assert "while(" not in hlo and "gather(" not in hlo
    assert " u8[4099,28]" in hlo            # the codes leave it narrowed
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.install()
    try:
        compiles = telemetry.registry().value("h2o3_xla_compiles_total")
        telemetry.clear_spans()
        with telemetry.span("t.digitize") as warm_span:
            # another bin count of the same dtype: nbins is traced
            warm = binning.digitize_with_edges(X, edges, 21)
        assert telemetry.registry().value("h2o3_xla_compiles_total") \
            == compiles
        assert not [s for s in telemetry.finished_spans()
                    if s.name.startswith("jit.")
                    and s.parent_id == warm_span.span_id]
    finally:
        telemetry.set_enabled(was)
    assert cold.dtype == warm.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))


# ------------------------------------------------- sharded (slow tier)


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
def test_packed_sharded_unsharded_bit_identical():
    """histogram_precision=float32 + packed codes: the (4,2)-mesh train
    reproduces the single-device split structure bit-for-bit (balanced
    y -> dyadic (g,h), order-independent psum — the
    test_gbm_sharded pattern applied to the packed path)."""
    from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh
    rng = np.random.default_rng(11)
    n, F = 2048, 6
    X = rng.normal(size=(n, F)).astype(np.float32)
    y = ((X[:, 0] > 0) ^ (X[:, 1] > 0.3)).astype(np.float32)
    idx1 = np.nonzero(y == 1)[0]
    idx0 = np.nonzero(y == 0)[0]
    k = min(len(idx0), len(idx1), 1000)
    sel = np.sort(np.concatenate([idx0[:k], idx1[:k]]))
    X, y = X[sel], y[sel]
    params = dict(ntrees=1, max_depth=4, nbins=16,
                  distribution="bernoulli", min_rows=2.0,
                  histogram_precision="float32", packed_codes=True,
                  score_tree_interval=0, stopping_rounds=0, seed=7)

    def train(mesh):
        old = current_mesh()
        set_mesh(mesh)
        try:
            cols = {f"f{i}": X[:, i] for i in range(F)}
            cols["y"] = y
            fr = h2o.Frame.from_numpy(cols)
            gbm = H2OGradientBoostingEstimator(**params)
            gbm.train(y="y", training_frame=fr)
            return gbm.model
        finally:
            set_mesh(old)

    m1 = train(make_mesh(n_data=1, n_model=1, devices=jax.devices()[:1]))
    m8 = train(make_mesh(n_data=4, n_model=2))
    np.testing.assert_array_equal(np.asarray(m1._feat),
                                  np.asarray(m8._feat))
    np.testing.assert_array_equal(np.asarray(m1._thr),
                                  np.asarray(m8._thr))
    np.testing.assert_array_equal(np.asarray(m1._is_split),
                                  np.asarray(m8._is_split))


def test_packed_gate_semantics(monkeypatch):
    """'auto' follows the accelerated-kernel availability; explicit
    True/False override."""
    monkeypatch.delenv("H2O3_PALLAS_INTERPRET", raising=False)
    assert not packed_codes_requested({"packed_codes": "auto"})  # CPU
    assert packed_codes_requested({"packed_codes": True})
    assert packed_codes_requested({"packed_codes": "true"})
    assert not packed_codes_requested({"packed_codes": False})
    monkeypatch.setenv("H2O3_PALLAS_INTERPRET", "1")
    assert packed_codes_requested({"packed_codes": "auto"})
    assert packed_codes_requested({})
