"""Shared histogram-tree machinery (GBM / DRF / IF / XGBoost-compat).

Reference: hex/tree/ — SharedTree.java:229 driver, per-level histogram
MRTask (ScoreBuildHistogram2.java:121-301 two-stage private-then-merge
accumulate), DHistogram (w,wY,wYY) bins merged up the reduce tree
(DHistogram.java:432), split finding on the reduced histograms
(DTree.java), CompressedTree storage.

TPU re-design (SURVEY.md §7.3):
- trees are complete binary arrays of static depth (XLA needs static
  shapes): node k's children are 2k+1 / 2k+2; rows carry an int32 node id
  and are re-routed by vectorized gathers each level — no mutable 'nids'
  column;
- per-level histograms come from the one-hot-matmul / scatter kernels in
  ops/histogram.py, all-reduced over ICI ('data' axis psum) instead of the
  MRTask tree / Rabit ring;
- split finding = masked cumsum + argmax over [nodes, features, bins, 2
  NA-directions] entirely on device (the reference scans bins per leaf on
  the driver);
- Newton (g, h) gains; NA gets a dedicated bin with learned direction
  (DHistogram.wNA semantics).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, NamedTuple, Optional

import os as _os

import jax
import jax.numpy as jnp
from jax import lax
import numpy as np

from h2o3_tpu.ops.binning import (CodesView, bin_matrix_device, pack_codes,
                                  packed_codes_record)
from h2o3_tpu.ops.hist_adaptive import (binned_level, binned_level_plan,
                                        can_builds_right, can_entry,
                                        can_other_child, can_splits,
                                        level_acc_rows)

NEG_INF = -1e30

# packed routing word layout: feat[0:14) | bin[14:28) | na_left[28] | split[29]
# (14 bits each caps features and bins at 16383 — asserted in TreeConfig and
# binning.bin_matrix; bins can exceed 10 bits when nbins_cats grows the
# shared bin count for high-cardinality categoricals)
FEAT_BITS = 14
FEAT_MASK = (1 << FEAT_BITS) - 1
BIN_SHIFT = FEAT_BITS
BIN_MASK = (1 << 14) - 1
NA_SHIFT = 28
SPLIT_SHIFT = 29


# largest node table looked up by selection (node_lookup): the largest
# complete tree at which the select was still 2x faster than the gather
# on the v5e at 10M rows (31.3 against 71.6 ms at 8191 entries, 62.6
# against 71.6 at 16383: tools/micro_node_lookup.py, PERF.md §6 "PR 30").
NODE_SELECT_MAX = 8191
# entries a select tree is unrolled over; larger tables loop over blocks
_SELECT_BLOCK = 128


def node_lookup_form(n_nodes: int) -> str:
    """Which form node_lookup runs for a table of ``n_nodes`` entries."""
    return "select" if n_nodes <= NODE_SELECT_MAX else "gather"


# The scorer's rule (predict_raw_stacked), from tools/micro_scorer.py on the
# v5e (PERF.md §6 "PR 32"): the predicate form costs about 3 us a node
# whatever the rows and 0.03-0.06 ns a node and row, the gather form about
# 50 ns a row and level. Largest tree descended by predicates: the largest
# complete one at which that was still 2x faster at 500k rows (66.6 against
# 275.5 ms a tree at 8191 nodes) and at 10M (2.37 against 6.77 s); at 131071
# nodes the gathers win (0.99 against 0.37 s).
SCORER_PREDICATE_MAX = 8191
# ... and fewest rows a node: every reading from there up had the predicates
# ahead (1024 rows: 0.20 against 0.55 ms a tree at 63 nodes, 0.29 against
# 0.37 at 127; 65536 rows: 18.7 against 32.6 at 8191), every one below the
# gathers or a tie (1024 rows, 511 nodes: 0.88 against 0.47; 64 rows, a
# serving bucket: 0.19 against 0.11 at 63 nodes, 12.5 against 0.13 at 8191)
SCORER_ROWS_PER_NODE = 8
# inner nodes of a level whose predicates are unrolled; a wider level
# loops over blocks of this many
_PREDICATE_BLOCK = 32


def scorer_node_form(n_nodes: int, rows: int) -> str:
    """Which form predict_raw_stacked's descent runs for trees of
    ``n_nodes`` heap slots over ``rows`` rows: static shapes alone."""
    return ("predicate" if n_nodes <= SCORER_PREDICATE_MAX
            and rows >= SCORER_ROWS_PER_NODE * n_nodes else "gather")


def _select_tree(entries, idx):
    """``entries[idx]`` for idx in [0, len(entries)) as a tree of selects
    on idx's bits, low bit first: n - 1 selects, all in one elementwise
    fusion over the rows. ``entries`` is a table (its entries enter as
    scalars) or a list of per-row arrays."""
    vals = [entries[m] for m in range(len(entries))]
    bit = 0
    while len(vals) > 1:
        odd = ((idx >> bit) & 1).astype(bool)
        # an entry with no partner passes up: no index inside the table
        # has this bit set from there
        vals = [jnp.where(odd, vals[j + 1], vals[j])
                if j + 1 < len(vals) else vals[j]
                for j in range(0, len(vals), 2)]
        bit += 1
    return jnp.broadcast_to(vals[0], idx.shape)


def node_lookup(table, nid):
    """``table[nid]`` bit for bit: a per-row lookup into a per-tree node
    table ([M]; ``nid`` is [rows] int32 in [0, M)).

    Up to NODE_SELECT_MAX entries the value is SELECTED, not gathered: a
    tree of selects on ``nid``'s bits over the table's entries as
    scalars, block by block above _SELECT_BLOCK entries, so it fuses into
    elementwise passes over the rows and no [rows, M] array exists.
    Nothing is computed on the values, so -0.0, inf, NaN and denormals
    pass through. Left to itself XLA:TPU keeps a real gather from 65
    entries (80 ms a tree at 10M rows and 127 nodes, against 0.4 ms
    selected) and below that, inside the boost chunk's scan, expands it
    into one pass over the rows per entry (31 ms at 63 nodes against
    0.25 ms): PERF.md §6, PR 30. Larger tables (DRF's depth-16 heaps)
    keep the gather, which does not grow with M. The rule reads M alone,
    a static shape."""
    M = table.shape[0]
    if node_lookup_form(M) == "gather":
        return table[nid]
    B = _SELECT_BLOCK
    if M <= B:
        return _select_tree(table, nid)
    n_blocks = -(-M // B)
    blocks = jnp.pad(table, (0, n_blocks * B - M)).reshape(n_blocks, B)
    low, high = nid & (B - 1), nid >> (B.bit_length() - 1)

    def block(k, acc):
        return jnp.where(high == k, _select_tree(blocks[k], low), acc)
    return jax.lax.fori_loop(0, n_blocks, block,
                             jnp.zeros(nid.shape, table.dtype))


@dataclass(frozen=True)
class TreeConfig:
    max_depth: int
    n_bins: int            # real bins B; NA bin index = B
    n_features: int
    min_rows: float = 10.0
    min_split_improvement: float = 1e-5
    reg_lambda: float = 0.0
    reg_alpha: float = 0.0   # L1 on leaf values (xgboost semantics)
    # XGBoost's min_child_weight: each child's HESSIAN sum must reach it
    # (0 = no such bound; min_rows bounds the row weights)
    min_child_weight: float = 0.0
    mtries: int = 0          # >0: random feature subset PER NODE per level
                             # (DRF mtries, hex/tree/drf/DRF.java)
    # col_sample_rate_change_per_level (hex/tree/DTree.java:57):
    # effective per-level subset size = (mtries or F)·factor^depth,
    # clamped to [1, F]
    col_rate_change: float = 1.0
    hist_method: str = "auto"
    # histogram_type=random (hex/tree/DHistogram.java HistogramType.Random):
    # randomize the adaptive grid's phase per tree/feature so split points
    # land at random offsets within a bin width
    random_grid: bool = False
    # histogram contraction precision on the MXU: 'bfloat16' (1-pass,
    # default — deviation bound quantified in ops/hist_adaptive.py) or
    # 'float32' (6-pass HIGHEST, exact); 'auto' = bfloat16
    histogram_precision: str = "auto"
    # CATEGORY-SET SPLITS (the packed path of a frame with enum features,
    # prepare_tree_inputs): per feature whether it splits by a set of its
    # levels, its real bin count, and its lane count on the level
    # kernel's global lane axis (ops/binning.lane_widths). All empty: no
    # set feature, every feature pick_W(n_bins) lanes and a threshold.
    set_feats: tuple = ()
    bin_counts: tuple = ()
    lane_widths: tuple = ()

    @property
    def set_words(self) -> int:
        """uint32 words of a node's exported set: a bit a level of the
        widest set feature."""
        cards = [n for n, s in zip(self.bin_counts, self.set_feats) if s]
        return -(-max(cards, default=0) // 32)

    @property
    def n_nodes(self) -> int:
        return 2 ** (self.max_depth + 1) - 1

    def __post_init__(self):
        assert self.n_features <= FEAT_MASK, self.n_features
        assert self.n_bins < BIN_MASK, self.n_bins


def _leaf_score2(g, h, cfg: TreeConfig):
    """Squared score T(g)²/(h+λ) with the xgboost L1 soft-threshold T."""
    lam = cfg.reg_lambda
    if cfg.reg_alpha:
        g = jnp.sign(g) * jnp.maximum(jnp.abs(g) - cfg.reg_alpha, 0.0)
    return g ** 2 / (h + lam + 1e-12)


def _leaf_value(g, h, cfg: TreeConfig):
    lam = cfg.reg_lambda
    if cfg.reg_alpha:
        g = jnp.sign(g) * jnp.maximum(jnp.abs(g) - cfg.reg_alpha, 0.0)
    return -g / (h + lam + 1e-12)


def _find_splits(trip, cfg: TreeConfig, col_mask, mono=None,
                 max_bin=None):
    """Best split per node from a (g, h, w) histogram triple, each
    [N, F', B'] with F' >= n_features and B' >= n_bins+1 (the pallas
    kernel's padded layout; trailing features/bins are zero).

    ``col_mask`` is [F] (per-tree column sampling) or [N, F] (per-node
    mtries subsets). ``mono`` ([F] int, -1/0/+1) enforces monotone
    constraints: a candidate split on feature f with mono[f]=c is invalid
    unless c·(left child value) <= c·(right child value) — the same
    pruning hex/tree/DTree.java applies via Constraints.

    ``max_bin`` restricts candidates to t in 1..max_bin-1 when the
    histogram's lane width exceeds the REAL bin count (the packed path:
    B = W-1 lanes, codes occupy max_bin real bins). Without the mask
    the empty lanes admit an 'all non-NA left vs NA right' candidate
    the unpacked global-sketch scan cannot express — masking keeps
    packed and unpacked candidate grids IDENTICAL, so f32 trees stay
    bit-identical on NA-heavy frames too.

    Returns (gain, feat, bin, na_left, g_tot, h_tot, w_tot, vl, vr) per
    node, where vl/vr are the SELECTED split's unclipped child values
    (used by callers to propagate monotone bounds)."""
    B = cfg.n_bins
    F = cfg.n_features
    g = trip[0][:, :F, :]
    h = trip[1][:, :F, :]
    w = trip[2][:, :F, :]
    g_na, h_na, w_na = g[..., B], h[..., B], w[..., B]
    gb, hb, wb = g[..., :B], h[..., :B], w[..., :B]
    if cfg.set_feats:
        # a set feature's bins in the order of G/(H + lambda): by the
        # convexity of the score the best two-way partition of its levels
        # is a prefix of that order (Fisher 1958), so the scan below
        # serves it unchanged. Levels no row of the node has sort last and
        # are never a candidate; a numeric feature's key is its bin index,
        # which the stable sort leaves in place. The sums ride the sort:
        # no gather
        isset = jnp.asarray(cfg.set_feats)[None, :, None]
        lane = jax.lax.broadcasted_iota(jnp.int32, gb.shape, 2)
        present = wb > 0
        key = jnp.where(isset, jnp.where(
            present, gb / (hb + cfg.reg_lambda + 1e-12), jnp.inf),
            lane.astype(jnp.float32))
        _, gb, hb, wb, order = jax.lax.sort(
            (key, gb, hb, wb, lane), dimension=2, num_keys=1, is_stable=True)
        n_present = present.sum(axis=-1)                          # [N, F]
    cg = jnp.cumsum(gb, axis=-1)
    ch = jnp.cumsum(hb, axis=-1)
    cw = jnp.cumsum(wb, axis=-1)
    g_tot = cg[..., -1] + g_na
    h_tot = ch[..., -1] + h_na
    w_tot = cw[..., -1] + w_na
    # candidate split t in 1..B-1: left = bins < t (+ NA if na_left)
    gl0, hl0, wl0 = cg[..., :-1], ch[..., :-1], cw[..., :-1]

    def gains(gl, hl, wl):
        gr = g_tot[..., None] - gl
        hr = h_tot[..., None] - hl
        wr = w_tot[..., None] - wl
        parent = _leaf_score2(g_tot, h_tot, cfg)
        gain = (_leaf_score2(gl, hl, cfg) + _leaf_score2(gr, hr, cfg)
                - parent[..., None])
        ok = (wl >= cfg.min_rows) & (wr >= cfg.min_rows)
        if cfg.min_child_weight > 0:
            ok = (ok & (hl >= cfg.min_child_weight)
                  & (hr >= cfg.min_child_weight))
        if mono is not None:
            c = mono.astype(jnp.float32)[None, :, None]      # [1,F,1]
            vl = _leaf_value(gl, hl, cfg)
            vr = _leaf_value(gr, hr, cfg)
            ok = ok & ((c == 0) | (c * (vr - vl) >= 0))
        return jnp.where(ok, gain, NEG_INF)

    gains_nr = gains(gl0, hl0, wl0)                                  # NA right
    gains_nl = gains(gl0 + g_na[..., None], hl0 + h_na[..., None],
                     wl0 + w_na[..., None])                          # NA left
    all_gains = jnp.stack([gains_nr, gains_nl], axis=-1)             # [N,F,B-1,2]
    cm = col_mask if col_mask.ndim == 2 else col_mask[None, :]
    all_gains = jnp.where(cm[:, :, None, None], all_gains, NEG_INF)
    if cfg.set_feats:
        # candidates t = 1 .. (a numeric feature's bins, a set feature's
        # levels present in the node) - 1
        last = jnp.where(isset[..., 0], n_present,
                         jnp.asarray(cfg.bin_counts)[None, :])
        tmask = jnp.arange(B - 1)[None, None, :] < (last[..., None] - 1)
        all_gains = jnp.where(tmask[..., None], all_gains, NEG_INF)
    elif max_bin is not None and max_bin - 1 < B - 1:
        tmask = jnp.arange(B - 1) < (max_bin - 1)
        all_gains = jnp.where(tmask[None, None, :, None], all_gains,
                              NEG_INF)
    N, F = all_gains.shape[0], all_gains.shape[1]
    flat = all_gains.reshape(N, -1)
    best = jnp.argmax(flat, axis=1)
    best_gain = jnp.take_along_axis(flat, best[:, None], axis=1)[:, 0]
    per_f = (B - 1) * 2
    feat = best // per_f
    rem = best % per_f
    bin_idx = rem // 2 + 1          # split t in 1..B-1
    na_left = (rem % 2) == 1
    # selected split's child (g, h, w) for bound propagation and
    # deepest-level leaf values (children of the last split level)
    nidx = jnp.arange(N)
    t_sel = bin_idx - 1
    gl_s = gl0[nidx, feat, t_sel]
    hl_s = hl0[nidx, feat, t_sel]
    wl_s = wl0[nidx, feat, t_sel]
    gl_s = gl_s + jnp.where(na_left, g_na[nidx, feat], 0.0)
    hl_s = hl_s + jnp.where(na_left, h_na[nidx, feat], 0.0)
    wl_s = wl_s + jnp.where(na_left, w_na[nidx, feat], 0.0)
    gt_s = g_tot[nidx, 0]
    ht_s = h_tot[nidx, 0]
    vl_sel = _leaf_value(gl_s, hl_s, cfg)
    vr_sel = _leaf_value(gt_s - gl_s, ht_s - hl_s, cfg)
    wr_sel = w_tot[nidx, 0] - wl_s
    # f=0 slice of per-feature totals == node totals
    out = (best_gain, feat.astype(jnp.int32), bin_idx.astype(jnp.int32),
           na_left, g_tot[:, 0], h_tot[:, 0], w_tot[:, 0], vl_sel, vr_sel,
           wl_s, wr_sel)
    if not cfg.set_feats:
        return out
    # the chosen split as a SET over the feature's B real bins: the first
    # ``bin_idx`` of its order (a numeric feature's: the bins below the
    # threshold); a level no row of the node has goes where NA goes
    chosen = (jnp.arange(F)[None, :] == feat[:, None])[..., None]  # [N,F,1]
    order_sel = jnp.sum(jnp.where(chosen, order, 0), axis=1)       # [N, B]
    here = jnp.any(chosen & present, axis=1)
    pos = jax.lax.broadcasted_iota(jnp.int32, order_sel.shape, 1)
    _, rank = jax.lax.sort((order_sel, pos), dimension=1, num_keys=1)
    set_split = jnp.asarray(cfg.set_feats)[feat]                   # [N]
    left = jnp.where(set_split[:, None] & ~here, na_left[:, None],
                     rank < bin_idx[:, None])
    return out + (left, set_split)


def _find_splits_sharded(trip, cfg: TreeConfig, col_mask, mono=None,
                         model_axis=None, max_bin=None):
    """Split search sharded over the mesh 'model' axis: each model shard
    scans a contiguous FEATURE BLOCK of the (already data-psum'd)
    histograms with the ordinary :func:`_find_splits`, and the global
    best split per node is reconstructed with one small all_gather +
    argmax over shards. Features never move — only [N, 8] candidate
    scalars cross the ICI (the reference has no wide-axis sharding at
    all, SURVEY.md §5; this divides the N·F·B split scan by n_model).

    Tie-breaking matches the single-shard argmax EXACTLY: the local
    flattened candidate order is feature-major and shard blocks are
    contiguous feature ranges, so "first max wins" picks the same split
    — sharded and unsharded trees stay bit-identical."""
    if model_axis is None or cfg.set_feats:
        # a set split's left set does not ride the winners' all_gather:
        # every model shard scans all features of a frame that has them
        return _find_splits(trip, cfg, col_mask, mono=mono,
                            max_bin=max_bin)
    n_model = jax.lax.axis_size(model_axis)
    if n_model == 1:
        return _find_splits(trip, cfg, col_mask, mono=mono,
                            max_bin=max_bin)
    from dataclasses import replace as dc_replace
    B = cfg.n_bins
    F = cfg.n_features
    F_loc = -(-F // n_model)
    Fp = F_loc * n_model
    midx = jax.lax.axis_index(model_axis)
    start = midx * F_loc
    # node totals from the full histograms (a shard whose block is pure
    # zero-padding has no real feature to read them from): any real
    # feature's bins sum to the node totals — use feature 0
    g_tot = trip[0][:, 0, : B + 1].sum(-1)
    h_tot = trip[1][:, 0, : B + 1].sum(-1)
    w_tot = trip[2][:, 0, : B + 1].sum(-1)

    def block(x):
        xp = jnp.pad(x[:, :F, :], ((0, 0), (0, Fp - F), (0, 0)))
        return jax.lax.dynamic_slice_in_dim(xp, start, F_loc, axis=1)

    trip_l = tuple(block(t) for t in trip)
    cm = col_mask if col_mask.ndim == 2 else col_mask[None, :]
    cm = jnp.pad(cm, ((0, 0), (0, Fp - F)))          # padding: never split
    cm_l = jax.lax.dynamic_slice_in_dim(cm, start, F_loc, axis=1)
    mono_l = None
    if mono is not None:
        mono_l = jax.lax.dynamic_slice_in_dim(
            jnp.pad(mono, (0, Fp - F)), start, F_loc)
    cfg_l = dc_replace(cfg, n_features=F_loc)
    (bg, bf, bb, bnl, _gt, _ht, _wt, vl, vr, wl, wr) = _find_splits(
        trip_l, cfg_l, cm_l, mono=mono_l, max_bin=max_bin)
    cand = jnp.stack([bg, (start + bf).astype(jnp.float32),
                      bb.astype(jnp.float32), bnl.astype(jnp.float32),
                      vl, vr, wl, wr], axis=-1)      # [N, 8]
    allc = jax.lax.all_gather(cand, model_axis)      # [n_model, N, 8]
    winner = jnp.argmax(allc[:, :, 0], axis=0)       # first max = low shard
    sel = jnp.take_along_axis(allc, winner[None, :, None], axis=0)[0]
    # feature/bin indices survive the f32 ride exactly (both < 2^14)
    return (sel[:, 0], sel[:, 1].astype(jnp.int32),
            sel[:, 2].astype(jnp.int32), sel[:, 3] > 0.5,
            g_tot, h_tot, w_tot, sel[:, 4], sel[:, 5], sel[:, 6],
            sel[:, 7])


BIGV = jnp.float32(1e30)


def _child_bounds(lo_b, hi_b, vl, vr, mono_dir, can):
    """Monotone bound propagation (hex/tree/DTree Constraints): a split
    on a constrained feature bounds both subtrees at the midpoint of the
    (clipped) child values; unconstrained splits inherit the parent's
    bounds. Returns interleaved [2N] (lo, hi) for the children level."""
    vl_c = jnp.clip(vl, lo_b, hi_b)
    vr_c = jnp.clip(vr, lo_b, hi_b)
    mid = 0.5 * (vl_c + vr_c)
    up = can & (mono_dir > 0)      # left <= right
    dn = can & (mono_dir < 0)
    lo_left = jnp.where(dn, mid, lo_b)
    hi_left = jnp.where(up, mid, hi_b)
    lo_right = jnp.where(up, mid, lo_b)
    hi_right = jnp.where(dn, mid, hi_b)
    lo2 = jnp.stack([lo_left, lo_right], 1).reshape(-1)
    hi2 = jnp.stack([hi_left, hi_right], 1).reshape(-1)
    return lo2, hi2


def _next_allowed(allowed, sets, bf, can):
    """Interaction-constraint propagation: children may only split on
    features sharing an interaction set with the parent's split feature
    (intersected with the parent's own allowance — path semantics).
    ``allowed`` [N, F] bool, ``sets`` [S, F] bool. (hex/tree
    interaction_constraints / GlobalInteractionConstraints)."""
    contains = sets[:, bf].T                     # [N, S]: sets with feat
    union = (contains.astype(jnp.float32) @ sets.astype(jnp.float32)) > 0
    child = jnp.where(can[:, None], allowed & union, allowed)
    return jnp.repeat(child, 2, axis=0)          # both children alike


def _level_mtries(cfg: TreeConfig, d: int, F: int) -> int:
    """Per-level column-subset size: mtries scaled by
    col_sample_rate_change_per_level^depth (hex/tree/DTree.java:57),
    clamped to [1, F]. 0 = use the full column set."""
    mt_d = cfg.mtries
    if cfg.col_rate_change != 1.0:
        base_m = cfg.mtries if cfg.mtries > 0 else F
        mt_d = int(min(max(1, round(base_m * cfg.col_rate_change ** d)), F))
        if mt_d >= F and cfg.mtries <= 0:
            mt_d = 0               # full set — no subset draw
    return mt_d


def grow_tree(codes, g, h, w, cfg: TreeConfig, col_mask, axis_name=None,
              key=None, mono=None, sets=None, model_axis=None):
    """Build one tree. All args are device arrays (codes [rows,F] int,
    g/h/w [rows] float32, already weight-multiplied); returns tree arrays
    of length M = 2^(D+1)-1 plus per-row final node ids.

    Runs under jit; the level loop is unrolled (static depth). Under plain
    jit on sharded inputs GSPMD inserts the histogram all-reduce; under
    shard_map pass ``axis_name='data'`` for explicit psums (this is the
    Rabit-allreduce replacement point).

    ``cfg.mtries > 0`` draws a fresh random feature subset per NODE per
    level from ``key`` (DRF mtries semantics, hex/tree/drf/DRF.java —
    the key must be identical across shards so splits agree).

    ``model_axis`` shards the per-level split SEARCH over the mesh
    'model' axis (histograms stay data-psum'd and replicated across
    model shards; see _find_splits_sharded)."""
    from h2o3_tpu.ops.histogram import build_histograms

    rm = codes.rm if isinstance(codes, CodesView) else codes
    D = cfg.max_depth
    M = cfg.n_nodes
    B1 = cfg.n_bins + 1
    rows, F = rm.shape

    feat = jnp.full(M, -1, jnp.int32)
    split_bin = jnp.zeros(M, jnp.int32)
    na_left = jnp.zeros(M, bool)
    is_split = jnp.zeros(M, bool)
    value = jnp.zeros(M, jnp.float32)
    gain_arr = jnp.zeros(M, jnp.float32)
    node_w = jnp.zeros(M, jnp.float32)

    # (g, h, w) stacked ONCE — constant across levels: dead/off-level rows
    # are excluded by OOB seg ids instead of per-level weight masking
    # (saves 3 × rows multiplies per level and keeps one operand cached)
    ghw = jnp.stack([g, h, w]).astype(jnp.float32)

    nid = jnp.zeros(rows, jnp.int32)
    prev_hist = None
    lo_b = jnp.full(1, -BIGV)
    hi_b = jnp.full(1, BIGV)
    allowed = (jnp.ones((1, F), bool) if sets is not None else None)
    for d in range(D):
        base = 2 ** d - 1
        N = 2 ** d
        local = nid - base
        in_level = (local >= 0) & (local < N)
        lid = jnp.clip(local, 0, N - 1)
        if prev_hist is None:
            seg = jnp.where(in_level, local, -1)
            hist = build_histograms(codes, seg, ghw, N, B1, cfg.hist_method)
            if axis_name is not None:
                hist = jax.lax.psum(hist, axis_name)
        else:
            # sibling subtraction: build only LEFT children (even local
            # ids), right = parent − left (halves the histogram FLOPs —
            # the reference plays the same trick per DHistogram pair).
            # Children of non-split parents get phantom mass but are
            # unreachable by routing, so never read.
            is_left = in_level & (local % 2 == 0)
            seg = jnp.where(is_left, local // 2, -1)
            hist_l = build_histograms(codes, seg, ghw, N // 2, B1,
                                      cfg.hist_method)
            if axis_name is not None:
                hist_l = jax.lax.psum(hist_l, axis_name)
            # interleave (left, parent−left) → [N, F', B'] per component
            hist = tuple(
                jnp.stack([hl, hp - hl], axis=1).reshape(
                    N, hl.shape[1], hl.shape[2])
                for hl, hp in zip(hist_l, prev_hist))
        prev_hist = hist
        level_mask = col_mask
        mt_d = _level_mtries(cfg, d, F)
        if mt_d > 0 and key is not None:
            u = jax.random.uniform(jax.random.fold_in(key, d), (N, F))
            u = jnp.where(col_mask[None, :], u, 2.0)  # excluded cols last
            kth = jnp.sort(u, axis=1)[:, min(mt_d, F) - 1]
            level_mask = (u <= kth[:, None]) & col_mask[None, :]
        if allowed is not None:
            lm2 = level_mask if level_mask.ndim == 2 else level_mask[None, :]
            level_mask = lm2 & allowed
        bg, bf, bb, bnl, gt, ht, wt, vl_s, vr_s, _wl, _wr = \
            _find_splits_sharded(hist, cfg, level_mask, mono=mono,
                                 model_axis=model_axis)
        can = (bg > jnp.maximum(cfg.min_split_improvement, 0.0)) & (wt > 0)
        idx = base + jnp.arange(N)
        feat = feat.at[idx].set(jnp.where(can, bf, -1))
        split_bin = split_bin.at[idx].set(bb)
        na_left = na_left.at[idx].set(bnl)
        is_split = is_split.at[idx].set(can)
        value = value.at[idx].set(
            jnp.clip(_leaf_value(gt, ht, cfg), lo_b, hi_b))
        gain_arr = gain_arr.at[idx].set(jnp.where(can, bg, 0.0))
        node_w = node_w.at[idx].set(wt)
        if mono is not None:
            lo_b, hi_b = _child_bounds(lo_b, hi_b, vl_s, vr_s, mono[bf], can)
        else:
            lo_b = jnp.repeat(lo_b, 2)
            hi_b = jnp.repeat(hi_b, 2)
        if allowed is not None:
            allowed = _next_allowed(allowed, sets, bf, can)
        # route rows: only rows whose current node is at this level AND
        # split. Per-node routing data is packed into ONE word so each row
        # does a single small-table gather (4 separate gathers cost ~8ms
        # per level at 1M rows on TPU)
        word = (bf | (bb << BIN_SHIFT) | (bnl.astype(jnp.int32) << NA_SHIFT)
                | (can.astype(jnp.int32) << SPLIT_SHIFT))
        rw = word[lid]
        node_feat = rw & FEAT_MASK
        node_bin = (rw >> BIN_SHIFT) & BIN_MASK
        node_nal = ((rw >> NA_SHIFT) & 1).astype(bool)
        node_can = ((rw >> SPLIT_SHIFT) & 1).astype(bool)
        c = jnp.take_along_axis(rm, node_feat[:, None].astype(jnp.int32),
                                axis=1)[:, 0].astype(jnp.int32)
        is_na = c == cfg.n_bins
        go_right = jnp.where(is_na, ~node_nal, c >= node_bin)
        child = 2 * nid + 1 + go_right.astype(jnp.int32)
        nid = jnp.where(in_level & node_can, child, nid)

    # deepest level: leaf values from segment totals
    baseD = 2 ** D - 1
    localD = nid - baseD
    inD = (localD >= 0) & (localD < 2 ** D)
    lidD = jnp.clip(localD, 0, 2 ** D - 1)
    gD, hD, wD = _segment_totals(lidD, inD, g, h, w, 2 ** D)
    if axis_name is not None:
        gD = jax.lax.psum(gD, axis_name)
        hD = jax.lax.psum(hD, axis_name)
        wD = jax.lax.psum(wD, axis_name)
    idxD = baseD + jnp.arange(2 ** D)
    value = value.at[idxD].set(
        jnp.clip(_leaf_value(gD, hD, cfg), lo_b, hi_b))
    node_w = node_w.at[idxD].set(wD)

    tree = {"feat": feat, "split_bin": split_bin, "na_left": na_left,
            "is_split": is_split, "value": value, "gain": gain_arr,
            "node_w": node_w}
    return tree, nid


# histogram_type values the fused ADAPTIVE kernel serves ('random' too,
# for a trainer whose tree_path call says so: only that kernel's
# per-tree grid phase can honor it)
ADAPTIVE_HIST_TYPES = ("uniform_adaptive", "uniform", "auto", "round_robin")


def packed_codes_requested(params) -> bool:
    """Packed binned-code hot-path gate (GBM/DRF ``packed_codes``
    param). 'auto' (default) packs wherever the binned pallas kernel
    runs — TPU, or the H2O3_PALLAS_INTERPRET escape — making int8/int16
    codes the default TPU hot loop; True forces the packed path
    everywhere (the scatter reference carries it on CPU — parity
    tests); False keeps the per-node adaptive f32 kernel."""
    v = params.get("packed_codes", "auto")
    if isinstance(v, str):
        v = v.lower()
    if v in ("auto", None):
        from h2o3_tpu.ops.hist_adaptive import pallas_interpret
        return jax.default_backend() == "tpu" or pallas_interpret()
    return v in (True, "true", "1")


def packed_bins_upper_bound(spec, params) -> int:
    """Upper bound on the global sketch's effective bin count, from the
    cat domains alone (numeric features never exceed nbins; identity
    cats need their cardinality, grouped cats at most nbins_cats+1).
    Lets the packed gating reject infeasible configs BEFORE paying the
    O(rows·F) sketch+digitise — binned_feasible is monotone in n_bins,
    so 'upper bound feasible' implies 'actual feasible'."""
    nbins = int(params["nbins"])
    nc = int(params.get("nbins_cats", 1024))
    cards = [len(spec.cat_domains.get(n, ())) for n, c in
             zip(spec.names, spec.is_cat) if c]
    mc = max(cards, default=0)
    return max(nbins, min(mc, nc + 1), 2)


def binned_feasible(n_bins: int, n_features: int, max_depth: int,
                    lanes: Optional[int] = None) -> bool:
    """Whether the packed binned kernel's deepest level fits VMEM —
    the adaptive_feasible bound applied to W = pick_W(n_bins): scratch
    + output block counted at [3·2^(D-1), F·W] f32 each, TWICE what the
    packed level holds since it accumulates one child a parent
    ([3·2^(D-2), F·W]); the bound is kept as it was (which depths pack
    is its own change). Past the 254-bin
    lane cap or the VMEM bound, the matmul/scatter global-sketch path
    takes over. ``lanes``: the level's lane count under per-feature lane
    widths (a frame with set features), which has no 254-bin cap: a
    feature there is as wide as its own bins."""
    from h2o3_tpu.ops.hist_adaptive import pick_W
    n_deep = 2 ** max(max_depth - 1, 0)
    if lanes is not None:
        return 2 * 3 * n_deep * lanes * 4 <= 96 * 2 ** 20
    if n_bins > 254:
        return False
    W = pick_W(n_bins)
    return 2 * 3 * n_deep * n_features * W * 4 <= 96 * 2 ** 20


def _adaptive_n_bins_eff(spec, params) -> int:
    """Effective bin count sizing the kernel's lane width W: enums want
    identity bins (card-1), capped by nbins_cats and the 254-lane max."""
    nbins = int(params["nbins"])
    cards = [len(spec.cat_domains.get(n, ())) for n, c in
             zip(spec.names, spec.is_cat) if c]
    max_card = max(cards, default=0)
    return max(nbins, min(max(max_card - 1, 0),
                          int(params.get("nbins_cats", 1024)), 254), 2)


def adaptive_feasible(spec, params, max_depth: int) -> bool:
    """Whether the fused adaptive kernel's deepest level fits VMEM
    (scratch + output block both hold [3·2^(D-1), F·W] f32: the f32
    adaptive level builds every node of a level, unlike the packed one;
    ~128MB/core on v5e, gated conservatively at 96MB). Beyond this the
    global-sketch path takes over (it tiles features and uses sibling
    subtraction)."""
    from h2o3_tpu.ops.hist_adaptive import pick_W
    if int(params["nbins"]) > 254:
        return False
    W = pick_W(_adaptive_n_bins_eff(spec, params))
    n_deep = 2 ** max(max_depth - 1, 0)
    level_bytes = 2 * 3 * n_deep * spec.n_features * W * 4
    return level_bytes <= 96 * 2 ** 20


def tree_config(params, max_depth: int, n_bins: int, n_features: int,
                mtries: int = 0, random_grid: bool = False) -> TreeConfig:
    """The ONE place a TreeConfig is built from an estimator's params:
    the packed, sketch, adaptive and streamed setups all come through
    here, so a new field is read once."""
    p = params
    return TreeConfig(
        max_depth=max_depth, n_bins=n_bins, n_features=n_features,
        min_rows=float(p["min_rows"]),
        min_split_improvement=float(p["min_split_improvement"]),
        reg_lambda=float(p.get("reg_lambda", 0.0)),
        reg_alpha=float(p.get("reg_alpha", 0.0)),
        min_child_weight=float(p.get("min_child_weight", 0.0)),
        mtries=mtries,
        col_rate_change=float(
            p.get("col_sample_rate_change_per_level", 1.0) or 1.0),
        hist_method=p.get("hist_kernel", "auto"),
        random_grid=random_grid,
        histogram_precision=str(
            p.get("histogram_precision", "auto")).lower())


def tree_path(hist_type: str, packed_requested: bool, n_bins: Optional[int],
              n_features: int, max_depth: int, *, adaptive_fits: bool,
              random_is_adaptive: bool = True,
              lanes: Optional[int] = None) -> str:
    """Which grower a tree train runs, as a pure function of what the
    trainers know: ``"packed"`` (grow_tree_binned on int8/int16 codes),
    ``"adaptive"`` (grow_tree_adaptive on raw features) or ``"sketch"``
    (grow_tree on the global sketch's int32 codes).

    ``packed_requested`` is :func:`packed_codes_requested`; ``n_bins`` is
    the sketch's bin count, or a bound on it from the categorical domains
    (:func:`packed_bins_upper_bound`) before the sketch has run, or None
    where nothing is known yet (packing is then taken to fit);
    ``adaptive_fits`` is :func:`adaptive_feasible`. The dense bin stage
    asks twice, before the sketch with the bound (an "adaptive" there
    saves the O(rows * F) sketch and digitise) and after it with the
    count; the streamed driver asks with its host sketch's count.

    - packed: requested, not ``random`` (its per-tree grid phase needs
      the per-level rebinning that packing removes), and the deepest
      level's accumulators fit (:func:`binned_feasible`; ``lanes`` is
      the level's lane count where the frame's features have their own
      lane widths, or a bound on it);
    - else adaptive: a uniform histogram type (``random`` among them
      where ``random_is_adaptive``: GBM; DRF bins ``random`` by the
      global sketch) whose kernel fits;
    - else the global sketch: ``quantiles_global``, more than 254 bins,
      or a depth whose level fits neither kernel."""
    if (packed_requested and hist_type != "random"
            and (n_bins is None
                 or binned_feasible(n_bins, n_features, max_depth, lanes))):
        return "packed"
    uniform = ADAPTIVE_HIST_TYPES + (("random",) if random_is_adaptive
                                     else ())
    if hist_type in uniform and adaptive_fits:
        return "adaptive"
    return "sketch"


def adaptive_setup(spec, params, max_depth: int, mtries: int = 0):
    """Shared GBM/DRF setup for the adaptive path: TreeConfig sized so
    enums get identity bins (card-1 real bins, capped by nbins_cats and
    the 254-lane max), per-feature finite root ranges (±inf masked BEFORE
    the min/max so one infinite cell can't zero a feature's range) and
    per-feature bin counts nb_f (the nbins_cats analog,
    hex/tree/DHistogram nbins_cats)."""
    p = params
    nbins = int(p["nbins"])
    nbins_cats = int(p.get("nbins_cats", 1024))
    cfg = tree_config(p, max_depth, _adaptive_n_bins_eff(spec, p),
                      spec.n_features, mtries=mtries,
                      random_grid=(str(p.get("histogram_type", "")).lower()
                                   == "random"))
    if spec.X is None:           # streaming mode: ranges from host X
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # all-NaN cols → 0 below
            Xh = np.where(np.isfinite(spec.X_host), spec.X_host, np.nan)
            root_lo = jnp.asarray(np.nan_to_num(
                np.nanmin(Xh, axis=0), nan=0.0).astype(np.float32))
            root_hi = jnp.asarray(np.nan_to_num(
                np.nanmax(Xh, axis=0), nan=0.0).astype(np.float32))
    else:
        Xf = jnp.where(jnp.isfinite(spec.X), spec.X, jnp.nan)
        root_lo = jnp.nan_to_num(jnp.nanmin(Xf, axis=0), nan=0.0)
        root_hi = jnp.nan_to_num(jnp.nanmax(Xf, axis=0), nan=0.0)
    cat = jnp.asarray(np.asarray(spec.is_cat, dtype=bool))
    span = jnp.maximum(root_hi - root_lo, 1.0)
    nb_f = jnp.where(cat, jnp.minimum(span, float(nbins_cats)),
                     float(nbins)).astype(jnp.float32)
    return cfg, root_lo, root_hi, nb_f


class TreeInputs(NamedTuple):
    """What :func:`prepare_tree_inputs` hands a dense tree trainer."""
    mode: str                    # tree_path's answer
    cfg: TreeConfig
    bm: Optional[object]         # the sketch's BinnedMatrix; None on adaptive
    pc: Optional[object]         # PackedCodes on the packed path
    root_lo: jax.Array           # adaptive: the features' finite ranges
    root_hi: jax.Array           # and per-feature bin counts (zeros
    nb_f: jax.Array              # elsewhere: the chunk's operands)
    level_plan: Optional[dict]   # packed: what its levels run

    @property
    def packed(self) -> bool:
        return self.mode == "packed"

    @property
    def adaptive(self) -> bool:
        return self.mode == "adaptive"

    def operands(self, X):
        """(row-major matrix, transposed operand or the same as a dummy,
        whether that operand is real, the NA bin) of the chunk step."""
        if self.adaptive:
            return X, X, False, 0
        codes = self.pc if self.packed else self.bm.codes
        has_t = codes.t is not None
        return (codes.rm, codes.t if has_t else codes.rm, has_t,
                self.pc.na_bin if self.packed else self.bm.na_bin)

    @property
    def set_features(self) -> int:
        """Features that split by a set of their levels."""
        return sum(self.cfg.set_feats)

    def mesh_attrs(self, mesh, n_trees: int) -> dict:
        """The layout a train of ``n_trees`` trees ran under: the mesh's
        ``n_data`` and ``n_model`` and, on the packed path, ``psum_bytes``,
        the bytes the train all-reduces over the data axis
        (:func:`packed_tree_psum_bytes` a tree; 0 on one shard)."""
        from h2o3_tpu.parallel.mesh import n_data_shards, n_model_shards
        nd = n_data_shards(mesh)
        out = {"n_data": nd, "n_model": n_model_shards(mesh)}
        if self.packed:
            out["psum_bytes"] = (n_trees * packed_tree_psum_bytes(
                self.cfg, self.pc.W,
                self.level_plan["level_hist"] == "smaller_child")
                if nd > 1 else 0)
        return out

    def loop_attrs(self) -> dict:
        """The loop span's attributes: the record's keys."""
        if not self.packed:
            return {}
        return {"W": self.pc.W, "code_bytes": self.pc.itemsize,
                "set_features": self.set_features, **self.level_plan}

    def record(self, mesh_attrs: dict) -> dict:
        """``model.output["packed_codes"]``: what the level kernel
        streamed, the plan of its levels, where the sketch's edges were
        made and how many columns it sorted, and the layout the train ran
        under (:meth:`mesh_attrs`)."""
        if not self.packed:
            return packed_codes_record(False)
        return packed_codes_record(
            True, dtype=self.pc.rm.dtype, W=self.pc.W,
            bytes_per_value=self.pc.itemsize, n_bins=self.bm.n_bins,
            plan={**self.level_plan, "sketch": self.bm.sketch,
                  "ranked_features": self.bm.ranked_features, **mesh_attrs},
            set_features=self.set_features)


def set_split_features(spec, params) -> tuple:
    """Per feature whether it can split by a SET of its levels: an enum
    of at most ``nbins_cats`` levels (wider ones keep the grouping of
    adjacent levels); () where there is none."""
    nc = int(params.get("nbins_cats", 1024))
    sf = tuple(bool(c) and 0 < len(spec.cat_domains.get(n, ())) <= nc
               for n, c in zip(spec.names, spec.is_cat))
    return sf if any(sf) else ()


def prepare_tree_inputs(spec, params, max_depth: int, *, prof,
                        mtries: int = 0,
                        random_is_adaptive: bool,
                        set_splits: bool = False) -> TreeInputs:
    """The bin stage of every dense tree trainer (GBM, XGBoost, DRF): the
    path decision (:func:`tree_path`), the sketch, digitise and pack it
    calls for, the TreeConfig, and the packed levels' plan.

    ``mtries`` is DRF's per-node feature subset; ``random_is_adaptive``
    is the trainer's reading of ``histogram_type="random"`` (see
    tree_path). ``set_splits`` (GBM under ``categorical_encoding`` auto
    or enum): on the packed path an enum feature (:func:`set_split_features`)
    splits by a set of its levels, every feature gets the lanes of its
    own bin count (ops/binning.lane_widths) and the levels route by set;
    off that path enums keep ordinal thresholds. The trainer's ``prof`` (its ``log.Profile``) times the
    stage as phase ``bin`` with ``bin.sketch``, ``bin.digitize``
    (ops/binning.bin_matrix_device) and ``bin.pack`` inside it, each
    ended by a fence on what it dispatched: the digitise's temporaries
    are freed before the pack allocates, and the boost loop's clock
    starts on a device with nothing of the bin stage in flight."""
    p = params
    F = spec.n_features
    hist_type = (p.get("histogram_type") or "uniform_adaptive").lower()
    path = partial(tree_path, hist_type, packed_codes_requested(p),
                   n_features=F, max_depth=max_depth,
                   adaptive_fits=adaptive_feasible(spec, p, max_depth),
                   random_is_adaptive=random_is_adaptive)
    from h2o3_tpu.ops.binning import lane_widths
    set_feats = set_split_features(spec, p) if set_splits else ()
    with prof.phase("bin"):
        # from the categorical domains alone: where packing cannot come in
        # under its lane and VMEM caps, take the adaptive kernel without
        # paying the sketch and digitise
        lanes = None
        if set_feats:
            nb, nc = max(int(p["nbins"]), 2), int(p["nbins_cats"])
            lanes = sum(lane_widths(
                [len(spec.cat_domains[n]) if s else
                 min(len(spec.cat_domains.get(n, ())), nc + 1) if c else nb
                 for n, c, s in zip(spec.names, spec.is_cat, set_feats)]))
        mode = path(packed_bins_upper_bound(spec, p), lanes=lanes)
        bm = pc = level_plan = None
        widths = bin_counts = ()
        if mode != "adaptive":
            # device-side sketch: X never leaves HBM. While packing is
            # still on offer the int32 transposed operand (with_t) is
            # skipped: pack_codes supersedes it with the int8/int16
            # layouts, and a rows*F*4 copy built to be dropped would cost
            # the HBM the packing saves
            bm = bin_matrix_device(
                spec.X, spec.names, spec.is_cat, spec.nrow,
                nbins=max(int(p["nbins"]), 2),
                nbins_cats=int(p["nbins_cats"]), histogram_type=hist_type,
                with_t=path(None) != "packed", prof=prof)
            # the sketch's own bin count: past the 254-lane cap or VMEM,
            # packing falls back to the fused adaptive kernel, not to the
            # slow matmul path the sketch would otherwise route to
            if set_feats:
                bin_counts = tuple(len(e) + 1 for e in bm.edges)
                widths = lane_widths(bin_counts)
            mode = path(bm.n_bins, lanes=sum(widths) if widths else None)
        if mode != "packed":
            set_feats = widths = bin_counts = ()    # enums stay ordinal
        if mode == "adaptive":
            bm = None
            cfg, root_lo, root_hi, nb_f = adaptive_setup(
                spec, p, max_depth, mtries=min(mtries, F))
        else:
            if mode == "packed":
                with prof.phase("bin.pack"):
                    pc = pack_codes(bm, widths=widths)
                    # free the int32 code view: the packed layouts replace
                    # it (1-2 bytes/value x2 <= half the f32 X footprint);
                    # only bm.edges / n_bins are read from here on
                    bm.codes = CodesView(rm=pc.rm, t=None)
                    # this path's bin fence (see the other paths' below),
                    # inside the phase whose device work it waits for
                    jax.block_until_ready(pc)  # h2o3-lint: allow[transfer-seam] bin-stage timing fence: replaces time the loop-entry fence already waited, unattributed
            cfg = tree_config(p, max_depth, bm.n_bins, bm.n_features,
                              mtries=min(mtries, bm.n_features))
            if set_feats:
                from dataclasses import replace as dc_replace
                cfg = dc_replace(cfg, set_feats=set_feats,
                                 bin_counts=bin_counts, lane_widths=widths)
            root_lo = jnp.zeros(cfg.n_features, jnp.float32)
            root_hi = jnp.zeros(cfg.n_features, jnp.float32)
            nb_f = jnp.zeros(cfg.n_features, jnp.float32)
        if mode == "packed":
            # the level kernel, feature block and row tile the packed
            # levels will run, and how the margin update reads a leaf's
            # value (by the tree's size): for the loop span and the
            # model's record
            level_plan = {
                **binned_level_plan(pc.W, cfg.n_features, binned_method(cfg),
                                    widths),
                "leaf_lookup": node_lookup_form(cfg.n_nodes),
                "n_nodes": cfg.n_nodes,
                # what a level accumulates (level_child_sums, by the
                # precision a shard's rows choose in grow_tree_binned) and
                # the deepest level's accumulator rows
                "level_hist": (
                    "smaller_child" if level_derives(_hist_mxu_dtype(
                        cfg, pc.rm.sharding.shard_shape(pc.rm.shape)[0]))
                    else "both_children"),
                "acc_rows": level_acc_rows(
                    2 ** (max_depth - 2) if max_depth > 1 else 0)}
        else:
            # the work above is dispatched, not done: wait for it here so
            # bin_s carries it. The loop-entry fence absorbed it otherwise,
            # in no span at all (about 11 s of a 13.5 s warm train at
            # 10M x 28 on the v5e, PR 22)
            jax.block_until_ready(  # h2o3-lint: allow[transfer-seam] bin-stage timing fence: replaces time the loop-entry fence already waited, unattributed
                (root_lo, root_hi) if mode == "adaptive" else bm.codes)
    return TreeInputs(mode, cfg, bm, pc, root_lo, root_hi, nb_f, level_plan)


def grow_tree_adaptive(X, g, h, w, cfg: TreeConfig, col_mask, root_lo,
                       root_hi, axis_name=None, key=None, nb_f=None,
                       mono=None, sets=None, model_axis=None):
    """Build one tree with PER-NODE ADAPTIVE uniform bins on raw features
    (H2O's default histogram_type=UniformAdaptive, hex/tree/DHistogram.java
    _min/_maxEx per-node re-binning) via the fused route+bin+histogram
    kernel (ops/hist_adaptive.py).

    X is [rows, F] float32 with NaN=NA (enum codes as floats — identity
    uniform bins reproduce ordinal enum splits). root_lo/root_hi are [F]
    global finite min/max (computed once per training run). Returns a
    tree dict with RAW split thresholds (``thr``) — no bin→threshold
    conversion at finalize, and training-time routing (x >= thr inside
    the kernel) is bit-identical to scoring-time walks.

    Child ranges narrow by the parent's split point on the split feature
    (exact) and by the parent's occupied-bin span elsewhere (within one
    bin width) — the static-shape analog of DHistogram's per-child
    min/max re-measurement.

    ``nb_f`` ([F] float, optional) gives PER-FEATURE bin counts: enums get
    nb = their root span so identity binning reproduces exact per-level
    splits up to W-1 categories (beyond that, ordinal grouping refined by
    narrowing — the nbins_cats analog)."""
    from h2o3_tpu.ops.hist_adaptive import (adaptive_level, pick_W,
                                            route_only)
    from dataclasses import replace as dc_replace

    D = cfg.max_depth
    M = cfg.n_nodes
    rows, F = X.shape
    W = pick_W(cfg.n_bins)
    # hist_kernel param: pallas/scatter honored; 'matmul' (a global-path
    # kernel name) degrades to scatter here
    method = (cfg.hist_method if cfg.hist_method in ("pallas", "scatter")
              else "scatter" if cfg.hist_method == "matmul" else "auto")
    # histogram_precision='auto': exact f32 when the frame is small
    # enough that the 1.4x hist cost is negligible, bf16 at scale.
    # Measured bound (tools/bf16_deviation.py, 2M rows, depth 8,
    # adversarial near-duplicate features): bf16 flips ~30% of split
    # choices BETWEEN statistically equivalent candidates; AUC delta
    # 2.8e-5. Deepest-level leaf values come from the same histograms,
    # so they carry the same precision choice (exact under 'float32').
    mxu_dtype = _hist_mxu_dtype(cfg, X.shape[0])
    if nb_f is None:
        nb_f = jnp.full(F, float(min(cfg.n_bins, W - 2)), jnp.float32)
    else:
        nb_f = jnp.minimum(nb_f.astype(jnp.float32), float(W - 2))
    find_cfg = dc_replace(cfg, n_bins=W - 1)  # NA lane at W-1 for _find_splits

    feat = jnp.full(M, -1, jnp.int32)
    thr_arr = jnp.zeros(M, jnp.float32)
    na_left = jnp.zeros(M, bool)
    is_split = jnp.zeros(M, bool)
    value = jnp.zeros(M, jnp.float32)
    gain_arr = jnp.zeros(M, jnp.float32)
    node_w = jnp.zeros(M, jnp.float32)

    ghw = jnp.stack([g, h, w]).astype(jnp.float32)
    nid = jnp.zeros(rows, jnp.int32)
    # per-(node, feature) ranges for the current level
    lo_d = jnp.broadcast_to(root_lo[None, :], (1, F)).astype(jnp.float32)
    hi_d = jnp.broadcast_to(root_hi[None, :], (1, F)).astype(jnp.float32)
    # previous level's split tables (root has none)
    zeros1 = jnp.zeros(1, jnp.float32)
    tables = (zeros1, zeros1, zeros1, zeros1)
    lo_b = jnp.full(1, -BIGV)          # monotone value bounds per node
    hi_b = jnp.full(1, BIGV)
    allowed = (jnp.ones((1, F), bool) if sets is not None else None)

    # histogram_type=random: per-(tree, feature) grid phase offset in
    # [0, 1) bin widths (key differs per tree → split points randomized
    # the way DHistogram.Random randomizes its bin boundaries)
    phase = None
    if cfg.random_grid and key is not None:
        phase = jax.random.uniform(jax.random.fold_in(key, 7919), (F,))

    # bandwidth-packed transpose for the pallas path: [rows, F] device
    # layout pads F to 128 lanes (~4.6x wasted HBM reads at F=28 —
    # measured in ops/hist_adaptive.py header); [F, rows] puts rows in
    # lanes. XLA hoists this loop-invariant transpose out of the per-tree
    # scan, so it costs one pass per chunk, not per level.
    on_tpu = (method == "pallas"
              or (method == "auto" and jax.default_backend() == "tpu"))
    Xt = X.T if on_tpu else None

    for d in range(D):
        N = 2 ** d
        base = N - 1
        if phase is not None:
            width0 = jnp.maximum(hi_d - lo_d, 0.0) / jnp.maximum(
                nb_f[None, :], 1.0)
            lo_d = lo_d - phase[None, :] * width0
        span = jnp.maximum(hi_d - lo_d, 0.0)
        inv_d = jnp.where(span > 0,
                          nb_f[None, :] / jnp.where(span > 0, span, 1.0), 0.0)
        nid, hist = adaptive_level(X, nid, ghw, tables, lo_d, inv_d,
                                   N // 2 if d else 0, N, base, W, method,
                                   mxu_dtype=mxu_dtype, xt=Xt)
        if axis_name is not None:
            hist = jax.lax.psum(hist, axis_name)
        trip = (hist[0], hist[1], hist[2])
        level_mask = col_mask
        mt_d = _level_mtries(cfg, d, F)
        if mt_d > 0 and key is not None:
            u = jax.random.uniform(jax.random.fold_in(key, d), (N, F))
            u = jnp.where(col_mask[None, :], u, 2.0)
            kth = jnp.sort(u, axis=1)[:, min(mt_d, F) - 1]
            level_mask = (u <= kth[:, None]) & col_mask[None, :]
        if allowed is not None:
            lm2 = level_mask if level_mask.ndim == 2 else level_mask[None, :]
            level_mask = lm2 & allowed
        bg, bf, bb, bnl, gt, ht, wt, vl_s, vr_s, wl_s, wr_s = \
            _find_splits_sharded(trip, find_cfg, level_mask, mono=mono,
                                 model_axis=model_axis)
        can = (bg > jnp.maximum(cfg.min_split_improvement, 0.0)) & (wt > 0)
        nidx = jnp.arange(N)
        lo_sel = lo_d[nidx, bf]
        inv_sel = inv_d[nidx, bf]
        # raw threshold: left ⇔ bin < t ⇔ x < lo + t/inv. Never store inf
        # (the kernel's one-hot LUT matmul turns inf·0 into NaN and
        # poisons every row's threshold at that level): a zero-span split
        # (NA-vs-finite on a constant feature) uses a huge FINITE value so
        # all finite rows still route left; non-split nodes get 0.0.
        BIG = jnp.float32(3.0e38)
        thr = jnp.where(can,
                        jnp.where(inv_sel > 0,
                                  lo_sel + bb.astype(jnp.float32)
                                  / jnp.maximum(inv_sel, 1e-30), BIG),
                        0.0)
        idx = base + nidx
        feat = feat.at[idx].set(jnp.where(can, bf, -1))
        thr_arr = thr_arr.at[idx].set(thr)
        na_left = na_left.at[idx].set(bnl)
        is_split = is_split.at[idx].set(can)
        value = value.at[idx].set(
            jnp.clip(_leaf_value(gt, ht, cfg), lo_b, hi_b))
        gain_arr = gain_arr.at[idx].set(jnp.where(can, bg, 0.0))
        node_w = node_w.at[idx].set(wt)
        if mono is not None:
            lo_b, hi_b = _child_bounds(lo_b, hi_b, vl_s, vr_s, mono[bf], can)
        else:
            lo_b = jnp.repeat(lo_b, 2)
            hi_b = jnp.repeat(hi_b, 2)
        if allowed is not None:
            allowed = _next_allowed(allowed, sets, bf, can)
        # next level's routing tables
        tables = (jnp.maximum(bf, 0).astype(jnp.float32), thr,
                  bnl.astype(jnp.float32), can.astype(jnp.float32))
        # next level's ranges: occupied-span narrowing + split-point cut
        whist = hist[2][..., :W - 1]                  # [N, F, W-1] real bins
        occ = whist > 0
        first = jnp.argmax(occ, axis=-1)              # [N, F]
        last = (W - 2) - jnp.argmax(occ[..., ::-1], axis=-1)
        width = jnp.where(inv_d > 0, 1.0 / jnp.maximum(inv_d, 1e-30), 0.0)
        lo_n = lo_d + first.astype(jnp.float32) * width
        hi_n = jnp.minimum(lo_d + (last + 1).astype(jnp.float32) * width, hi_d)
        any_occ = occ.any(axis=-1)
        lo_n = jnp.where(any_occ, lo_n, lo_d)
        hi_n = jnp.where(any_occ, hi_n, hi_d)
        fsel = (jnp.arange(F)[None, :] == bf[:, None]) & can[:, None]
        lo_left, hi_left = lo_n, jnp.where(fsel, jnp.minimum(thr[:, None], hi_n), hi_n)
        lo_right, hi_right = jnp.where(fsel, jnp.maximum(thr[:, None], lo_n), lo_n), hi_n
        lo_d = jnp.stack([lo_left, lo_right], axis=1).reshape(2 * N, F)
        hi_d = jnp.stack([hi_left, hi_right], axis=1).reshape(2 * N, F)

    # deepest level: leaf values are the LAST split level's selected
    # left/right child stats — already in the (psum'd) histograms, so the
    # final pass only needs to ROUTE rows for the margin update (a ~3x
    # cheaper kernel than a full level; with histogram_precision=float32
    # these stats are exact, with bf16 they carry the documented bound)
    if D == 0:
        # degenerate stump: one root leaf from exact totals
        g0 = g * (w > 0)
        h0 = h * (w > 0)
        gs, hs, ws = g0.sum(), h0.sum(), w.sum()
        if axis_name is not None:
            gs = jax.lax.psum(gs, axis_name)
            hs = jax.lax.psum(hs, axis_name)
            ws = jax.lax.psum(ws, axis_name)
        value = value.at[0].set(_leaf_value(gs, hs, cfg))
        node_w = node_w.at[0].set(ws)
        tree = {"feat": feat, "thr": thr_arr, "na_left": na_left,
                "is_split": is_split, "value": value, "gain": gain_arr,
                "node_w": node_w}
        return tree, nid
    ND = 2 ** D
    baseD = ND - 1
    nid = route_only(X, nid, tables, ND // 2, baseD, method, xt=Xt)
    vD = jnp.stack([vl_s, vr_s], axis=1).reshape(ND)
    wD = jnp.stack([wl_s, wr_s], axis=1).reshape(ND)
    idxD = baseD + jnp.arange(ND)
    value = value.at[idxD].set(jnp.clip(vD, lo_b, hi_b))
    node_w = node_w.at[idxD].set(wD)

    tree = {"feat": feat, "thr": thr_arr, "na_left": na_left,
            "is_split": is_split, "value": value, "gain": gain_arr,
            "node_w": node_w}
    return tree, nid


def _hist_mxu_dtype(cfg: TreeConfig, rows: int):
    """Histogram contraction precision shared by every grower:
    ``histogram_precision`` forces f32 (exact 6-pass HIGHEST) or bf16;
    'auto' picks exact f32 below 2^18 rows where the ~1.4x hist cost
    is negligible, bf16 at scale (deviation bound in
    ops/hist_adaptive.py and README)."""
    if cfg.histogram_precision in ("float32", "f32"):
        return jnp.float32
    if cfg.histogram_precision in ("bfloat16", "bf16"):
        return jnp.bfloat16
    return jnp.float32 if rows < (1 << 18) else jnp.bfloat16


def levels_per_pass(max_depth: int, n_features: int, W: int) -> int:
    """Resolve ``H2O3_LEVELS_PER_PASS`` — how many consecutive tree
    levels one fused dispatch covers in the streamed binned driver.

    - integer: clamped to [1, max_depth]; 1 is the exact old per-level
      path (one dispatch + one host sync per level);
    - unset / 'auto': VMEM-budgeted — the largest L <= 4 whose DEEPEST
      possible window keeps the sum of its levels' histograms in node
      order (3 · 2^d · F · W · 4 bytes over the window; the kernels'
      accumulators hold half of each) inside half the
      kernel VMEM limit, the same ceiling the per-level accumulator
      scratch is provisioned against. L=4 everywhere practical; the
      bound only bites at extreme depth × features × W products where
      the fused executable's histogram working set would thrash.
    """
    from h2o3_tpu.ops.hist_adaptive import _VMEM_LIMIT
    D = max(1, int(max_depth))
    raw = _os.environ.get("H2O3_LEVELS_PER_PASS", "").strip().lower()
    if raw and raw != "auto":
        return max(1, min(int(raw), D))
    budget = _VMEM_LIMIT // 2
    L = 1
    while L < min(4, D):
        cand = L + 1
        top = sum(3 * (1 << d) * n_features * W * 4
                  for d in range(max(0, D - cand), D))
        if top > budget:
            break
        L = cand
    return L


def _binned_split_level(trip, find_cfg: TreeConfig, level_mask,
                        cfg: TreeConfig, mono=None, model_axis=None):
    """ONE level's split selection + the derived next-level routing
    tables, shared by every binned driver: the dense trace-time loop,
    the streamed per-level pass and the fused L-level window all run
    THIS function, so the multi-level path traces exactly the
    per-level ops and f32 bit-parity holds by construction. Returns
    (the _find_splits 11-tuple, can, tables)."""
    sel = _find_splits_sharded(trip, find_cfg, level_mask, mono=mono,
                               model_axis=model_axis, max_bin=cfg.n_bins)
    bg, bf, bb, bnl = sel[0], sel[1], sel[2], sel[3]
    wt_ = sel[6]
    can = (bg > jnp.maximum(cfg.min_split_improvement, 0.0)) & (wt_ > 0)
    # next level's routing tables: the split BIN rides where the
    # adaptive path carries a raw threshold — an exact integer-valued
    # float; ``can`` also says which child the next level BUILDS: the one
    # with the smaller w (ops/hist_adaptive.py's conventions say why),
    # chosen after the data psum so every shard chooses alike
    tables = (jnp.maximum(bf, 0).astype(jnp.float32),
              bb.astype(jnp.float32), bnl.astype(jnp.float32),
              can_entry(can, sel[10] < sel[9]))
    if cfg.set_feats:
        # routing by set: the chosen feature's lane offset rides where the
        # bin did, and a fifth table holds the left set over the feature's
        # LOCAL codes, its NA code's entry the NA direction
        from h2o3_tpu.ops.hist_adaptive import lane_offsets
        f = jnp.maximum(bf, 0)
        na_code = jnp.asarray(cfg.lane_widths)[f] - 1
        code = jnp.arange(max(cfg.lane_widths))[None, :]
        left = jnp.pad(sel[11], ((0, 0), (0, 1)))       # [N, W], W = B + 1
        left = jnp.where(code == na_code[:, None], bnl[:, None], left)
        tables = (tables[0],
                  jnp.asarray(lane_offsets(cfg.lane_widths),
                              jnp.float32)[f],
                  tables[2], tables[3], left.astype(jnp.float32))
    return sel, can, tables


def level_derives(mxu_dtype) -> bool:
    """Whether a packed level takes each sibling as parent - built (bf16
    sums) or builds it too (float32 histograms, which promise a node's sums
    to the rounding of its own rows: ops/hist_adaptive.py, PRECISION)."""
    return mxu_dtype != jnp.float32


def packed_tree_psum_bytes(cfg: TreeConfig, W: int, derives: bool) -> int:
    """Bytes ONE packed tree all-reduces over the data axis, from shapes:
    a level psums what its kernels accumulated (:func:`level_child_sums`:
    (g, h, w) of one child a previous-level node, of both where the level
    does not derive) over every lane in float32, and the tree's end the
    leaves' (g, h, w) totals."""
    lanes = sum(cfg.lane_widths) if cfg.lane_widths else cfg.n_features * W
    rows = sum((1 if d == 0 or derives else 2) * level_acc_rows(2 ** d // 2)
               for d in range(cfg.max_depth))
    return 4 * (rows * lanes + 3 * 2 ** cfg.max_depth)


def level_child_sums(codes_rm, nid, ghw, tables, n_prev: int, level_base: int,
                     W: int, method: str = "auto", mxu_dtype=jnp.bfloat16,
                     ct=None, widths: tuple = ()):
    """What a packed level accumulates over one holder of rows (a shard, a
    chunk): (nid', sums [k, 3, max(n_prev, 1), ...]), LINEAR in the rows, so
    shards psum it and chunks add it before :func:`sibling_level_hist`.
    k = 1: ``binned_level``'s built child of every previous-level node (at
    the root, the root). k = 2 below the root where the level does not
    derive (:func:`level_derives`): the sibling as well, by the same kernel
    called again on the same ``nid`` with the other child chosen."""
    def level(tabs):
        return binned_level(codes_rm, nid, ghw, tabs, n_prev, level_base, W,
                            method, mxu_dtype=mxu_dtype, ct=ct, widths=widths)
    nid2, built = level(tables)
    if n_prev == 0 or level_derives(mxu_dtype):
        return nid2, built[None]
    other = level(tables[:3] + (can_other_child(tables[3]),) + tables[4:])[1]
    return nid2, jnp.stack([built, other])


def sibling_level_hist(sums, parent, tables):
    """A level's histogram [3, N, ...] in node order from what its kernels
    accumulated: ``sums`` [k, 3, N/2, ...] (:func:`level_child_sums`,
    already summed over shards and chunks: the subtraction is linear),
    ``parent`` the previous level's own [3, N/2, ...] (None at the root,
    whose histogram ``sums[0]`` is) and ``tables``, the routing tables that
    level handed down, whose ``can`` entry says which child was built
    first. With k = 1 the sibling is parent - built. A node that did not
    split gives BOTH children an empty histogram: routing reaches neither,
    and the parent's mass left on one of them would be searched, and
    recorded, as a split. Every packed driver reassembles its levels
    here."""
    built = sums[0]
    if parent is None:
        return built
    can = tables[3].reshape((1, -1) + (1,) * (built.ndim - 2))
    other = (sums[1] if sums.shape[0] == 2
             else jnp.where(can_splits(can), parent - built, 0.0))
    right_built = can_builds_right(can)
    pair = jnp.stack([jnp.where(right_built, other, built),
                      jnp.where(right_built, built, other)], axis=2)
    return pair.reshape(3, 2 * built.shape[1], *built.shape[2:])


def padded_level_hist(hist, cfg: TreeConfig):
    """A level's flat histogram [3, N, lanes] (features at their lane
    offsets, ``cfg.lane_widths``) as the split search's [3, N, F, W]:
    each feature's real bins from lane 0, its NA lane last, W the widest
    feature's lane count."""
    from h2o3_tpu.ops.hist_adaptive import lane_offsets
    W = max(cfg.lane_widths)
    cols = []
    for off, w, nb in zip(lane_offsets(cfg.lane_widths), cfg.lane_widths,
                          cfg.bin_counts):
        cols.append(jnp.concatenate(
            [hist[..., off:off + nb],
             jnp.zeros(hist.shape[:2] + (W - 1 - nb,), hist.dtype),
             hist[..., off + w - 1:off + w]], axis=-1))
    return jnp.stack(cols, axis=2)


def pack_set_bits(left, set_split, can, n_words: int):
    """[N, n_words] uint32: bit ``b`` of a node's words says level ``b``
    of its split feature goes left; zeros where the node splits on no
    set."""
    bits = jnp.pad(left, ((0, 0), (0, max(n_words * 32 - left.shape[1], 0)))
                   )[:, :n_words * 32]
    bits = bits & (set_split & can)[:, None]
    words = bits.reshape(-1, n_words, 32).astype(jnp.uint32) << jnp.arange(
        32, dtype=jnp.uint32)
    return words.sum(axis=-1, dtype=jnp.uint32)


def _level_record(sel, can, cfg: TreeConfig):
    """The per-level split record the streamed drivers fetch to host —
    built on device, batched into ONE counted pytree fetch per L-level
    window (transfer-seam contract)."""
    bg, bf, bb, bnl = sel[0], sel[1], sel[2], sel[3]
    gt, ht, wt_ = sel[4], sel[5], sel[6]
    return {"feat": jnp.where(can, bf, -1), "bin": bb, "nal": bnl,
            "can": can, "val": _leaf_value(gt, ht, cfg),
            "gain": jnp.where(can, bg, 0.0), "w": wt_}


@lru_cache(maxsize=64)
def _fused_binned_window(cfg: TreeConfig, d0: int, Lw: int, W: int,
                         trans: bool, mxu_name: str):
    """ONE jitted executable running ``Lw`` consecutive binned levels:
    route + histogram + split selection + next-level tables, unrolled
    Lw times at trace time exactly like the dense grower's loop. The
    packed codes operand is read once per window, ``nid`` and the
    routing tables carry on-device between levels, and the host syncs
    only on the window-boundary record fetch — eliminating per-level
    dispatch overhead and per-level nid round-trips. Each level's body
    is the streamed per-level pass verbatim (binned_level +
    _binned_split_level + _level_record), so f32 multi-level trees are
    bit-identical to the per-level path. lru-cached per (cfg, window,
    layout): a warm retrain reuses the executable (zero-recompile
    guard)."""
    from dataclasses import replace as dc_replace

    find_cfg = dc_replace(cfg, n_bins=W - 1)
    mxu_dtype = jnp.float32 if mxu_name == "float32" else jnp.bfloat16

    def window(x, nid, ghw, tables, col_mask, hist):
        recs = []
        for j in range(Lw):
            d = d0 + j
            N = 1 << d
            nid, sums = level_child_sums(
                None if trans else x, nid, ghw, tables,
                N // 2 if d else 0, N - 1, W,
                mxu_dtype=mxu_dtype, ct=x if trans else None)
            hist = sibling_level_hist(sums, hist if d else None, tables)
            sel, can, tables = _binned_split_level(
                (hist[0], hist[1], hist[2]), find_cfg, col_mask, cfg)
            recs.append(_level_record(sel, can, cfg))
        return nid, recs, tables, hist

    return jax.jit(window)


def binned_method(cfg: TreeConfig) -> str:
    """``hist_kernel`` as the packed level's dispatch reads it."""
    return (cfg.hist_method if cfg.hist_method in ("pallas", "scatter")
            else "scatter" if cfg.hist_method == "matmul" else "auto")


def grow_tree_binned(codes_rm, g, h, w, cfg: TreeConfig, col_mask,
                     axis_name=None, key=None, mono=None, sets=None,
                     model_axis=None, ct=None):
    """Build one tree on PACKED global-sketch bin codes — the
    XGBoost ``tree_method=hist`` shape made TPU-native: features are
    binned ONCE per train (ops/binning.pack_codes), the int8/int16
    code matrix is the representation the hot loop computes on, split
    thresholds thread through the levels as BIN INDICES, and finalize
    unbins to raw thresholds (bins_to_thresholds_stacked reads
    ``tree["split_bin"]``).

    ``codes_rm`` is [rows, F] int8/int16 with NA = the reserved bin
    W-1; ``ct`` is the pre-transposed [F, rows_p] pallas operand
    (pad = W-1). cfg.n_bins is the REAL bin count (codes in
    [0, n_bins-1]); the kernel lane width is W = pick_W(n_bins) and
    the split search scans W-1 real lanes with the NA lane at W-1
    (lanes beyond n_bins are empty; a selected split bin past the
    edge list unbins to +inf = all non-NA left).

    Per level the fused binned kernel routes rows by integer
    code-vs-bin compare and builds the histogram one-hot straight off
    the codes — no lo/inv rebinning anywhere, so the hot loop moves
    1-2 bytes/value instead of 4. Below the root a call accumulates ONE
    child of every previous-level node, the one with the smaller w
    ([3, N/2, ...] at a level of N nodes). With bf16 sums the previous
    level's histogram is kept and the sibling is parent - built, under
    ``axis_name`` after a psum of the built half alone; float32
    histograms build the sibling by a second call
    (:func:`level_child_sums`, :func:`sibling_level_hist`)."""
    from h2o3_tpu.ops.hist_adaptive import binned_route_only, pick_W
    from dataclasses import replace as dc_replace

    D = cfg.max_depth
    M = cfg.n_nodes
    rows, F = codes_rm.shape
    widths = cfg.lane_widths
    W = max(widths) if widths else pick_W(cfg.n_bins)
    method = binned_method(cfg)
    mxu_dtype = _hist_mxu_dtype(cfg, rows)
    find_cfg = dc_replace(cfg, n_bins=W - 1)   # NA lane at W-1
    cat_set = jnp.zeros((M, cfg.set_words), jnp.uint32)
    set_split = jnp.zeros(M, bool)

    feat = jnp.full(M, -1, jnp.int32)
    split_bin = jnp.zeros(M, jnp.int32)
    na_left = jnp.zeros(M, bool)
    is_split = jnp.zeros(M, bool)
    value = jnp.zeros(M, jnp.float32)
    gain_arr = jnp.zeros(M, jnp.float32)
    node_w = jnp.zeros(M, jnp.float32)

    ghw = jnp.stack([g, h, w]).astype(jnp.float32)
    nid = jnp.zeros(rows, jnp.int32)
    zeros1 = jnp.zeros(1, jnp.float32)
    tables = (zeros1, zeros1, zeros1, zeros1)
    if widths:
        tables += (jnp.zeros((1, W), jnp.float32),)    # the root has no set
    lo_b = jnp.full(1, -BIGV)
    hi_b = jnp.full(1, BIGV)
    allowed = (jnp.ones((1, F), bool) if sets is not None else None)

    if D == 0:
        g0 = g * (w > 0)
        h0 = h * (w > 0)
        gs, hs, ws = g0.sum(), h0.sum(), w.sum()
        if axis_name is not None:
            gs = jax.lax.psum(gs, axis_name)
            hs = jax.lax.psum(hs, axis_name)
            ws = jax.lax.psum(ws, axis_name)
        value = value.at[0].set(_leaf_value(gs, hs, cfg))
        node_w = node_w.at[0].set(ws)
        tree = {"feat": feat, "split_bin": split_bin, "na_left": na_left,
                "is_split": is_split, "value": value, "gain": gain_arr,
                "node_w": node_w}
        if cfg.set_feats:
            tree.update(cat_set=cat_set, set_split=set_split)
        return tree, nid

    vl_s = vr_s = wl_s = wr_s = None
    level_hist = None
    for d in range(D):
        N = 2 ** d
        base = N - 1
        nid, sums = level_child_sums(codes_rm, nid, ghw, tables,
                                     N // 2 if d else 0, base, W, method,
                                     mxu_dtype=mxu_dtype, ct=ct,
                                     widths=widths)
        if axis_name is not None:
            sums = jax.lax.psum(sums, axis_name)
        hist = level_hist = sibling_level_hist(sums, level_hist, tables)
        if widths:
            hist = padded_level_hist(hist, cfg)
        trip = (hist[0], hist[1], hist[2])
        level_mask = col_mask
        mt_d = _level_mtries(cfg, d, F)
        if mt_d > 0 and key is not None:
            u = jax.random.uniform(jax.random.fold_in(key, d), (N, F))
            u = jnp.where(col_mask[None, :], u, 2.0)
            kth = jnp.sort(u, axis=1)[:, min(mt_d, F) - 1]
            level_mask = (u <= kth[:, None]) & col_mask[None, :]
        if allowed is not None:
            lm2 = level_mask if level_mask.ndim == 2 else level_mask[None, :]
            level_mask = lm2 & allowed
        sel, can, tables = _binned_split_level(trip, find_cfg, level_mask,
                                               cfg, mono=mono,
                                               model_axis=model_axis)
        bg, bf, bb, bnl, gt, ht, wt, vl_s, vr_s, wl_s, wr_s = sel[:11]
        nidx = jnp.arange(N)
        idx = base + nidx
        if cfg.set_feats:
            cat_set = cat_set.at[idx].set(
                pack_set_bits(sel[11], sel[12], can, cfg.set_words))
            set_split = set_split.at[idx].set(sel[12] & can)
        feat = feat.at[idx].set(jnp.where(can, bf, -1))
        split_bin = split_bin.at[idx].set(bb)
        na_left = na_left.at[idx].set(bnl)
        is_split = is_split.at[idx].set(can)
        value = value.at[idx].set(
            jnp.clip(_leaf_value(gt, ht, cfg), lo_b, hi_b))
        gain_arr = gain_arr.at[idx].set(jnp.where(can, bg, 0.0))
        node_w = node_w.at[idx].set(wt)
        if mono is not None:
            lo_b, hi_b = _child_bounds(lo_b, hi_b, vl_s, vr_s, mono[bf], can)
        else:
            lo_b = jnp.repeat(lo_b, 2)
            hi_b = jnp.repeat(hi_b, 2)
        if allowed is not None:
            allowed = _next_allowed(allowed, sets, bf, can)

    # deepest level: route, then EXACT per-leaf (g,h,w) segment totals —
    # the same tail as grow_tree, so packed and unpacked f32 trees are
    # bit-identical INCLUDING leaf values (and under bf16 the leaves
    # stay exact, like the reference's driver-side leaf stats; the
    # totals matmul is tiny next to a level kernel)
    ND = 2 ** D
    baseD = ND - 1
    nid = binned_route_only(codes_rm, nid, tables, ND // 2, baseD, W,
                            method, ct=ct)
    localD = nid - baseD
    inD = (localD >= 0) & (localD < ND)
    lidD = jnp.clip(localD, 0, ND - 1)
    gD, hD, wD = _segment_totals(lidD, inD, g, h, w, ND)
    if axis_name is not None:
        gD = jax.lax.psum(gD, axis_name)
        hD = jax.lax.psum(hD, axis_name)
        wD = jax.lax.psum(wD, axis_name)
    idxD = baseD + jnp.arange(ND)
    value = value.at[idxD].set(
        jnp.clip(_leaf_value(gD, hD, cfg), lo_b, hi_b))
    node_w = node_w.at[idxD].set(wD)

    tree = {"feat": feat, "split_bin": split_bin, "na_left": na_left,
            "is_split": is_split, "value": value, "gain": gain_arr,
            "node_w": node_w}
    if cfg.set_feats:
        tree.update(cat_set=cat_set, set_split=set_split)
    return tree, nid


def predict_raw_tree(X, tree, max_depth: int):
    """Walk ONE tree (dict of [M] arrays with raw ``thr``) over raw
    features; used for validation-margin updates inside the training
    chunk. Returns (leaf values [rows], nid)."""
    rows = X.shape[0]
    nid = jnp.zeros(rows, jnp.int32)
    for _ in range(max_depth):
        f = jnp.maximum(tree["feat"], 0)[nid]
        s = tree["is_split"][nid]
        th = tree["thr"][nid]
        nl = tree["na_left"][nid]
        xv = jnp.take_along_axis(X, f[:, None], axis=1)[:, 0]
        go_right = jnp.where(jnp.isnan(xv), ~nl, xv >= th)
        nid = jnp.where(s, 2 * nid + 1 + go_right.astype(jnp.int32), nid)
    return node_lookup(tree["value"], nid), nid


def grow_tree_spmd(codes, g, h, w, cfg: TreeConfig, col_mask,
                   data_axis: str = "data", model_axis: str = "model"):
    """Fully-sharded tree build for multi-chip meshes: rows over the
    'data' axis AND features over the 'model' axis.

    Runs inside shard_map with in_specs codes=P(data, model), g/h/w/
    col_mask sharded accordingly. Per level:
      1. each shard builds histograms for its (row-block × feature-block);
      2. psum over the data axis → complete histograms for local features
         (the ICI all-reduce replacing Rabit / the MRTask reduce tree);
      3. local split finding, then an all_gather + argmax over the model
         axis picks the global best split per node (features never move);
      4. row routing: the model-shard owning the winning feature computes
         the children for its nodes; a psum over the model axis broadcasts
         the routing to all feature shards (rows are replicated across the
         model axis, so this is a small [rows] exchange).

    The reference has no feature-axis sharding at all (SURVEY.md §5) —
    every JVM node holds all columns of its rows; this is where the TPU
    design scales wider data than the reference can.
    """
    from h2o3_tpu.ops.histogram import build_histograms

    D = cfg.max_depth
    M = cfg.n_nodes
    B1 = cfg.n_bins + 1
    rows, F_loc = codes.shape
    midx = jax.lax.axis_index(model_axis)
    n_model = jax.lax.axis_size(model_axis)

    feat = jnp.full(M, -1, jnp.int32)
    split_bin = jnp.zeros(M, jnp.int32)
    na_left = jnp.zeros(M, bool)
    is_split = jnp.zeros(M, bool)
    value = jnp.zeros(M, jnp.float32)

    ghw = jnp.stack([g, h, w]).astype(jnp.float32)
    nid = jnp.zeros(rows, jnp.int32)
    for d in range(D):
        base = 2 ** d - 1
        N = 2 ** d
        local = nid - base
        in_level = (local >= 0) & (local < N)
        lid = jnp.clip(local, 0, N - 1)
        seg = jnp.where(in_level, local, -1)
        hist = build_histograms(codes, seg, ghw, N, B1, cfg.hist_method)
        hist = jax.lax.psum(hist, data_axis)
        (bg, bf, bb, bnl, gt, ht, wt,
         _vl, _vr, _wl, _wr) = _find_splits(hist, cfg, col_mask)
        # global best over the model axis
        cand = jnp.stack([bg, (midx * F_loc + bf).astype(jnp.float32),
                          bb.astype(jnp.float32), bnl.astype(jnp.float32)], 1)
        allc = jax.lax.all_gather(cand, model_axis)          # [n_model, N, 4]
        winner = jnp.argmax(allc[:, :, 0], axis=0)           # [N]
        sel = jnp.take_along_axis(allc, winner[None, :, None], axis=0)[0]
        gbg, gbf, gbb, gbnl = sel[:, 0], sel[:, 1].astype(jnp.int32), \
            sel[:, 2].astype(jnp.int32), sel[:, 3] > 0.5
        can = (gbg > jnp.maximum(cfg.min_split_improvement, 0.0)) & (wt > 0)
        idx = base + jnp.arange(N)
        feat = feat.at[idx].set(jnp.where(can, gbf, -1))
        split_bin = split_bin.at[idx].set(gbb)
        na_left = na_left.at[idx].set(gbnl)
        is_split = is_split.at[idx].set(can)
        value = value.at[idx].set(_leaf_value(gt, ht, cfg))
        # routing: owner shard of each node's feature computes children
        node_feat_g = gbf[lid]
        owner = node_feat_g // F_loc
        node_feat_l = node_feat_g % F_loc
        node_bin = gbb[lid]
        node_nal = gbnl[lid]
        node_can = can[lid]
        c = jnp.take_along_axis(codes, node_feat_l[:, None], axis=1)[:, 0]
        c = c.astype(jnp.int32)
        is_na = c == cfg.n_bins
        go_right = jnp.where(is_na, ~node_nal, c >= node_bin)
        child = 2 * nid + 1 + go_right.astype(jnp.int32)
        mine = (owner == midx) & in_level & node_can
        routed = jnp.where(mine, child, 0)
        routed = jax.lax.psum(routed, model_axis)
        nid = jnp.where(in_level & node_can, routed, nid)

    baseD = 2 ** D - 1
    localD = nid - baseD
    inD = (localD >= 0) & (localD < 2 ** D)
    lidD = jnp.clip(localD, 0, 2 ** D - 1)
    gD = jnp.zeros(2 ** D, jnp.float32).at[lidD].add(jnp.where(inD, g, 0.0))
    hD = jnp.zeros(2 ** D, jnp.float32).at[lidD].add(jnp.where(inD, h, 0.0))
    gD = jax.lax.psum(gD, data_axis)
    hD = jax.lax.psum(hD, data_axis)
    idxD = baseD + jnp.arange(2 ** D)
    value = value.at[idxD].set(_leaf_value(gD, hD, cfg))

    tree = {"feat": feat, "split_bin": split_bin, "na_left": na_left,
            "is_split": is_split, "value": value}
    return tree, nid


# segments up to which _segment_totals factors the node one-hot (64 x 64)
_SEGMENT_FACTOR_MAX = 4096


def _segment_totals(lid, valid, g, h, w, n_seg: int):
    """Per-node (g,h,w) sums. One-hot matmul for small node counts (TPU
    scatter-add costs ~20ms/1M rows; the matmul is <1ms). Up to
    _SEGMENT_FACTOR_MAX segments (the leaves of depths 9 to 12) the node
    id is split into a high and a low part, 2^k each: the sums are one
    [rows, hi]^T x [rows, 3 lo] product of two small one-hots at HIGHEST
    precision, exact as the scatter's adds are, where the scatter took
    three passes of 20 ms a million rows (1.2 s a depth-10 tree at 40M
    rows). Scatter beyond."""
    if n_seg <= 256:
        oh = (lid[:, None] == jnp.arange(n_seg)[None, :]).astype(jnp.float32)
        ghw = jnp.stack([jnp.where(valid, g, 0.0), jnp.where(valid, h, 0.0),
                         jnp.where(valid, w, 0.0)], axis=1)
        tot = jax.lax.dot_general(oh, ghw, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return tot[:, 0], tot[:, 1], tot[:, 2]
    if n_seg <= _SEGMENT_FACTOR_MAX:
        n_lo = 1 << ((n_seg - 1).bit_length() // 2)
        n_hi = -(-n_seg // n_lo)
        hi = (lid // n_lo)[:, None] == jnp.arange(n_hi)[None, :]
        # column c of the right side: (g, h, w)[c // n_lo] where the row's
        # low part is c % n_lo; elementwise in (row, c), so it fuses into
        # the product's operand and no [rows, 3 lo] array exists
        col = jnp.arange(3 * n_lo)[None, :]
        val = jnp.where(col < n_lo, g[:, None],
                        jnp.where(col < 2 * n_lo, h[:, None], w[:, None]))
        right = jnp.where(((lid % n_lo)[:, None] == col % n_lo)
                          & valid[:, None], val, 0.0)
        tot = jax.lax.dot_general(
            hi.astype(jnp.float32), right, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)            # [hi, 3 * lo]
        tot = tot.reshape(n_hi, 3, n_lo).transpose(1, 0, 2).reshape(
            3, n_hi * n_lo)[:, :n_seg]
        return tot[0], tot[1], tot[2]
    gD = jnp.zeros(n_seg, jnp.float32).at[lid].add(jnp.where(valid, g, 0.0))
    hD = jnp.zeros(n_seg, jnp.float32).at[lid].add(jnp.where(valid, h, 0.0))
    wD = jnp.zeros(n_seg, jnp.float32).at[lid].add(jnp.where(valid, w, 0.0))
    return gD, hD, wD


def refuse_set_splits(model, what: str) -> None:
    """For a reader of ``thr`` that cannot read a set: a clear error on a
    model with category-set splits, where it would misread NaN
    thresholds."""
    if getattr(model, "_cat_set", None) is not None:
        raise NotImplementedError(
            f"{what} reads every split as a threshold, and model "
            f"{getattr(model, 'key', '?')} splits enum columns on sets of "
            f"levels (categorical_encoding 'auto'/'enum'); train with "
            f"categorical_encoding='label_encoder' for thresholds on the "
            f"level index")


def set_levels(words: np.ndarray, n_levels: int) -> np.ndarray:
    """A node's exported set words [n] uint32 as a bool per level."""
    bits = (np.asarray(words, np.uint32)[:, None]
            >> np.arange(32, dtype=np.uint32)) & 1
    out = np.zeros(n_levels, bool)
    k = min(n_levels, bits.size)
    out[:k] = bits.reshape(-1)[:k].astype(bool)
    return out


def set_bit(words, code):
    """Whether bit ``code`` of a set is on: ``words`` [..., n] uint32 a
    row, ``code`` [...] int32; False outside the words."""
    n = words.shape[-1]
    inside = (code >= 0) & (code < 32 * n)
    c = jnp.clip(code, 0, 32 * n - 1)
    word = jnp.take_along_axis(words, (c >> 5)[..., None], axis=-1)[..., 0]
    return inside & (((word >> (c & 31).astype(jnp.uint32)) & 1) == 1)


def predict_binned(codes, tree, max_depth: int, na_bin):
    """Prediction on a binned matrix (leaf lookup); one packed-word gather
    per level (see grow_tree routing). ``na_bin`` is the NA code, one a
    feature where the codes are packed under per-feature lane widths; a
    tree with ``cat_set`` sends a row at a set split left iff its code's
    bit is on (validation margins inside the boost chunk)."""
    rm = codes.rm if isinstance(codes, CodesView) else codes
    rows = rm.shape[0]
    na_of = jnp.asarray(na_bin, jnp.int32)
    word = (jnp.maximum(tree["feat"], 0)
            | (tree["split_bin"] << BIN_SHIFT)
            | (tree["na_left"].astype(jnp.int32) << NA_SHIFT)
            | (tree["is_split"].astype(jnp.int32) << SPLIT_SHIFT))
    nid = jnp.zeros(rows, jnp.int32)
    for d in range(max_depth):
        # before level d's step a row sits above level d + 1: only
        # those nodes' words can be named
        rw = node_lookup(word[:2 ** (d + 1) - 1], nid)
        f = rw & FEAT_MASK
        b = (rw >> BIN_SHIFT) & BIN_MASK
        nl = ((rw >> NA_SHIFT) & 1).astype(bool)
        s = ((rw >> SPLIT_SHIFT) & 1).astype(bool)
        c = jnp.take_along_axis(rm, f[:, None], axis=1)[:, 0]
        c = c.astype(jnp.int32)
        is_na = c == (na_of[f] if na_of.ndim else na_of)
        go_right = jnp.where(is_na, ~nl, c >= b)
        if "cat_set" in tree:
            go_right = jnp.where(
                tree["set_split"][nid] & ~is_na,
                ~set_bit(tree["cat_set"][nid], c), go_right)
        nid = jnp.where(s, 2 * nid + 1 + go_right.astype(jnp.int32), nid)
    return node_lookup(tree["value"], nid), nid


def _level_of(x):
    """A column's values as enum levels: the level index, -1 where the
    value is NA or no level (negative, fractional parts dropped)."""
    return jnp.where((x >= 0) & (x < 2.0 ** 30), x, -1.0).astype(jnp.int32)


def _score_tree_gather(X, feat, thr, na_left, is_split, value,
                       max_depth: int, cat_set=None, is_set=None):
    """A row's leaf value in one tree by per-row lookups: four node tables
    and one element of ``X`` a level, then the value; flat in the tree's
    size. With ``cat_set`` [M, n] / ``is_set`` [M] two more: a row at a
    set split goes left iff its level's bit is on, and where NA goes if
    the level lies outside the words."""
    nid = jnp.zeros(X.shape[0], jnp.int32)
    for _ in range(max_depth):
        f = feat[nid]
        s = is_split[nid]
        th = thr[nid]
        nl = na_left[nid]
        x = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
        go_right = jnp.where(jnp.isnan(x), ~nl, x >= th)
        if cat_set is not None:
            lvl = _level_of(x)
            known = (lvl >= 0) & (lvl < 32 * cat_set.shape[-1])
            go_right = jnp.where(
                is_set[nid], jnp.where(known, ~set_bit(cat_set[nid], lvl),
                                       ~nl), go_right)
        nid = jnp.where(s, 2 * nid + 1 + go_right.astype(jnp.int32), nid)
    return value[nid]


def _block_step(XT, feat, thr, right_if_na, is_split, low, cat_set=None,
                is_set=None):
    """(go_right, is_split) of every row at node ``low`` of a block of
    nodes (tables [B], ``feat`` clipped at 0): each node's predicate over
    ALL rows from scalars of its tables and one contiguous column of
    ``XT``, then the row's own selected on ``low``'s bits. Plain ``lax``
    calls where ``jnp``'s would do: a nested ``jnp`` wrapper is a trace of
    its own, and a level unrolls up to _PREDICATE_BLOCK of these when the
    scorer's program is traced. With ``cat_set``
    [B, n] / ``is_set`` [B] a node's predicate is the set's where the
    node splits on one: the word selected on the level's high bits, its
    bit tested, no per-row index."""
    go = []
    for j in range(feat.shape[0]):
        x = lax.dynamic_index_in_dim(XT, feat[j], 0, keepdims=False)
        na = lax.broadcast(right_if_na[j], x.shape)
        right = lax.select(lax.ne(x, x), na,
                           lax.ge(x, lax.broadcast(thr[j], x.shape)))
        if cat_set is not None:
            lvl = _level_of(x)
            n = cat_set.shape[-1]
            word = _select_tree(cat_set[j], jnp.clip(lvl >> 5, 0, n - 1))
            out = (((word >> (lvl & 31).astype(jnp.uint32)) & 1) == 0)
            known = (lvl >= 0) & (lvl < 32 * n)
            right = jnp.where(is_set[j], jnp.where(known, out, na), right)
        go.append(right)
    return _select_tree(go, low), _select_tree(is_split, low)


def _score_tree_predicates(XT, feat, thr, na_left, is_split, value,
                           max_depth: int, cat_set=None, is_set=None):
    """``_score_tree_gather``'s values with no per-row index: level d's
    2^d predicates are evaluated on whole columns and a row's own is
    selected on the bits of its place in the level (unrolled up to
    _PREDICATE_BLOCK nodes a level, a loop over blocks above), then the
    value by ``node_lookup``. The same comparison on the same operands
    and the same integer routing, so the bits are equal; O(nodes)
    elementwise work a row."""
    B = _PREDICATE_BLOCK
    feat, na_left = jnp.maximum(feat, 0), ~na_left
    tabs = (feat, thr, na_left, is_split)
    if cat_set is not None:
        tabs += (cat_set, is_set)
    nid = jnp.zeros(XT.shape[1], jnp.int32)
    for d in range(max_depth):
        base, n = 2 ** d - 1, 2 ** d
        at = nid - base      # negative where the row stopped above
        if n <= B:
            go, s = _block_step(XT, *(t[base:base + n] for t in tabs[:4]), at,
                                *(t[base:base + n] for t in tabs[4:]))
        else:
            low, high = at & (B - 1), at >> (B.bit_length() - 1)

            def block(k, acc):
                cut = [lax.dynamic_slice_in_dim(t, base + k * B, B)
                       for t in tabs]
                got = _block_step(XT, *cut[:4], low, *cut[4:])
                return tuple(jnp.where(high == k, g, a)
                             for g, a in zip(got, acc))
            none = jnp.zeros(nid.shape, bool)
            go, s = lax.fori_loop(0, n // B, block, (none, none))
        nid = jnp.where(s & (at >= 0), 2 * nid + 1 + go.astype(jnp.int32),
                        nid)
    return node_lookup(value, nid)


@partial(jax.jit, static_argnames=("max_depth", "node_form"))
def _score_stack(X, feat, thr, na_left, is_split, value, cat_set, is_set, *,
                 max_depth: int, node_form: str):
    """``predict_raw_stacked``'s program: one tree a scan step, the descent
    in ``node_form``. The tables and ``X`` are arguments, so the program
    is keyed on shapes, depth and form alone: every model with tables of
    one shape (a grid's, AutoML's, a checkpoint's prior) runs one
    executable, and the persistent compile cache's key holds no weights."""
    if node_form == "predicate":
        Xs, score_tree = X.T, _score_tree_predicates
    else:
        Xs, score_tree = X, _score_tree_gather

    def one_tree(carry, t):
        sets = {} if cat_set is None else {"cat_set": cat_set[t],
                                           "is_set": is_set[t]}
        return carry, score_tree(Xs, feat[t], thr[t], na_left[t], is_split[t],
                                 value[t], max_depth, **sets)

    _, contribs = lax.scan(one_tree, None, jnp.arange(feat.shape[0]))
    return contribs.T  # [rows, T]


def predict_raw_stacked(X, feat, thr, na_left, is_split, value, max_depth: int,
                        cat_set=None, is_set=None):
    """Scoring-time prediction on raw features for a stack of T trees.

    feat/thr/... are [T, M]; X is [rows, F] float32 with NaN=NA.
    Returns [rows, T] per-tree contributions; caller sums/weights.
    The score0 analog (hex/Model.java:2304, GBM: walk CompressedTrees)
    vectorized over rows, one tree a scan step, in ONE jitted program
    (``_score_stack``) that JAX caches on the shapes it sees: the first
    call of a shape traces, lowers and loads it, every later one (of this
    model or of any other with tables of that shape) is a cache hit.
    Each distinct padded row count of ``X`` is a program of its own.
    Under a caller's ``jax.jit`` (serving's bucket executables) it is
    inlined.

    Up to SCORER_PREDICATE_MAX nodes and from SCORER_ROWS_PER_NODE rows a
    node the descent looks nothing up by row (``_score_tree_predicates``
    on the transposed matrix, a bitcast under the TPU's layout of a
    narrow matrix): a level's ``X[r, feat[nid[r]]]`` is an element
    gather, 5-11 ms at 500k rows on the v5e, 88% of a predict's device
    time (PERF.md §6, PR 32). Larger trees (DRF's deep heaps) and small
    batches (the serving buckets) keep the gathers, 5 a level and one for
    the value, whose cost has no per-node part. The rule
    (``scorer_node_form``) reads static shapes and is read HERE, on every
    call, and handed to the program as a static argument: the form is
    part of the program's key, not of whatever a shape first compiled.
    Both forms give the same bits.

    ``cat_set`` [T, M, n] uint32 and ``is_set`` [T, M] (a model with
    category-set splits, GBM on enum columns): a row at a node with
    ``is_set`` goes left iff the bit of its level (the enum column's
    value) is on in the node's words, and where NA goes if the value is
    NA or past the words. Without them the program is the numeric one."""
    return _score_stack(
        X, feat, thr, na_left, is_split, value, cat_set, is_set,
        max_depth=max_depth,
        node_form=scorer_node_form(feat.shape[1], X.shape[0]))


def bins_to_thresholds(tree_split_bin: np.ndarray, tree_feat: np.ndarray,
                       edges: List[np.ndarray]) -> np.ndarray:
    """Convert bin-space splits to raw-value thresholds for scoring:
    left ⇔ code < t ⇔ raw < edges[feat][t-1]."""
    M = tree_split_bin.shape[0]
    thr = np.zeros(M, dtype=np.float32)
    for m in range(M):
        f = tree_feat[m]
        if f < 0:
            continue
        e = edges[f]
        t = tree_split_bin[m]
        if len(e) == 0 or t - 1 >= len(e):
            # t > E is reachable when a feature has fewer unique edges than
            # nbins: all non-NA rows go left, only NA can go right. Clamping
            # to e[-1] (the old behaviour) misrouted rows >= e[-1] into the
            # NA branch at scoring time.
            thr[m] = np.inf
        else:
            thr[m] = e[t - 1]
    return thr


def bins_to_thresholds_stacked(split_bin: np.ndarray, feat: np.ndarray,
                               edges: List[np.ndarray]) -> np.ndarray:
    """Vectorized bin→raw-threshold conversion for a whole [T, M] tree
    stack at once (the per-node Python loop in :func:`bins_to_thresholds`
    costs ~T·M dict/branch steps at finalize; this is three numpy
    gathers). Semantics identical: non-split nodes → 0, split bins past
    a feature's edge list → +inf (all non-NA left)."""
    if not edges:
        return np.zeros_like(split_bin, dtype=np.float32)
    emax = max((len(e) for e in edges), default=0)
    emat = np.full((len(edges), max(emax, 1)), np.inf, dtype=np.float32)
    elen = np.zeros(len(edges), dtype=np.int64)
    for f, e in enumerate(edges):
        emat[f, : len(e)] = e
        elen[f] = len(e)
    fidx = np.maximum(feat, 0)
    t = split_bin.astype(np.int64)
    over = (t - 1) >= elen[fidx]
    thr = emat[fidx, np.clip(t - 1, 0, max(emax - 1, 0))]
    thr = np.where(over, np.float32(np.inf), thr)
    return np.where(feat < 0, np.float32(0.0), thr).astype(np.float32)


# chunk-length buckets (shared GBM/DRF): single-shot chunk lengths (the
# whole-train chunk, a final partial interval) round UP to the next
# bucket with the tail trees masked via the traced n_active (their
# compute is wasted and finalize drops them — bounded to ONE chunk per
# train, ≤ ~25% of that chunk's scan; REPEATED lengths like a full
# score interval compile exact instead, see the GBM loop). Grid/AutoML
# ntrees variants landing in the same bucket reuse the executable (and
# its persistent-compile-cache entry) instead of compiling one scan per
# distinct remainder.
CHUNK_BUCKETS = (1, 2, 3, 4, 5, 8, 10, 13, 16, 20, 25, 32, 40, 50)


def chunk_bucket(c: int) -> int:
    """Smallest bucket >= c."""
    for b in CHUNK_BUCKETS:
        if b >= c:
            return b
    # beyond 50 (an over-50 score_tree_interval): next multiple of 10
    # keeps the masked-tail waste under ~20% of a chunk
    return -(-c // 10) * 10


def collect_chunk_trees(all_trees, M: int, edges) -> dict:
    """Shared GBM/DRF finalize front half: ONE pytree ``device_get`` of
    the ``[(stacked chunk trees, n_active), ...]`` list, padding-bucket
    tail slicing, and the bin→raw-threshold conversion. Returns host
    arrays [T_active·K, M] keyed feat/na_left/is_split/value/gain/
    node_w/thr."""
    from h2o3_tpu import telemetry
    host = telemetry.device_get([t for t, _ in all_trees],
                                pipeline="train")
    acts = [n for _, n in all_trees]

    def cat(kk):
        return np.concatenate(
            [np.asarray(t[kk])[:n].reshape(-1, M)
             for t, n in zip(host, acts)])

    out = {k: cat(k) for k in ("feat", "na_left", "is_split", "value",
                               "gain", "node_w")}
    if "thr" in host[0]:
        # adaptive path: raw thresholds straight from the grower
        out["thr"] = cat("thr")
    else:
        out["thr"] = bins_to_thresholds_stacked(cat("split_bin"),
                                                out["feat"], edges)
    if "cat_set" in host[0]:
        # category-set splits: the sets' words and which nodes hold one;
        # such a node has no threshold
        out["cat_set"] = np.concatenate(
            [np.asarray(t["cat_set"])[:n].reshape(
                (-1, M) + np.asarray(t["cat_set"]).shape[-1:])
             for t, n in zip(host, acts)])
        out["is_set"] = cat("set_split")
        out["thr"] = np.where(out["is_set"], np.float32(np.nan), out["thr"])
    return out


def _streamed_stump(chunks, dist, lr, cfg: TreeConfig):
    """Depth-0 streamed tree shared by the adaptive and binned streamed
    growers: exact (g,h,w) totals over chunks -> one root leaf, applied
    without ever uploading X (need_x=False passes)."""
    from h2o3_tpu import telemetry
    gs = hs = ws = 0.0
    for ch in chunks.level_pass(need_x=False):
        ghw = ch.ghw(dist)
        # ONE counted fetch of the three chunk scalars
        s3 = telemetry.device_get(
            (ghw[0].sum(), ghw[1].sum(), ghw[2].sum()),
            pipeline="train")
        gs += float(s3[0])
        hs += float(s3[1])
        ws += float(s3[2])
    v0 = float(telemetry.device_get(
        _leaf_value(jnp.float32(gs), jnp.float32(hs), cfg),
        pipeline="train"))
    tree = {"feat": np.full(1, -1, np.int32),
            "thr": np.zeros(1, np.float32),
            "na_left": np.zeros(1, bool),
            "is_split": np.zeros(1, bool),
            "value": np.array([v0], np.float32),
            "gain": np.zeros(1, np.float32),
            "node_w": np.array([ws], np.float32)}
    v0_dev = jnp.asarray(np.array([v0], np.float32))
    for ch in chunks.level_pass(need_x=False):
        ch.apply_leaf(jnp.float32(lr), v0_dev,
                      jnp.zeros(ch.e - ch.s, jnp.int32))
    return tree


def grow_tree_adaptive_streamed(chunks, dist, lr, cfg: TreeConfig,
                                root_lo, root_hi, nb_f, key=None,
                                sample_rate: float = 1.0,
                                col_mask=None):
    """Host-chunked adaptive tree build for frames beyond the device
    budget (the memman streaming mode; water/Cleaner.java graceful
    degradation). Semantics match grow_tree_adaptive with per-node
    adaptive bins; rows stream through the SAME level kernels via the
    ``chunks`` manager (models/streaming.py StreamedChunks):

    - chunks inside the budget's RESIDENT window keep X on device for
      the whole train — uploaded once per train, not once per level
      (the old path re-uploaded every chunk every level);
    - overflow chunks double-buffer: chunk k+1's upload is issued while
      chunk k's level kernel runs;
    - per-level histograms accumulate across chunks (the psum analog is
      a device '+'), and resident chunks' margins update ON DEVICE with
      the dense chunk body's f32 arithmetic — a fully-resident streamed
      train is bit-identical to the dense grower on one chunk.

    Returns the tree dict of [M] numpy arrays with raw thresholds; the
    updated margins live in ``chunks`` (``gather_margin()`` at the end
    of training)."""
    from h2o3_tpu.ops.hist_adaptive import adaptive_level, pick_W, route_only

    rows, F = chunks.rows, chunks.F
    D = cfg.max_depth
    M = cfg.n_nodes
    W = pick_W(cfg.n_bins)
    if nb_f is None:
        nb_f = jnp.full(F, float(min(cfg.n_bins, W - 2)), jnp.float32)
    else:
        nb_f = jnp.minimum(jnp.asarray(nb_f, jnp.float32), float(W - 2))
    from dataclasses import replace as dc_replace
    find_cfg = dc_replace(cfg, n_bins=W - 1)
    if col_mask is None:
        col_mask = jnp.ones(F, bool)
    # histogram contraction precision: same rule as the dense grower,
    # sized by the frame's PADDED row count like the dense path's
    # X.shape[0] so the choice agrees at the 2^18 boundary
    mxu_dtype = _hist_mxu_dtype(cfg, chunks.padded_rows)

    chunks.begin_tree(key, sample_rate)

    if D == 0:
        # degenerate stump (the dense grower's D==0 branch)
        return _streamed_stump(chunks, dist, lr, cfg)

    feat = np.full(M, -1, np.int32)
    thr_arr = np.zeros(M, np.float32)
    na_left = np.zeros(M, bool)
    is_split = np.zeros(M, bool)
    value = np.zeros(M, np.float32)
    gain_arr = np.zeros(M, np.float32)
    node_w = np.zeros(M, np.float32)

    lo_d = jnp.broadcast_to(jnp.asarray(root_lo)[None, :], (1, F)
                            ).astype(jnp.float32)
    hi_d = jnp.broadcast_to(jnp.asarray(root_hi)[None, :], (1, F)
                            ).astype(jnp.float32)
    zeros1 = jnp.zeros(1, jnp.float32)
    tables = (zeros1, zeros1, zeros1, zeros1)
    vl_s = vr_s = wl_s = wr_s = None

    for d in range(D):
        N = 2 ** d
        base = N - 1
        span = jnp.maximum(hi_d - lo_d, 0.0)
        inv_d = jnp.where(span > 0,
                          nb_f[None, :] / jnp.where(span > 0, span, 1.0),
                          0.0)
        hist = None
        perf_acc = getattr(chunks, "perf_acc", None)
        for ch in chunks.level_pass():
            ghw = ch.ghw(dist)
            nid2, h_c = adaptive_level(ch.X, ch.nid, ghw, tables, lo_d,
                                       inv_d, N // 2 if d else 0, N, base,
                                       W, mxu_dtype=mxu_dtype)
            if perf_acc is not None:
                # streamed-level jit seam (ISSUE 11): one trace+lower
                # per (chunk shape, level) key; every later chunk/tree
                # hitting the same shape pays a dict lookup. The
                # capture wall is noted on the accumulator so cold
                # windows surface it as a caveat next to their MFU.
                import time as _time
                from functools import partial as _partial

                from h2o3_tpu.telemetry import costmodel
                t_cap0 = _time.perf_counter()
                perf_acc.add(costmodel.traced_cost(
                    ("gbm.stream_level", ch.X.shape, int(N), int(W),
                     str(mxu_dtype.__name__)),
                    _partial(adaptive_level, n_prev=N // 2 if d else 0,
                             n_nodes=N, level_base=base, W=W,
                             mxu_dtype=mxu_dtype),
                    ch.X, ch.nid, ghw, tables, lo_d, inv_d))
                perf_acc.note_capture_seconds(
                    _time.perf_counter() - t_cap0)
            ch.put_nid(nid2)
            hist = h_c if hist is None else hist + h_c
        trip = (hist[0], hist[1], hist[2])
        bg, bf, bb, bnl, gt, ht, wt_, vl_s, vr_s, wl_s, wr_s = _find_splits(
            trip, find_cfg, col_mask)
        can = (bg > jnp.maximum(cfg.min_split_improvement, 0.0)) & (wt_ > 0)
        nidx = jnp.arange(N)
        lo_sel = lo_d[nidx, bf]
        inv_sel = inv_d[nidx, bf]
        BIG = jnp.float32(3.0e38)
        thr = jnp.where(can,
                        jnp.where(inv_sel > 0,
                                  lo_sel + bb.astype(jnp.float32)
                                  / jnp.maximum(inv_sel, 1e-30), BIG), 0.0)
        idx = base + np.arange(N)
        # ONE counted pytree fetch per level (these were seven raw
        # device_gets — transfer-seam burn-down)
        from h2o3_tpu import telemetry
        lvl = telemetry.device_get(
            {"feat": jnp.where(can, bf, -1), "thr": thr, "nal": bnl,
             "can": can, "val": _leaf_value(gt, ht, cfg),
             "gain": jnp.where(can, bg, 0.0), "w": wt_},
            pipeline="train")
        feat[idx] = np.asarray(lvl["feat"])
        thr_arr[idx] = np.asarray(lvl["thr"])
        na_left[idx] = np.asarray(lvl["nal"])
        is_split[idx] = np.asarray(lvl["can"])
        value[idx] = np.asarray(lvl["val"])
        gain_arr[idx] = np.asarray(lvl["gain"])
        node_w[idx] = np.asarray(lvl["w"])
        tables = (jnp.maximum(bf, 0).astype(jnp.float32), thr,
                  bnl.astype(jnp.float32), can.astype(jnp.float32))
        whist = hist[2][..., :W - 1]
        occ = whist > 0
        first = jnp.argmax(occ, axis=-1)
        last = (W - 2) - jnp.argmax(occ[..., ::-1], axis=-1)
        width = jnp.where(inv_d > 0, 1.0 / jnp.maximum(inv_d, 1e-30), 0.0)
        lo_n = lo_d + first.astype(jnp.float32) * width
        hi_n = jnp.minimum(lo_d + (last + 1).astype(jnp.float32) * width,
                           hi_d)
        any_occ = occ.any(axis=-1)
        lo_n = jnp.where(any_occ, lo_n, lo_d)
        hi_n = jnp.where(any_occ, hi_n, hi_d)
        fsel = (jnp.arange(F)[None, :] == bf[:, None]) & can[:, None]
        lo_left, hi_left = lo_n, jnp.where(
            fsel, jnp.minimum(thr[:, None], hi_n), hi_n)
        lo_right, hi_right = jnp.where(
            fsel, jnp.maximum(thr[:, None], lo_n), lo_n), hi_n
        lo_d = jnp.stack([lo_left, lo_right], axis=1).reshape(2 * N, F)
        hi_d = jnp.stack([hi_left, hi_right], axis=1).reshape(2 * N, F)

    # deepest level: route chunks, leaf values from last selected splits
    ND = 2 ** D
    baseD = ND - 1
    from h2o3_tpu import telemetry
    vD_h, wD = (np.asarray(v) for v in telemetry.device_get(
        (jnp.stack([vl_s, vr_s], axis=1).reshape(ND),
         jnp.stack([wl_s, wr_s], axis=1).reshape(ND)), pipeline="train"))
    value[baseD:] = vD_h
    node_w[baseD:] = wD
    tree = {"feat": feat, "thr": thr_arr, "na_left": na_left,
            "is_split": is_split, "value": value, "gain": gain_arr,
            "node_w": node_w}
    # final route + margin update: one fused device pass per chunk (the
    # deepest values stay on device — same f32 lookup+FMA as the dense
    # chunk body's `margin + lr_t * node_lookup(tree["value"], nid)`)
    value_dev = jnp.asarray(value)
    lr_t = jnp.float32(lr)
    for ch in chunks.level_pass():
        nid2 = route_only(ch.X, ch.nid, tables, ND // 2, baseD)
        ch.apply_leaf(lr_t, value_dev, nid2)
    return tree


def grow_tree_binned_streamed(chunks, dist, lr, cfg: TreeConfig, edges,
                              key=None, sample_rate: float = 1.0,
                              col_mask=None):
    """Host-chunked PACKED tree build: the streamed counterpart of
    :func:`grow_tree_binned`. The resident-window representation is the
    int8/int16 CODE matrix (models/streaming.py ``packed_W`` mode), so
    the memman budget fits ~4x more rows resident than f32 X and
    overflow-chunk H2D moves codes, not floats. Split thresholds
    thread as bin indices; the returned tree carries RAW thresholds
    (unbinned from ``edges`` here, once, at tree end) so the streamed
    caller's finalize shape matches the adaptive streamed grower's."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.ops.hist_adaptive import binned_route_only, pick_W
    from dataclasses import replace as dc_replace

    rows, F = chunks.rows, chunks.F
    D = cfg.max_depth
    M = cfg.n_nodes
    W = pick_W(cfg.n_bins)
    assert chunks.packed_W == W, (chunks.packed_W, W)
    find_cfg = dc_replace(cfg, n_bins=W - 1)
    if col_mask is None:
        col_mask = jnp.ones(F, bool)
    mxu_dtype = _hist_mxu_dtype(cfg, chunks.padded_rows)

    chunks.begin_tree(key, sample_rate)

    if D == 0:
        return _streamed_stump(chunks, dist, lr, cfg)

    feat = np.full(M, -1, np.int32)
    sbin_arr = np.zeros(M, np.int32)
    na_left = np.zeros(M, bool)
    is_split = np.zeros(M, bool)
    value = np.zeros(M, np.float32)
    gain_arr = np.zeros(M, np.float32)
    node_w = np.zeros(M, np.float32)

    zeros1 = jnp.zeros(1, jnp.float32)
    tables = (zeros1, zeros1, zeros1, zeros1)
    trans = chunks.kernel_layout == "t"
    perf_acc = getattr(chunks, "perf_acc", None)

    # L-level fused windows (ISSUE 17): H2O3_LEVELS_PER_PASS levels per
    # host round-trip. A single-chunk window runs ONE jitted dispatch
    # covering all its levels (codes tile-resident, nid + routing
    # tables on-chip, split selection between passes in the same
    # executable); a multi-chunk window keeps the per-level chunk loop
    # (the cross-chunk histogram reduction is a real barrier) but
    # still batches every level's split-record fetch into one sync at
    # the window boundary. L=1 is the exact old path.
    L = levels_per_pass(D, F, W)
    level_hist = None          # the previous level's, for its children's
    d = 0
    while d < D:
        Lw = min(L, D - d)
        if Lw > 1 and chunks.interrupt_pending():
            # PR-15 chunk-commit contract: a pending cancel/preempt
            # clamps the window so the cooperative yield lands at the
            # NEXT level boundary, not L levels later
            Lw = 1
        if Lw > 1 and chunks.C == 1:
            win = _fused_binned_window(cfg, d, Lw, W, trans,
                                       str(mxu_dtype.__name__))
            recs = None
            for ch in chunks.level_pass():
                ghw = ch.ghw(dist)
                if perf_acc is not None:
                    # streamed-window jit seam: one trace+lower per
                    # (chunk shape, window) key — the captured bytes
                    # show the codes operand read ONCE per Lw levels
                    import time as _time

                    from h2o3_tpu.telemetry import costmodel
                    t_cap0 = _time.perf_counter()
                    perf_acc.add(costmodel.traced_cost(
                        ("gbm.stream_window_binned", ch.X.shape,
                         int(d), int(Lw), int(W),
                         str(mxu_dtype.__name__)),
                        win, ch.X, ch.nid, ghw, tables, col_mask,
                        level_hist))
                    perf_acc.note_capture_seconds(
                        _time.perf_counter() - t_cap0)
                nid2, recs, tables, level_hist = win(
                    ch.X, ch.nid, ghw, tables, col_mask, level_hist)
                ch.put_nid(nid2)
        else:
            recs = []
            for j in range(Lw):
                dd = d + j
                N = 2 ** dd
                base = N - 1
                sums = None
                for ch in chunks.level_pass():
                    ghw = ch.ghw(dist)
                    rm_arg = None if trans else ch.X
                    ct_arg = ch.X if trans else None
                    nid2, h_c = level_child_sums(
                        rm_arg, ch.nid, ghw, tables, N // 2 if dd else 0,
                        base, W, mxu_dtype=mxu_dtype, ct=ct_arg)
                    if perf_acc is not None:
                        # streamed-level jit seam, binned flavour: one
                        # trace+lower per (chunk shape, level) key — the
                        # captured bytes carry the packed
                        # representation's 1-2 byte/value traffic
                        import time as _time
                        from functools import partial as _partial

                        from h2o3_tpu.telemetry import costmodel
                        t_cap0 = _time.perf_counter()
                        perf_acc.add(costmodel.traced_cost(
                            ("gbm.stream_level_binned", ch.X.shape,
                             int(N), int(W), str(mxu_dtype.__name__)),
                            _partial(level_child_sums,
                                     n_prev=N // 2 if dd else 0,
                                     level_base=base, W=W,
                                     mxu_dtype=mxu_dtype),
                            rm_arg, ch.nid, ghw, tables, ct=ct_arg))
                        perf_acc.note_capture_seconds(
                            _time.perf_counter() - t_cap0)
                    ch.put_nid(nid2)
                    sums = h_c if sums is None else sums + h_c
                hist = level_hist = sibling_level_hist(sums, level_hist,
                                                       tables)
                sel, can, tables = _binned_split_level(
                    (hist[0], hist[1], hist[2]), find_cfg, col_mask, cfg)
                recs.append(_level_record(sel, can, cfg))
        # ONE counted pytree fetch per WINDOW (transfer-seam contract):
        # every level's split records batched into a single host sync
        # at the L-level boundary
        lvl_h = telemetry.device_get(recs, pipeline="train")
        for j, r in enumerate(lvl_h):
            N = 2 ** (d + j)
            idx = (N - 1) + np.arange(N)
            feat[idx] = np.asarray(r["feat"])
            sbin_arr[idx] = np.asarray(r["bin"])
            na_left[idx] = np.asarray(r["nal"])
            is_split[idx] = np.asarray(r["can"])
            value[idx] = np.asarray(r["val"])
            gain_arr[idx] = np.asarray(r["gain"])
            node_w[idx] = np.asarray(r["w"])
        d += Lw

    # deepest level, two passes matching the dense binned tail: (A)
    # route each chunk and accumulate EXACT per-leaf (g,h,w) segment
    # totals; (B) apply leaf values — pass B reads the stored nids and
    # never touches X, so per-tree X traffic is unchanged (D level
    # passes + one route pass)
    ND = 2 ** D
    baseD = ND - 1
    tot = None
    for ch in chunks.level_pass():
        rm_arg = None if trans else ch.X
        ct_arg = ch.X if trans else None
        nid2 = binned_route_only(rm_arg, ch.nid, tables, ND // 2, baseD,
                                 W, ct=ct_arg)
        ch.put_nid(nid2)
        ghw = ch.ghw(dist)
        localD = nid2 - baseD
        inD = (localD >= 0) & (localD < ND)
        lidD = jnp.clip(localD, 0, ND - 1)
        t3 = _segment_totals(lidD, inD, ghw[0], ghw[1], ghw[2], ND)
        tot = t3 if tot is None else tuple(a + b for a, b in zip(tot, t3))
    vD_h, wD = (np.asarray(v) for v in telemetry.device_get(
        (_leaf_value(tot[0], tot[1], cfg), tot[2]), pipeline="train"))
    value[baseD:] = vD_h
    node_w[baseD:] = wD
    # unbin ONCE at tree end: bin-space splits -> raw thresholds, the
    # same conversion the dense finalize applies (left <=> code < t
    # <=> raw < edges[t-1]; past-the-edges bins -> +inf)
    thr_arr = bins_to_thresholds_stacked(sbin_arr[None, :], feat[None, :],
                                         edges)[0]
    tree = {"feat": feat, "thr": thr_arr, "na_left": na_left,
            "is_split": is_split, "value": value, "gain": gain_arr,
            "node_w": node_w}
    value_dev = jnp.asarray(value)
    lr_t = jnp.float32(lr)
    for ch in chunks.level_pass(need_x=False):
        ch.apply_leaf(lr_t, value_dev, ch.nid)
    return tree
