"""The packed grower's PARENT formulation, kept as the tests' oracle: every
node of a level built directly from its own rows by a scatter, nothing taken
by subtraction. ``grow_tree_binned`` accumulates one child a parent and
derives the sibling (``tree.sibling_level_hist``); with integer g, h, w every
sum is exact in bf16 and f32, so both formulations must grow the same tree
bit for bit."""
from dataclasses import replace

import jax.numpy as jnp
import numpy as np

from h2o3_tpu.models import tree as T
from h2o3_tpu.ops import hist_adaptive as ha


def direct_level_hist(codes, nid, ghw, N, base, W, widths=()):
    """[3, N, F, W] (flat [3, N, lanes] under ``widths``): every node of the
    level from the rows that stand on it, ``codes`` local, float64 sums."""
    codes, nid, ghw = (np.asarray(a) for a in (codes, nid, ghw))
    rows, F = codes.shape
    off = np.asarray(ha.lane_offsets(widths) if widths
                     else W * np.arange(F))
    lanes = sum(widths) if widths else F * W
    hist = np.zeros((3, N, lanes))
    on = (nid >= base) & (nid < base + N)
    lane = off[None, :] + codes.astype(np.int64)
    for k in range(3):
        np.add.at(hist[k], (np.repeat(nid[on] - base, F),
                            lane[on].reshape(-1)),
                  np.repeat(ghw[k][on].astype(np.float64), F))
    hist = jnp.asarray(hist, jnp.float32)
    return hist if widths else hist.reshape(3, N, F, W)


def grow_direct(codes, g, h, w, cfg: T.TreeConfig, col_mask):
    """``grow_tree_binned``'s records of the split levels (feat, split_bin,
    na_left, is_split, gain, node_w, value before clipping) and the rows'
    final node ids, by the parent formulation. No monotone bounds, mtries or
    interaction sets: the cases that use it set none."""
    D, M = cfg.max_depth, cfg.n_nodes
    widths = cfg.lane_widths
    W = max(widths) if widths else ha.pick_W(cfg.n_bins)
    find_cfg = replace(cfg, n_bins=W - 1)
    rec = {"feat": np.full(M, -1, np.int32),
           "split_bin": np.zeros(M, np.int32),
           "na_left": np.zeros(M, bool), "is_split": np.zeros(M, bool),
           "gain": np.zeros(M, np.float32), "node_w": np.zeros(M, np.float32),
           "value": np.zeros(M, np.float32)}
    ghw = jnp.stack([g, h, w]).astype(jnp.float32)
    nid = jnp.zeros(codes.shape[0], jnp.int32)
    tables = None
    for d in range(D):
        N, base = 2 ** d, 2 ** d - 1
        if d:
            nid = ha.binned_route_only_xla(codes, nid, tables, N // 2, base,
                                           W)
        hist = direct_level_hist(codes, nid, ghw, N, base, W, widths)
        if widths:
            hist = T.padded_level_hist(hist, cfg)
        sel, can, tables = T._binned_split_level(
            (hist[0], hist[1], hist[2]), find_cfg, col_mask, cfg)
        idx = base + np.arange(N)
        rec["feat"][idx] = np.where(can, sel[1], -1)
        rec["split_bin"][idx] = sel[2]
        rec["na_left"][idx] = sel[3]
        rec["is_split"][idx] = can
        rec["gain"][idx] = np.where(can, sel[0], 0.0)
        rec["node_w"][idx] = sel[6]
        rec["value"][idx] = T._leaf_value(sel[4], sel[5], cfg)
    nid = ha.binned_route_only_xla(codes, nid, tables, 2 ** D // 2,
                                   2 ** D - 1, W)
    return rec, np.asarray(nid)


def assert_same_tree(tree, nid, want, want_nid, depth):
    """``grow_tree_binned``'s (tree, nid) equal :func:`grow_direct`'s at
    every split level, and a parent that keeps its rows leaves both
    children empty and unsplit (one of them is "parent - nothing" before
    ``sibling_level_hist``'s mask); the case has to hold such a parent."""
    inner = np.arange(len(want["feat"])) < 2 ** depth - 1
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(tree[k])[inner], v[inner],
                                      err_msg=k)
    np.testing.assert_array_equal(np.asarray(nid), want_nid)
    is_split, node_w = np.asarray(tree["is_split"]), np.asarray(tree["node_w"])
    kept = np.flatnonzero(inner & ~is_split & (node_w > 0))
    kept = kept[kept < 2 ** (depth - 1) - 1]
    assert len(kept), "the case has no inner node that keeps its rows"
    for child in (2 * kept + 1, 2 * kept + 2):
        assert not is_split[child].any() and not node_w[child].any()
