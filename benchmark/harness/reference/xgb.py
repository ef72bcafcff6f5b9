"""Plain reference for the XGBoost-hist cell, in straightforward ``jax.numpy``
float32. It imports nothing of the program.

What is XGBoost's here (Chen & Guestrin 2016, eq. 6 and 7, with the L1 term
of the library's ``CalcWeight``/``CalcGain``):

  score(G, H)  = T(G)^2 / (H + lambda),   T(G) = sign(G) max(|G| - alpha, 0)
  gain         = score(G_L, H_L) + score(G_R, H_R) - score(G, H)
  leaf weight  = -eta T(G) / (H + lambda)
  a split is allowed where H_L >= min_child_weight and H_R >= min_child_weight
  (a bound on each child's HESSIAN sum, not on its rows), and taken where
  its gain exceeds gamma

with the bernoulli objective's g = p - y, h = p (1 - p). Rows, the exported
tree table, the scorer and the log-loss are ``reference/gbm.py``'s (the same
rows and the same export serve both estimators; nothing there is changed).
New here: the NA lane (a NaN value is left out of its feature's quantiles,
takes code ``nb``, and a split sends it left or right, whichever gains more);
exact per-node (G, H, W) histograms by one-hot products one block of rows AND
one feature at a time (a ``[65536, 28 * 255]`` one-hot is 1.9 GB in f32, and
folding features into its lanes is a relayout at 255 lanes), each f32 addend
as its three exact bfloat16 terms (``level_hist``: the sums a ``HIGHEST``
product gives at half the passes; with ``HIGHEST`` the check took 36 s a run
at 10M rows); the gain above; and the followed tree's numbers.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from harness.reference import gbm as base

BLOCK = base.BLOCK
EPS_H = 1e-12           # the program's guard in H + lambda + 1e-12

make_rows, edge_matrix = base.make_rows, base.edge_matrix
pack_tree_table, route_rows, score, grad_hess, node_totals = (
    base.pack_tree_table, base.route_rows, base.score, base.grad_hess,
    base.node_totals)


def quantile_edges(Xb, nb: int) -> list[np.ndarray]:
    """Per feature the ``nb - 1`` inner quantiles of its values that are
    there (a NaN, as every pad row is, is in no bin), by linear interpolation
    between the two nearest ranks (numpy's default), duplicates dropped."""
    Xs = base._sorted_columns(Xb)                      # [F, P], NaN last
    n = np.asarray(jnp.sum(~jnp.isnan(Xs), axis=1))
    virt = np.linspace(0.0, 1.0, nb + 1)[1:-1][None, :] * np.maximum(
        n - 1, 0)[:, None]
    lo, hi = np.floor(virt).astype(np.int64), np.ceil(virt).astype(np.int64)
    a = np.asarray(jnp.take_along_axis(Xs, jnp.asarray(lo), axis=1), np.float64)
    b = np.asarray(jnp.take_along_axis(Xs, jnp.asarray(hi), axis=1), np.float64)
    vals = (a + (b - a) * (virt - lo)).astype(np.float32)
    return [np.unique(v[~np.isnan(v)])[: nb - 1] for v in vals]


@jax.jit
def digitize(Xb, emat, nb):
    """code = the number of the feature's edges at or below the value; a NaN
    takes the NA lane ``nb``. One feature at a time: [B, E] compares."""
    def one(x):                                        # x [B, F]
        def feature(a):
            col, e = a
            c = jnp.sum(col[:, None] >= e[None, :], axis=-1, dtype=jnp.int32)
            return jnp.where(jnp.isnan(col), nb, c)
        return lax.map(feature, (x.T, emat)).T
    return lax.map(one, Xb)


def split3(a):
    """An f32 array as three bfloat16 terms whose sum is the array, bit for
    bit (8 + 8 + 8 mantissa bits). ``reduce_precision`` and not a cast there
    and back, which XLA may elide."""
    hi = lax.reduce_precision(a, 8, 7)
    mid = lax.reduce_precision(a - hi, 8, 7)
    return [t.astype(jnp.bfloat16) for t in (hi, mid, a - hi - mid)]


@partial(jax.jit, static_argnames=("N", "lanes"))
def level_hist(codes, nid, ghw, base_id, N: int, lanes: int):
    """[N, 3, F, lanes] sums of (g, h, w) by node of this level, feature and
    code, one feature and 65,536 rows at a time. Each f32 addend goes in as
    its three exact bfloat16 terms against a 0/1 one-hot, so every product
    is exact and the sums are f32 accumulations: what a ``HIGHEST`` product
    gives, in three MXU passes for six."""
    F = codes.shape[-1]
    width = -(-lanes // 128) * 128                     # whole lane tiles

    def rows(acc, blk):
        c, n, a = blk
        onn = ((n - base_id)[:, None] == jnp.arange(N)[None, :]
               ).astype(jnp.float32)
        A = jnp.concatenate(split3(
            (onn[:, :, None] * a[:, None, :]).reshape(BLOCK, N * 3)), axis=1)

        def feature(col):                              # [B] codes
            ob = (col[:, None] == jnp.arange(width)[None, :]
                  ).astype(jnp.bfloat16)
            p = jnp.dot(A.T, ob, preferred_element_type=jnp.float32)
            return p[:N * 3] + p[N * 3:2 * N * 3] + p[2 * N * 3:]

        return acc + lax.map(feature, c.T), None

    acc, _ = lax.scan(rows, jnp.zeros((F, N * 3, width), jnp.float32),
                      (codes, nid, ghw))
    return acc.reshape(F, N, 3, width).transpose(1, 2, 0, 3)[..., :lanes]


def soft(g, alpha: float):
    return np.sign(g) * np.maximum(np.abs(g) - alpha, 0.0)


def score2(g, h, lam: float, alpha: float):
    return soft(g, alpha) ** 2 / (h + lam + EPS_H)


def weight(g, h, lam: float, alpha: float):
    """A node's value before the learning rate."""
    return -soft(g, alpha) / (h + lam + EPS_H)


def best_splits(hist: np.ndarray, nb: int, lam: float, alpha: float,
                min_child_weight: float):
    """Per node the best gain any allowed (feature, bin, NA side) split
    offers, from the exact histogram, in float64; and the node's totals."""
    h = hist.astype(np.float64)
    cum = [np.cumsum(h[:, k, :, :nb], axis=-1)[..., :-1] for k in range(3)]
    na = [h[:, k, :, nb] for k in range(3)]
    G, H, W = (h[:, k, 0].sum(-1) for k in range(3))
    parent = score2(G, H, lam, alpha)[:, None, None]
    best = np.full(len(G), -np.inf)
    for na_left in (False, True):
        gl, hl = (c + (n[..., None] if na_left else 0.0)
                  for c, n in zip(cum[:2], na[:2]))
        gr, hr = G[:, None, None] - gl, H[:, None, None] - hl
        gain = score2(gl, hl, lam, alpha) + score2(gr, hr, lam, alpha) - parent
        ok = (hl >= min_child_weight) & (hr >= min_child_weight)
        gain = np.where(ok, gain, -np.inf)
        best = np.maximum(best, gain.reshape(len(G), -1).max(axis=1))
    return best, np.stack([G, H, W], axis=1)


def follow_tree(Xb, codes, ghw, tree: dict, depth: int, nb: int, lam: float,
                alpha: float, min_child_weight: float, gamma: float) -> dict:
    """Exact statistics of one exported tree under its own routing: per node
    (heap order, NaN where no row arrives) the exact (G, H, W), the best gain
    on offer and the exact gain of the program's own split."""
    M = 2 ** (depth + 1) - 1
    packed, thr, _ = pack_tree_table({k: tree[k][None] for k in
                                      ("feat", "is_split", "na_left", "thr",
                                       "value")})
    packed, thr = packed[0], thr[0]
    totals = np.full((M, 3), np.nan)
    best = np.full(M, np.nan)
    nid = jnp.zeros(codes.shape[:2], jnp.int32)
    for d in range(depth):
        N, base_id = 2 ** d, 2 ** d - 1
        hist = np.asarray(level_hist(codes, nid, ghw, base_id, N, nb + 1))
        b, t = best_splits(hist, nb, lam, alpha, min_child_weight)
        best[base_id:base_id + N] = np.where(b > max(gamma, 0.0), b, 0.0)
        totals[base_id:base_id + N] = t
        nid = route_rows(Xb, nid, packed, thr, d)
    ND, baseD = 2 ** depth, 2 ** depth - 1
    totals[baseD:] = np.asarray(node_totals(nid, ghw, baseD, ND), np.float64)
    arrived = totals[:, 2] > 0
    totals[~arrived] = np.nan
    best[~arrived] = np.nan
    own = np.zeros(M)
    s = score2(totals[:, 0], totals[:, 1], lam, alpha)
    for i in range(baseD):
        if tree["is_split"][i] and arrived[i]:
            kids = np.nan_to_num(s[2 * i + 1]) + np.nan_to_num(s[2 * i + 2])
            own[i] = kids - s[i]
    own[~arrived] = np.nan
    own[baseD:] = np.nan
    return {"totals": totals, "best_gain": best, "own_gain": own}
