"""Plain reference for GBM on a table with ENUM columns, in straightforward
``jax.numpy`` float32. It imports nothing of the program.

What is new beside ``reference/gbm.py`` (rows, gradients, log-loss and the
node totals are that file's):

* bins: an enum column of C levels has C identity bins (its value IS its
  bin), a numeric column ``nbins`` equal-width bins between its least and
  largest value; every column has one more lane for its missing values. The
  lanes of all columns lie side by side on one axis (``Layout``), as
  XGBoost's hist and LightGBM index their bins.
* exact per-node (G, H, w) by lane: one one-hot product a block of rows,
  each f32 addend as its three exact bfloat16 terms (``split3``: what a
  ``HIGHEST`` product gives at half the passes).
* the split search, on the host in float64 (``best_splits``). A numeric
  column offers its thresholds. An enum column's levels with a row in the
  node, P, are ordered by G_b / (H_b + eps), ties by level; the first k of
  that order go left, k = 1 .. |P| - 1. Because G^2 / H is convex, the best
  of all 2^(|P|-1) - 1 two-way partitions of P is such a prefix (Fisher
  1958; ``brute_force_best`` proves it on small P in the tests). Missing
  values are tried on both sides; a child needs ``min_rows`` rows;
  gain = S(L) + S(R) - S(parent), S = G^2 / (H + eps).
* an exported tree may hold, beside thresholds, SETS: ``cat_set[t, m]`` are
  uint32 words whose bit b says level b goes left at node m, and
  ``is_set[t, m]`` says the node splits on one. A level whose bit is off
  goes right; a missing value goes where ``na_left`` says. ``route_level``
  and ``score`` test membership.

Departures from H2O-3 (``hex/tree/DTree.java:findBestSplitPoint``), as the
configuration's ``assumed`` lists them: H2O-3's GBM orders an enum's bins by
mean residual and scores squared error; this repository's GBM scores
Newton's G^2/H everywhere, so the order is by G/H.
"""
from __future__ import annotations

from functools import partial
from itertools import combinations
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from harness.reference import gbm as base

BLOCK = base.BLOCK
EPS_H = base.EPS_H

make_rows, grad_hess, node_totals, lookup = (
    base.make_rows, base.grad_hess, base.node_totals, base.lookup)


class Layout(NamedTuple):
    """Where each column's lanes lie: ``kinds[f]`` "enum" or "numeric",
    ``bins[f]`` real bins, lane ``offsets[f] + b`` bin b, lane
    ``offsets[f] + bins[f]`` the missing values; ``lanes`` in all."""
    kinds: tuple
    bins: tuple
    offsets: tuple
    lanes: int


def layout(kinds, cards, nbins: int) -> Layout:
    bins = tuple(int(c) if k == "enum" else int(nbins)
                 for k, c in zip(kinds, cards))
    off = np.concatenate([[0], np.cumsum([b + 1 for b in bins])])
    return Layout(tuple(kinds), bins, tuple(int(o) for o in off[:-1]),
                  int(off[-1]))


def uniform_edges(Xb, lay: Layout) -> list:
    """Per numeric column its ``bins - 1`` inner edges; None for an enum."""
    flat = Xb.reshape(-1, Xb.shape[-1])
    lo = np.asarray(jnp.nanmin(flat, axis=0), np.float64)
    hi = np.asarray(jnp.nanmax(flat, axis=0), np.float64)
    return [None if k == "enum" else
            np.linspace(lo[f], hi[f], b + 1)[1:-1].astype(np.float32)
            for f, (k, b) in enumerate(zip(lay.kinds, lay.bins))]


def digitize(Xb, edges: list, lay: Layout):
    """[nblk, B, F] int32 LANES: a numeric value's bin is the number of its
    column's edges at or below it, an enum value's its level; a missing
    value takes the column's last lane."""
    E = max([len(e) for e in edges if e is not None] + [1])
    emat = np.full((len(edges), E), np.inf, np.float32)
    for f, e in enumerate(edges):
        if e is not None:
            emat[f, :len(e)] = e
    enum = jnp.asarray([k == "enum" for k in lay.kinds])
    nb = jnp.asarray(lay.bins, jnp.int32)
    off = jnp.asarray(lay.offsets, jnp.int32)
    emat = jnp.asarray(emat)

    @jax.jit
    def run(Xb):
        def one(x):                                           # [B, F]
            c = jnp.zeros(x.shape, jnp.int32)
            for j in range(E):                               # E compares
                c = c + (x >= emat[None, :, j])
            c = jnp.where(enum[None, :], jnp.clip(
                jnp.nan_to_num(x), 0, nb[None, :] - 1).astype(jnp.int32), c)
            return off[None, :] + jnp.where(jnp.isnan(x), nb[None, :], c)
        return lax.map(one, Xb)
    return run(Xb)


def split3(a):
    """An f32 array as three bfloat16 terms whose sum is the array, bit for
    bit. ``reduce_precision`` and not a cast there and back, which XLA may
    elide."""
    hi = lax.reduce_precision(a, 8, 7)
    mid = lax.reduce_precision(a - hi, 8, 7)
    return [t.astype(jnp.bfloat16) for t in (hi, mid, a - hi - mid)]


@partial(jax.jit, static_argnames=("N", "lanes"))
def level_hist(codes, nid, ghw, base_id, N: int, lanes: int):
    """[N, 3, lanes] exact sums of (g, h, w) by node of this level and
    lane, 65,536 rows at a time."""
    width = -(-lanes // 128) * 128

    def rows(acc, blk):
        c, n, a = blk
        onn = ((n - base_id)[:, None] == jnp.arange(N)[None, :]
               ).astype(jnp.float32)
        A = jnp.concatenate(split3(
            (onn[:, :, None] * a[:, None, :]).reshape(BLOCK, N * 3)), axis=1)
        lane = jnp.arange(width)[None, :]
        ob = jnp.zeros((BLOCK, width), jnp.bfloat16)
        for f in range(c.shape[1]):             # columns never share a lane
            ob = ob + (c[:, f, None] == lane).astype(jnp.bfloat16)
        p = jnp.dot(A.T, ob, preferred_element_type=jnp.float32)
        return acc + p[:N * 3] + p[N * 3:2 * N * 3] + p[2 * N * 3:], None

    acc, _ = lax.scan(rows, jnp.zeros((N * 3, width), jnp.float32),
                      (codes, nid, ghw))
    return acc.reshape(N, 3, width)[..., :lanes]


# --------------------------------------------------------- split search


def score2(g, h):
    return g * g / (h + EPS_H)


def _scan(g, h, w, na, tot, min_rows: float, n_valid):
    """Best gain over prefixes of bins in the order given: ``g, h, w``
    [N, B]; ``na`` and ``tot`` [N, 3]; prefix k (1-based) is allowed where
    k < n_valid [N]. Returns (gain [N], k [N], na_left [N])."""
    G, H, W = tot[:, 0], tot[:, 1], tot[:, 2]
    parent = score2(G, H)
    best = np.full(len(G), -np.inf)
    best_k = np.zeros(len(G), np.int64)
    best_nl = np.zeros(len(G), bool)
    cg, ch, cw = (np.cumsum(a, axis=1)[:, :-1] for a in (g, h, w))
    k = np.arange(1, g.shape[1])[None, :]
    for na_left in (False, True):
        gl, hl, wl = (c + (na[:, j, None] if na_left else 0.0)
                      for j, c in enumerate((cg, ch, cw)))
        gr, hr, wr = G[:, None] - gl, H[:, None] - hl, W[:, None] - wl
        gain = score2(gl, hl) + score2(gr, hr) - parent[:, None]
        ok = (wl >= min_rows) & (wr >= min_rows) & (k < n_valid[:, None])
        gain = np.where(ok, gain, -np.inf)
        if gain.shape[1] == 0:
            continue
        j = gain.argmax(axis=1)
        top = gain[np.arange(len(G)), j]
        better = top > best
        best = np.where(better, top, best)
        best_k = np.where(better, j + 1, best_k)
        best_nl = np.where(better, na_left, best_nl)
    return best, best_k, best_nl


def set_order(g, h, w):
    """The order of an enum's levels: those with a row first, by
    G / (H + eps) ascending, ties by level."""
    ratio = np.where(w > 0, g / (h + EPS_H), np.inf)
    return np.argsort(ratio, axis=1, kind="stable")


def best_splits(hist: np.ndarray, lay: Layout, min_rows: float,
                ordinal: bool = False):
    """Per node the best split any column offers, from the exact histogram
    [N, 3, lanes], in float64: (gain [N], totals [N, 3], and ``pick``: per
    node the column, na_left and, as a bool per bin of that column, which
    bins go left). ``ordinal`` scans an enum's levels in index order, as a
    program without set splits does."""
    h = hist.astype(np.float64)
    N = h.shape[0]
    f0 = slice(lay.offsets[0], lay.offsets[0] + lay.bins[0] + 1)
    tot = h[:, :, f0].sum(axis=2)                              # [N, 3]
    best = np.full(N, -np.inf)
    pick = [None] * N
    for f, (kind, nb, off) in enumerate(zip(lay.kinds, lay.bins,
                                            lay.offsets)):
        g, hh, w = (h[:, j, off:off + nb] for j in range(3))
        na = h[:, :, off + nb]
        if kind == "enum" and not ordinal:
            order = set_order(g, hh, w)
            n_valid = (w > 0).sum(axis=1)
        else:
            order = np.broadcast_to(np.arange(nb), (N, nb))
            n_valid = np.full(N, nb)
        gs, hs, ws = (np.take_along_axis(a, order, axis=1)
                      for a in (g, hh, w))
        gain, k, nl = _scan(gs, hs, ws, na, tot, min_rows, n_valid)
        for n in np.flatnonzero(gain > best):
            left = np.zeros(nb, bool)
            left[order[n, :k[n]]] = True
            if kind == "enum" and not ordinal:
                left[w[n] <= 0] = nl[n]       # no row has it: as missing
            pick[n] = (f, bool(nl[n]), left)
        best = np.maximum(best, gain)
    return best, tot, pick


def brute_force_best(g, h, w, na, min_rows: float) -> float:
    """The best gain over ALL two-way partitions of the levels with a row
    of one enum column at one node (``g, h, w`` [B], ``na`` [3]), missing
    values on either side: exponential, for the tests' small B."""
    P = [b for b in range(len(w)) if w[b] > 0]
    tot = np.array([g.sum(), h.sum(), w.sum()]) + na
    parent = score2(tot[0], tot[1])
    best = -np.inf
    for r in range(1, len(P)):
        for left in combinations(P, r):
            left = list(left)
            for nl in (0.0, 1.0):
                L = np.array([g[left].sum(), h[left].sum(),
                              w[left].sum()]) + nl * na
                R = tot - L
                if L[2] >= min_rows and R[2] >= min_rows:
                    best = max(best, score2(L[0], L[1]) + score2(R[0], R[1])
                               - parent)
    return best


# ----------------------------------------------------- trees, exported


def pack_tree_table(model: dict):
    """``gbm.pack_tree_table`` and the sets: (packed int32 [T, M] with bit
    10 "splits on a set", thr, value, words uint32 [T, M, n])."""
    packed, thr, value = base.pack_tree_table(model)
    T, M = packed.shape
    words = np.asarray(model.get("cat_set", np.zeros((T, M, 1), np.uint32)),
                       np.uint32)
    is_set = np.asarray(model.get("is_set", np.zeros((T, M), bool)))
    packed = packed | jnp.asarray(is_set.astype(np.int32) << 10)
    return packed, jnp.nan_to_num(thr), value, jnp.asarray(words)


def route_level(X, nid, packed, thr, words, d: int):
    """Rows at a node of level ``d`` of one tree go one level down: by the
    raw threshold, or by the bit of the row's level in the node's set."""
    lo, n = 2 ** d - 1, 2 ** d
    pk, th = lookup(packed, nid, lo, n), lookup(thr, nid, lo, n)
    f, split, na_left, by_set = (pk & 0xFF, (pk >> 8) & 1, (pk >> 9) & 1,
                                 (pk >> 10) & 1)
    sel = f[..., None] == jnp.arange(X.shape[-1], dtype=jnp.int32)
    x = jnp.sum(jnp.where(sel, X, jnp.zeros((), X.dtype)), axis=-1)
    level = jnp.nan_to_num(x.astype(jnp.float32)).astype(jnp.int32)
    n_words = words.shape[-1]
    word = jnp.zeros(nid.shape, jnp.uint32)
    for j in range(n_words):
        word = jnp.where((level >> 5) == j, lookup(words[:, j], nid, lo, n),
                         word)
    inside = (level >= 0) & (level < 32 * n_words)
    in_set = inside & (((word >> (level & 31).astype(jnp.uint32)) & 1) == 1)
    right = jnp.where(by_set == 1, ~in_set, x >= th.astype(X.dtype))
    right = jnp.where(jnp.isnan(x), na_left == 0, right)
    return jnp.where(split == 1, 2 * nid + 1 + right.astype(jnp.int32), nid)


@partial(jax.jit, static_argnames=("d",))
def route_rows(Xb, nid, packed, thr, words, d: int):
    return lax.map(lambda a: route_level(a[0], a[1], packed, thr, words, d),
                   (Xb, nid))


@partial(jax.jit, static_argnames=("depth", "stops"))
def score(Xb, yb, wb, packed, thr, value, words, f0, depth: int,
          stops: tuple = ()):
    """``gbm.score`` over trees that may split on sets: (the margin before
    each tree of ``stops`` and the last, log-loss after each tree)."""
    M, T = packed.shape[1], packed.shape[0]

    def block(a):
        X, y, w = a

        def one_tree(margin, t):
            nid = jnp.zeros(X.shape[:1], jnp.int32)
            for d in range(depth):
                nid = route_level(X, nid, packed[t], thr[t], words[t], d)
            margin = margin + lookup(value[t], nid, 0, M)
            return margin, base._logloss_sum(margin, y, w)

        margin, at, sums, lo = jnp.full(X.shape[:1], f0, jnp.float32), [], [], 0
        for hi in stops + (T,):
            margin, s = lax.scan(one_tree, margin, jnp.arange(lo, hi))
            at.append(margin)
            sums.append(s)
            lo = hi
        return tuple(at), jnp.concatenate(sums)

    margin, sums = lax.map(block, (Xb, yb, wb))
    return margin, jnp.sum(sums, axis=0) / jnp.sum(wb)


def follow_tree(Xb, codes, ghw, tree: dict, depth: int, lay: Layout,
                min_rows: float, min_split_improvement: float,
                ordinal: bool = False) -> dict:
    """Exact statistics of one exported tree under its own routing, sets
    included: per node (heap order, NaN where no row arrives) the exact
    (G, H, W), the best gain on offer over thresholds and sets, and the
    exact gain of the program's own split. ``ordinal``: also, as
    ``ordinal_gain``, the best an ordinal scan of the enums offers there."""
    M = 2 ** (depth + 1) - 1
    one = {k: np.asarray(tree[k])[None] for k in
           ("feat", "is_split", "na_left", "thr", "value")}
    for k in ("cat_set", "is_set"):
        if k in tree:
            one[k] = np.asarray(tree[k])[None]
    packed, thr, _, words = pack_tree_table(one)
    packed, thr, words = packed[0], thr[0], words[0]
    totals = np.full((M, 3), np.nan)
    best = np.full(M, np.nan)
    ordi = np.full(M, np.nan)
    nid = jnp.zeros(codes.shape[:2], jnp.int32)
    for d in range(depth):
        N, lo = 2 ** d, 2 ** d - 1
        hist = np.asarray(level_hist(codes, nid, ghw, lo, N, lay.lanes))
        b, t, _ = best_splits(hist, lay, min_rows)
        floor = max(min_split_improvement, 0.0)
        best[lo:lo + N] = np.where(b > floor, b, 0.0)
        if ordinal:
            o = best_splits(hist, lay, min_rows, ordinal=True)[0]
            ordi[lo:lo + N] = np.where(o > floor, o, 0.0)
        totals[lo:lo + N] = t
        nid = route_rows(Xb, nid, packed, thr, words, d)
    ND, loD = 2 ** depth, 2 ** depth - 1
    totals[loD:] = np.asarray(node_totals(nid, ghw, loD, ND), np.float64)
    arrived = totals[:, 2] > 0
    totals[~arrived] = np.nan
    best[~arrived] = np.nan
    own = np.zeros(M)
    s = score2(totals[:, 0], totals[:, 1])
    for i in range(loD):
        if tree["is_split"][i] and arrived[i]:
            kids = np.nan_to_num(s[2 * i + 1]) + np.nan_to_num(s[2 * i + 2])
            own[i] = kids - s[i]
    own[~arrived] = np.nan
    own[loD:] = np.nan
    return {"totals": totals, "best_gain": best, "own_gain": own,
            "ordinal_gain": ordi}
