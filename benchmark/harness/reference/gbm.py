"""Plain reference for the GBM cells, in straightforward ``jax.numpy`` float32.

It imports nothing of the program. It makes its own rows from the seed (the
benchmark's generator), its own bin edges and codes, its own gradients, and
exact per-node sums by a one-hot matrix product at ``HIGHEST`` precision in
blocks of rows. A trained model is read in its exported layout (heap of
nodes, ``x >= thr`` goes right, leaf values carry the learning rate).

Two uses:

* ``follow_tree``: a tree is a partition, so it is checked as one. Routed by
  the program's own splits, every node's exact (G, H, W), its Newton value
  and the exact gain of the split the program chose are compared with what
  the program recorded, and the chosen split with the best the exact
  histogram offers (its regret). Following the program's splits instead of
  growing a second tree keeps a near-tie, which bf16 sums may break either
  way, from sending the two trees apart.
* ``score``: the exported model over every row, tree by tree, with the
  log-loss after each tree.

``precision="bfloat16"`` computes the scorer in the next precision below, as
the score cell's control.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

BLOCK = 1 << 16
HI = lax.Precision.HIGHEST
EPS_H = 1e-12      # the configuration's leaf formula: -G / (H + lambda + 1e-12)


# ------------------------------------------------------------------ rows


def blocked(a, fill):
    """[P, ...] -> [nblk, BLOCK, ...], padded with ``fill``."""
    pad = (-a.shape[0]) % BLOCK
    if pad:
        a = jnp.concatenate(
            [a, jnp.full((pad,) + a.shape[1:], fill, a.dtype)])
    return a.reshape((-1, BLOCK) + a.shape[1:])


def make_rows(generator, seed: int, rows: int, padded: int, features: int,
              part: int = 0):
    """X [nblk, B, F], y, w [nblk, B]: the cell's rows again from the seed;
    pad rows have weight 0 and NaN features."""
    X, y = generator.make(seed, rows, padded, features, part=part)
    w = (jnp.arange(padded) < rows).astype(jnp.float32)
    y = jnp.where(w > 0, y, 0.0)
    return blocked(X, jnp.nan), blocked(y, 0.0), blocked(w, 0.0)


# ----------------------------------------------------------------- edges


@jax.jit
def _sorted_columns(Xb):
    cols = Xb.reshape(-1, Xb.shape[-1]).T            # [F, P], NaN sorts last
    return lax.map(jnp.sort, cols)


def quantile_edges(Xb, rows: int, nbins: int) -> list[np.ndarray]:
    """Per feature the ``nbins - 1`` inner quantiles of the real rows, by
    linear interpolation between the two nearest ranks (numpy's default),
    duplicates dropped."""
    Xs = _sorted_columns(Xb)
    virt = np.linspace(0.0, 1.0, nbins + 1)[1:-1] * (rows - 1)
    lo, hi = np.floor(virt).astype(np.int64), np.ceil(virt).astype(np.int64)
    a = np.asarray(Xs[:, lo], np.float64)
    b = np.asarray(Xs[:, hi], np.float64)
    vals = a + (b - a) * (virt - lo)[None, :]
    return [np.unique(v.astype(np.float32))[: nbins - 1] for v in vals]


def uniform_edges(Xb, rows: int, nbins: int) -> list[np.ndarray]:
    """Per feature ``nbins`` equal-width bins between its least and its
    largest value."""
    flat = Xb.reshape(-1, Xb.shape[-1])
    lo = np.asarray(jnp.nanmin(flat, axis=0), np.float64)
    hi = np.asarray(jnp.nanmax(flat, axis=0), np.float64)
    return [np.linspace(a, b, nbins + 1)[1:-1].astype(np.float32)
            for a, b in zip(lo, hi)]


EDGES = {"quantiles_global": quantile_edges, "uniform_adaptive": uniform_edges}


def edge_matrix(edges: list[np.ndarray]) -> np.ndarray:
    E = max(len(e) for e in edges)
    mat = np.full((len(edges), E), np.inf, np.float32)
    for f, e in enumerate(edges):
        mat[f, : len(e)] = e
    return mat


@jax.jit
def digitize(Xb, emat):
    """code = number of edges at or below the value: left of split ``t``
    is ``code < t`` is ``x < edges[t - 1]``."""
    def one(x):
        return jnp.sum(x[:, :, None] >= emat[None, :, :], axis=-1,
                       dtype=jnp.int32)
    return lax.map(one, Xb)


# ------------------------------------------------------- trees, exported


def pack_tree_table(model: dict):
    """(packed int32 [T, M]: feature | is_split << 8 | na_left << 9,
    thr f32 [T, M], value f32 [T, M])."""
    feat = np.maximum(model["feat"], 0).astype(np.int32)
    packed = (feat | (model["is_split"].astype(np.int32) << 8)
              | (model["na_left"].astype(np.int32) << 9))
    return (jnp.asarray(packed), jnp.asarray(model["thr"], jnp.float32),
            jnp.asarray(model["value"], jnp.float32))


def lookup(table, idx, lo: int, n: int):
    """``table[idx]`` for idx in [lo, lo + n), 0 elsewhere: n selects and a
    sum, exact, where a 10M-row gather from a small table takes the chip
    about 80 ms."""
    hit = (idx - lo)[..., None] == jnp.arange(n, dtype=jnp.int32)
    return jnp.sum(jnp.where(hit, table[lo:lo + n],
                             jnp.zeros((), table.dtype)), axis=-1)


def route_level(X, nid, packed, thr, d: int):
    """Rows at a node of level ``d`` of one tree go one level down, by the
    raw threshold; rows that ended higher up stay."""
    base, n = 2 ** d - 1, 2 ** d
    pk, th = lookup(packed, nid, base, n), lookup(thr, nid, base, n)
    f, split, na_left = pk & 0xFF, (pk >> 8) & 1, (pk >> 9) & 1
    sel = f[..., None] == jnp.arange(X.shape[-1], dtype=jnp.int32)
    x = jnp.sum(jnp.where(sel, X, jnp.zeros((), X.dtype)), axis=-1)
    right = jnp.where(jnp.isnan(x), na_left == 0, x >= th.astype(X.dtype))
    return jnp.where(split == 1, 2 * nid + 1 + right.astype(jnp.int32), nid)


@partial(jax.jit, static_argnames=("d",))
def route_rows(Xb, nid, packed, thr, d: int):
    return lax.map(lambda a: route_level(a[0], a[1], packed, thr, d),
                   (Xb, nid))


def sigmoid(f):
    return 1.0 / (1.0 + jnp.exp(-f))


def _logloss_sum(margin, y, w):
    m = margin.astype(jnp.float32)
    # log(1 + exp(-|m|)) + max(m, 0) - m y: the Bernoulli deviance / 2
    ll = jnp.maximum(m, 0.0) - m * y + jnp.log1p(jnp.exp(-jnp.abs(m)))
    return jnp.sum(ll * w)


@partial(jax.jit, static_argnames=("depth", "precision", "stops"))
def score(Xb, yb, wb, packed, thr, value, f0, depth: int,
          precision: str = "float32", stops: tuple = ()):
    """(margin [nblk, B], log-loss after each tree [T]), block by block.
    With ``stops`` (tree indices, ascending) the margin is instead a tuple:
    the margin before each of those trees, then the last."""
    dt = jnp.bfloat16 if precision == "bfloat16" else jnp.float32
    M, T = packed.shape[1], packed.shape[0]

    def block(a):
        X, y, w = a[0].astype(dt), a[1], a[2]

        def one_tree(margin, t):
            pk, th, val = packed[t], thr[t], value[t]
            nid = jnp.zeros(X.shape[:1], jnp.int32)
            for d in range(depth):
                nid = route_level(X, nid, pk, th, d)
            margin = margin + lookup(val, nid, 0, M).astype(dt)
            return margin, _logloss_sum(margin, y, w)

        margin, at, sums, lo = jnp.full(X.shape[:1], f0, dt), [], [], 0
        for hi in stops + (T,):
            margin, s = lax.scan(one_tree, margin, jnp.arange(lo, hi))
            at.append(margin)
            sums.append(s)
            lo = hi
        return (tuple(at) if stops else margin), jnp.concatenate(sums)

    margin, sums = lax.map(block, (Xb, yb, wb))
    return margin, jnp.sum(sums, axis=0) / jnp.sum(wb)


# ------------------------------------------------- exact per-node sums


def grad_hess(margin, y, w):
    p = sigmoid(margin)
    return jnp.stack([(p - y) * w, jnp.maximum(p * (1.0 - p), 1e-9) * w, w],
                     axis=-1)                                   # [nblk, B, 3]


@partial(jax.jit, static_argnames=("N", "nb"))
def level_hist(codes, nid, ghw, base, N: int, nb: int):
    """[N, 3, F, nb] sums of (g, h, w) by node of this level, feature, bin."""
    F = codes.shape[-1]

    def body(acc, blk):
        c, n, a = blk
        onn = ((n - base)[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
        A = (onn[:, :, None] * a[:, None, :]).reshape(BLOCK, N * 3)
        ob = (c[:, :, None] == jnp.arange(nb)[None, None, :]).astype(
            jnp.float32).reshape(BLOCK, F * nb)
        return acc + jnp.dot(A.T, ob, precision=HI), None

    acc, _ = lax.scan(body, jnp.zeros((N * 3, F * nb), jnp.float32),
                      (codes, nid, ghw))
    return acc.reshape(N, 3, F, nb)


@partial(jax.jit, static_argnames=("N",))
def node_totals(nid, ghw, base, N: int):
    """[N, 3] sums of (g, h, w) by node of one level."""
    def body(acc, blk):
        n, a = blk
        onn = ((n - base)[:, None] == jnp.arange(N)[None, :]).astype(jnp.float32)
        return acc + jnp.dot(onn.T, a, precision=HI), None

    acc, _ = lax.scan(body, jnp.zeros((N, 3), jnp.float32), (nid, ghw))
    return acc


def _score2(g, h):
    return g * g / (h + EPS_H)


def best_splits(hist: np.ndarray, min_rows: float):
    """Per node the best gain any (feature, bin) split offers, from the exact
    histogram, in float64. Rows are NA-free, so NA left and right tie."""
    h = hist.astype(np.float64)
    g_c, h_c, w_c = (np.cumsum(h[:, k], axis=-1)[..., :-1] for k in range(3))
    G, H, W = (h[:, k, 0].sum(-1) for k in range(3))
    gl, hl, wl = g_c, h_c, w_c
    gr, hr, wr = (G[:, None, None] - gl, H[:, None, None] - hl,
                  W[:, None, None] - wl)
    gain = (_score2(gl, hl) + _score2(gr, hr)
            - _score2(G, H)[:, None, None])
    gain = np.where((wl >= min_rows) & (wr >= min_rows), gain, -np.inf)
    return gain.reshape(len(G), -1).max(axis=1), np.stack([G, H, W], axis=1)


def follow_tree(Xb, codes, ghw, tree: dict, depth: int, nb: int,
                min_rows: float, min_split_improvement: float) -> dict:
    """Exact statistics of one exported tree under its own routing.

    ``tree`` holds one tree's host arrays [M]. Returns per node (heap
    order, NaN where no row arrives) the exact (G, H, W), the best gain on
    offer and the exact gain of the program's own split."""
    M = 2 ** (depth + 1) - 1
    packed, thr, _ = pack_tree_table({k: tree[k][None] for k in
                                      ("feat", "is_split", "na_left", "thr",
                                       "value")})
    packed, thr = packed[0], thr[0]
    totals = np.full((M, 3), np.nan)
    best = np.full(M, np.nan)
    nid = jnp.zeros(codes.shape[:2], jnp.int32)
    nmax = 2 ** max(depth - 1, 0)
    for d in range(depth):
        N, base = 2 ** d, 2 ** d - 1
        hist = np.asarray(level_hist(codes, nid, ghw, base, nmax, nb))[:N]
        b, t = best_splits(hist, min_rows)
        best[base:base + N] = np.where(b > max(min_split_improvement, 0.0),
                                       b, 0.0)
        totals[base:base + N] = t
        nid = route_rows(Xb, nid, packed, thr, d)
    ND, baseD = 2 ** depth, 2 ** depth - 1
    totals[baseD:] = np.asarray(node_totals(nid, ghw, baseD, ND), np.float64)
    arrived = totals[:, 2] > 0
    totals[~arrived] = np.nan
    best[~arrived] = np.nan
    own = np.zeros(M)
    s = _score2(totals[:, 0], totals[:, 1])
    for i in range(baseD):
        if tree["is_split"][i] and arrived[i]:
            kids = np.nan_to_num(s[2 * i + 1]) + np.nan_to_num(s[2 * i + 2])
            own[i] = kids - s[i]
    own[~arrived] = np.nan
    own[baseD:] = np.nan
    return {"totals": totals, "best_gain": best, "own_gain": own}
