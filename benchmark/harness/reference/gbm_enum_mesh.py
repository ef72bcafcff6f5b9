"""``reference/gbm_enum.py`` with the row blocks laid over several chips, in
plain ``jax.numpy``: the whole airline table (123.5M rows) does not fit one
chip's memory, and its check should take what the one-chip cell's takes. It
imports nothing of the program.

The semantics are that file's, unchanged: identity bins for an enum, equal
width bins for a numeric column, exact per-node sums by one one-hot product a
block (each f32 addend as its three bfloat16 terms), the ordered-prefix split
search in float64 on the host, trees followed under the program's own
routing, sets included. What differs is where the rows lie: the blocks'
leading axis ([blocks, B, ...]) is split over a one-axis mesh of the cell's
chips, every chip maps or scans over its own blocks (``jax.shard_map`` around
that file's per-block functions), and what is summed over rows (a level's
histogram, the leaves' totals, the log-loss) is added across chips by one
``psum``. Per-row results (codes, node ids, margins) stay where their rows
are. The block length follows the rows a chip holds (at most that file's
65,536), so a rehearsal does not multiply one-hots of padding.

Copied from that file because they name its block length or its callees:
``level_hist``, ``score``'s normalisation, ``follow_tree``.
``benchmark/tests/test_airline_mesh_cell.py`` holds this file to
``reference/gbm_enum.py`` at 4,096 rows."""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from harness.reference import gbm as base
from harness.reference import gbm_enum as one

BLOCK = base.BLOCK
EPS_H = base.EPS_H
Layout, layout, best_splits, score2, pack_tree_table, grad_hess = (
    one.Layout, one.layout, one.best_splits, one.score2,
    one.pack_tree_table, one.grad_hess)

ROWS = P("rows")


def mesh_of(a):
    """The mesh a row-sharded array lies on."""
    return a.sharding.mesh


def on_rows(fn, mesh, n_in: int, out_specs, replicated: int = 0):
    """``fn`` over each chip's own blocks: the first ``n_in`` arguments are
    split by their leading axis, ``replicated`` more are whole on every
    chip."""
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(ROWS,) * n_in + (P(),) * replicated,
        out_specs=out_specs, check_vma=False))


# ------------------------------------------------------------------ rows


def make_rows(generator, seed: int, rows: int, padded: int, features: int,
              devices: int, part: int = 0):
    """X [blocks, B, F], y, w [blocks, B], the blocks split over ``devices``
    chips: the cell's rows again from the seed, each chip's own rows in its
    own blocks; pad rows have weight 0 and NaN features."""
    X, y = generator.make(seed, rows, padded, features, part=part,
                          devices=devices)
    per = padded // devices
    B = min(BLOCK, -(-per // 8) * 8)
    pad = (-per) % B

    def local(X, y):
        at = lax.axis_index("rows") * per + jnp.arange(per)
        w = (at < rows).astype(jnp.float32)
        y = jnp.where(w > 0, y, 0.0)
        X = jnp.pad(X, ((0, pad), (0, 0)), constant_values=jnp.nan)
        return (X.reshape(-1, B, X.shape[-1]),
                jnp.pad(y, (0, pad)).reshape(-1, B),
                jnp.pad(w, (0, pad)).reshape(-1, B))

    return on_rows(local, mesh_of(X), 2, (ROWS, ROWS, ROWS))(X, y)


# a min and a max a column: XLA reduces them on each chip, then across chips
uniform_edges = one.uniform_edges


def digitize(Xb, edges: list, lay: Layout):
    """``gbm_enum.digitize``, each chip over its own blocks."""
    return on_rows(lambda x: one.digitize(x, edges, lay), mesh_of(Xb), 1,
                   ROWS)(Xb)


# ------------------------------------------------- exact per-node sums


@lru_cache(maxsize=64)
def _level_hist(mesh, N: int, lanes: int):
    width = -(-lanes // 128) * 128

    def local(codes, nid, ghw, base_id):
        B = codes.shape[1]

        def rows(acc, blk):
            c, n, a = blk
            onn = ((n - base_id)[:, None] == jnp.arange(N)[None, :]
                   ).astype(jnp.float32)
            A = jnp.concatenate(one.split3(
                (onn[:, :, None] * a[:, None, :]).reshape(B, N * 3)), axis=1)
            lane = jnp.arange(width)[None, :]
            ob = jnp.zeros((B, width), jnp.bfloat16)
            for f in range(c.shape[1]):         # columns never share a lane
                ob = ob + (c[:, f, None] == lane).astype(jnp.bfloat16)
            p = jnp.dot(A.T, ob, preferred_element_type=jnp.float32)
            return acc + p[:N * 3] + p[N * 3:2 * N * 3] + p[2 * N * 3:], None

        acc, _ = lax.scan(rows, jnp.zeros((N * 3, width), jnp.float32),
                          (codes, nid, ghw))
        return lax.psum(acc, "rows").reshape(N, 3, width)[..., :lanes]

    return on_rows(local, mesh, 3, P(), replicated=1)


def level_hist(codes, nid, ghw, base_id: int, N: int, lanes: int):
    """``gbm_enum.level_hist``: [N, 3, lanes] exact sums of (g, h, w) by
    node of this level and lane, every chip over its own blocks, the
    chips' sums added by one psum."""
    return _level_hist(mesh_of(codes), N, lanes)(codes, nid, ghw,
                                                 jnp.int32(base_id))


@lru_cache(maxsize=8)
def _node_totals(mesh, base_id: int, N: int):
    return on_rows(
        lambda n, a: lax.psum(base.node_totals(n, a, base_id, N), "rows"),
        mesh, 2, P())


def node_totals(nid, ghw, base_id: int, N: int):
    """``gbm.node_totals`` over the chips' blocks, psum'd."""
    return _node_totals(mesh_of(nid), base_id, N)(nid, ghw)


# ----------------------------------------------------- trees, exported


@lru_cache(maxsize=32)
def _route_rows(mesh, d: int):
    return on_rows(partial(one.route_rows, d=d), mesh, 2, ROWS, replicated=3)


def route_rows(Xb, nid, packed, thr, words, d: int):
    """``gbm_enum.route_rows``, each chip over its own blocks."""
    return _route_rows(mesh_of(Xb), d)(Xb, nid, packed, thr, words)


def score(Xb, yb, wb, packed, thr, value, words, f0, depth: int,
          stops: tuple = ()):
    """``gbm_enum.score``: (the margin before each tree of ``stops`` and the
    last, log-loss after each tree), the log-loss sums and the weights
    added over the chips before the division."""
    M, T = packed.shape[1], packed.shape[0]

    def local(Xb, yb, wb, packed, thr, value, words):
        def block(a):
            X, y, w = a

            def one_tree(margin, t):
                nid = jnp.zeros(X.shape[:1], jnp.int32)
                for d in range(depth):
                    nid = one.route_level(X, nid, packed[t], thr[t],
                                          words[t], d)
                margin = margin + one.lookup(value[t], nid, 0, M)
                return margin, base._logloss_sum(margin, y, w)

            margin, at, sums, lo = (jnp.full(X.shape[:1], f0, jnp.float32),
                                    [], [], 0)
            for hi in stops + (T,):
                margin, s = lax.scan(one_tree, margin, jnp.arange(lo, hi))
                at.append(margin)
                sums.append(s)
                lo = hi
            return tuple(at), jnp.concatenate(sums)

        margin, sums = lax.map(block, (Xb, yb, wb))
        return margin, (lax.psum(jnp.sum(sums, axis=0), "rows")
                        / lax.psum(jnp.sum(wb), "rows"))

    return on_rows(local, mesh_of(Xb), 3,
                   ((ROWS,) * (len(stops) + 1), P()), replicated=4)(
        Xb, yb, wb, packed, thr, value, words)


def follow_tree(Xb, codes, ghw, tree: dict, depth: int, lay: Layout,
                min_rows: float, min_split_improvement: float,
                ordinal: bool = False) -> dict:
    """``gbm_enum.follow_tree`` over this file's ``level_hist``,
    ``route_rows`` and ``node_totals``: per node (heap order, NaN where no
    row arrives) the exact (G, H, W), the best gain on offer over
    thresholds and sets, the exact gain of the program's own split and,
    with ``ordinal``, the best an ordinal scan of the enums offers."""
    M = 2 ** (depth + 1) - 1
    single = {k: np.asarray(tree[k])[None] for k in
              ("feat", "is_split", "na_left", "thr", "value")}
    for k in ("cat_set", "is_set"):
        if k in tree:
            single[k] = np.asarray(tree[k])[None]
    packed, thr, _, words = pack_tree_table(single)
    packed, thr, words = packed[0], thr[0], words[0]
    totals = np.full((M, 3), np.nan)
    best = np.full(M, np.nan)
    ordi = np.full(M, np.nan)
    nid = jax.device_put(jnp.zeros(codes.shape[:2], jnp.int32),
                         NamedSharding(mesh_of(codes), ROWS))
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
