"""Airline-on-time-shaped synthetic rows: the eight predictors and the binary
response of szilard/GBM-perf's table (US DOT / ASA Data Expo 2009), made on
the device in one jitted call from the seed.

Columns, in order (``COLUMNS``: name, kind, levels): Month (12), DayofMonth
(31) and DayOfWeek (7) uniform; DepTime numeric, hhmm in 0..2359, a two-humped
day; UniqueCarrier (22), Origin (300) and Dest (300) with a heavy-tailed
popularity (a few hubs carry most flights, many airports few, so deep nodes
see levels with no row); Distance numeric, lognormal within 11..4962 miles.
An enum column is its level index as a float, which is how the platform holds
one. The response ``dep_delayed_15min`` is logistic in per-level effects drawn
from the seed for carrier, origin, destination, month and weekday (NOT
monotone in the level index: a set of levels splits better than any
threshold), a rising effect of DepTime and a small one of Distance, shifted
so that about a fifth of the rows are positive. No value is missing, as in
GBM-perf's table; rows past ``rows`` are NaN, as a Vec's padding is.

Only the shape is the airline table's (kinds, cardinalities, skew, base
rate): the rows are synthetic (PERF.md, Open questions)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

COLUMNS = (("Month", "enum", 12), ("DayofMonth", "enum", 31),
           ("DayOfWeek", "enum", 7), ("DepTime", "numeric", 0),
           ("UniqueCarrier", "enum", 22), ("Origin", "enum", 300),
           ("Dest", "enum", 300), ("Distance", "numeric", 0))


def key_of(seed: int, part: int = 0):
    """--seed may pass 2**31: fold the high bits in, a PRNGKey takes 32."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, int(part)) if part else key


def _effect(table, idx):
    """``table[idx]`` by selects: a per-row gather costs the chip 10 ns."""
    hit = idx[:, None] == jnp.arange(table.shape[0])[None, :]
    return jnp.sum(jnp.where(hit, table[None, :], 0.0), axis=1)


def _popular(k_perm, k_draw, n: int, levels: int, skew: float):
    """Level indices with P(level of rank r) ~ (r + 1)^-skew, by inverting
    the cumulative distribution (one compare a level, no search); the ranks
    are dealt to the indices by a permutation from ``k_perm``, the same for
    every block, and the draws come from ``k_draw``."""
    p = (jnp.arange(levels, dtype=jnp.float32) + 1.0) ** -skew
    cdf = jnp.cumsum(p / p.sum())
    u = jax.random.uniform(k_draw, (n,))

    def count(c, rank):
        return rank + (u >= cdf[c])
    rank = jax.lax.fori_loop(0, levels - 1, count, jnp.zeros(n, jnp.int32))
    perm = jax.random.permutation(k_perm, levels)
    return _effect(perm.astype(jnp.float32), rank).astype(jnp.int32)


BLOCK = 1 << 20


@partial(jax.jit, static_argnames=("rows", "padded"))
def _make(key, rows: int, padded: int):
    """The table block by block (``lax.map``): the [block, 300] selects of
    the hubs' columns never exist for all rows at once."""
    k_eff, k_rows = jax.random.split(key)
    ke = jax.random.split(k_eff, 5)
    eff = {"carrier": 0.5 * jax.random.normal(ke[0], (22,)),
           "origin": 0.6 * jax.random.normal(ke[1], (300,)),
           "dest": 0.4 * jax.random.normal(ke[2], (300,)),
           "month": 0.3 * jax.random.normal(ke[3], (12,)),
           "weekday": 0.2 * jax.random.normal(ke[4], (7,))}
    k_ca, k_or, k_de = jax.random.split(jax.random.fold_in(k_eff, 7), 3)
    n = min(BLOCK, padded)
    n_blk = -(-padded // n)

    def block(i):
        k = jax.random.split(jax.random.fold_in(k_rows, i), 10)
        month = jax.random.randint(k[0], (n,), 0, 12)
        dom = jax.random.randint(k[1], (n,), 0, 31)
        dow = jax.random.randint(k[2], (n,), 0, 7)
        hump = jax.random.uniform(k[3], (n,)) < 0.45
        minute = jnp.where(hump, 8.5 * 60 + 110.0 * jax.random.normal(
            k[4], (n,)), 17.0 * 60 + 150.0 * jax.random.normal(k[5], (n,)))
        minute = jnp.clip(minute, 0.0, 24 * 60 - 1.0).astype(jnp.int32)
        dep = ((minute // 60) * 100 + minute % 60).astype(jnp.float32)
        carrier = _popular(k_ca, k[6], n, 22, 1.0)
        origin = _popular(k_or, k[7], n, 300, 1.1)
        dest = _popular(k_de, k[8], n, 300, 1.1)
        dist = jnp.clip(jnp.exp(6.3 + 0.75 * jax.random.normal(
            jax.random.fold_in(k[9], 1), (n,))), 11.0, 4962.0)
        dist = jnp.round(dist)
        logit = (_effect(eff["carrier"], carrier)
                 + _effect(eff["origin"], origin)
                 + _effect(eff["dest"], dest)
                 + _effect(eff["month"], month)
                 + _effect(eff["weekday"], dow)
                 + 1.1 * (dep / 2400.0 - 0.55)
                 + 0.1 * (jnp.log(dist) - 6.3) - 1.65)
        y = jax.random.uniform(jax.random.fold_in(k[9], 2), (n,)) \
            < jax.nn.sigmoid(logit)
        X = jnp.stack([month, dom, dow, dep, carrier, origin, dest, dist],
                      axis=1).astype(jnp.float32)
        return X, y.astype(jnp.float32)

    X, y = jax.lax.map(block, jnp.arange(n_blk))
    X = X.reshape(-1, 8)[:padded]
    y = y.reshape(-1)[:padded]
    real = jnp.arange(padded) < rows
    return jnp.where(real[:, None], X, jnp.nan), jnp.where(real, y, jnp.nan)


def make(seed: int, rows: int, padded: int, features: int = 8,
         part: int = 0):
    """(X [padded, 8] f32, y [padded] f32 in {0, 1}); an enum column holds
    its level index; rows past ``rows`` are NaN."""
    if int(features) != len(COLUMNS):
        raise ValueError(f"the airline table has {len(COLUMNS)} predictors, "
                         f"not {features}")
    return _make(key_of(seed, part), rows=rows, padded=padded)
