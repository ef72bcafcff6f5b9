"""HIGGS-shaped synthetic rows: ``features`` standard-normal columns and a
binary label whose log-odds are ``bench.py``'s formula (two linear terms, one
product, one sine). Made on the device in one jitted call from the seed, so
that set-up pays neither 280M host normals nor their upload; the reference
calls the same function for the same rows. ``part`` names a further table of
the same seed (0 the training rows, 1 the held-out rows a score cell scores).

Only the shape is HIGGS's (28 real columns, a binary label): the columns are
independent standard normals, not HIGGS's marginals (PERF.md, Open questions)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def key_of(seed: int, part: int = 0):
    """--seed may pass 2**31: fold the high bits in, a PRNGKey takes 32."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, int(part)) if part else key


@partial(jax.jit, static_argnames=("rows", "padded", "features"))
def _make(key, rows: int, padded: int, features: int):
    kx, ky = jax.random.split(key)
    X = jax.random.normal(kx, (padded, features), jnp.float32)
    logit = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * jnp.sin(3.0 * X[:, 4]))
    y = (jax.random.uniform(ky, (padded,)) < jax.nn.sigmoid(logit))
    real = jnp.arange(padded) < rows
    # pad rows are NA, as a Vec's padding is
    X = jnp.where(real[:, None], X, jnp.nan)
    y = jnp.where(real, y.astype(jnp.float32), jnp.nan)
    return X, y


def make(seed: int, rows: int, padded: int, features: int, part: int = 0):
    """(X [padded, features] f32, y [padded] f32 in {0, 1}); rows past
    ``rows`` are NaN."""
    return _make(key_of(seed, part), rows=rows, padded=padded,
                 features=features)
