"""A numeric Vec's factor made where its payload lives.

``Vec.asfactor`` (h2o-py ``vec.asfactor()``; Rapids ``as.factor``) turns
a numeric column into an enum: the distinct finite values, sorted, are the
domain (``str(int(v))`` for an integral value, ``str(v)`` otherwise), a
row's code is its value's rank in that domain, and NaN, ±inf and the pad
rows are ``ENUM_NA``. On a device-only column the host formula (numpy
``unique`` and ``searchsorted``) fetched the whole padded column and
uploaded the codes again. The range path here gives the same domain and
codes, bit for bit, from the device payload: H2O-3's
``VecUtils.CollectIntegerDomain``. One reduction gives the finite count,
min, max and whether every finite value is an integer within ±2^24; where
the range R = max − min + 1 is at most ``RANGE_MAX``, one pass finds which
of the R integers occur and makes each row's code as the count of
occurring integers below it, by compare-and-reduce (no sort, scatter or
gather). Reductions and elementwise work only, so on a mesh the codes stay
row-sharded. It fetches a few scalars and R flags and nothing else.

Any other column, and one of fewer than ``DEVICE_MIN_ROWS`` rows, keeps the
host formula (``factor`` returns None).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.frame.vec import ENUM_NA, level_label

# Largest integer range the range path takes. Its second pass costs R
# compares a row: a whole factor of 10M rows on a TPU v5e took 4.5 ms at
# R = 2 to 64, 8.3 at 256, 20.5 at 1,024 and 148 at 4,096, against the
# host formula's 0.48 to 1.22 s (tools/micro_factor.py).
RANGE_MAX = 1024

# Fewest rows the range path takes. Its two programs compile once for
# each padded length a process meets (0.2 to 2.8 s on a TPU v5e), and a
# warm factor takes 3 to 4.5 ms at any length, where the host formula
# takes 1 ms at 4,096 rows, 15 ms at 2^20, 56 ms at 2^22 and 294 ms at
# 2^23: the least length read at which the range pass repays its first
# call within ten factors of one column, as a grid or an AutoML run
# trains many models on one frame (6.3 at 2^23, 20 at 2^22;
# tools/micro_factor.py).
DEVICE_MIN_ROWS = 1 << 23

_EXACT = float(1 << 24)     # float32 holds every integer up to here


def _valid(x, nrow):
    return (jnp.arange(x.shape[0]) < nrow) & jnp.isfinite(x)


@jax.jit
def _summary(x, nrow):
    """(finite count, min, max, every finite value an integer in ±2^24)."""
    valid = _valid(x, nrow)
    small_int = jnp.all(~valid | ((x == jnp.round(x))
                                  & (jnp.abs(x) <= _EXACT)))
    return (jnp.sum(valid, dtype=jnp.int32),
            jnp.min(jnp.where(valid, x, jnp.inf)),
            jnp.max(jnp.where(valid, x, -jnp.inf)), small_int)


@partial(jax.jit, static_argnames="width")
def _range_codes(x, nrow, lo, width):
    """(codes, which of lo .. lo + width - 1 occur). The [width, rows]
    compares keep rows on the minor axis, so a reduction over them is a
    lane reduction and the codes' sum runs over sublanes."""
    valid = _valid(x, nrow)
    off = jnp.where(valid, x - lo, -1.0)       # exact: integers in [0, R)
    ks = jnp.arange(width, dtype=x.dtype)[:, None]
    present = jnp.any(ks == off[None, :], axis=1)
    below = jnp.sum(present[:, None] & (ks < off[None, :]), axis=0,
                    dtype=jnp.int32)
    return jnp.where(valid, below, ENUM_NA), present


def _width(n: int) -> int:
    """Power-of-two program size for n: a handful of programs in all."""
    return 1 << max(0, int(n) - 1).bit_length()


def factor(x, nrow: int):
    """(codes, domain) for a float32 payload ``x`` of ``nrow`` rows, or
    None where the host formula has to make it (see the module)."""
    if nrow < DEVICE_MIN_ROWS:
        return None
    from h2o3_tpu.telemetry import device_get
    count, lo, hi, small_int = device_get(_summary(x, nrow))
    lo, hi = (float(lo), float(hi)) if count else (0.0, 0.0)
    if not (small_int and hi - lo + 1 <= RANGE_MAX):
        return None
    codes, present = _range_codes(x, nrow, lo, width=_width(hi - lo + 1))
    ks = np.flatnonzero(device_get(present))
    return codes, tuple(level_label(lo + k) for k in ks)
