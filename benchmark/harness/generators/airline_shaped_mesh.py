"""``airline_shaped``'s table, made ROW-SHARDED: the same rows for a seed and
a row count, each block on the chip that holds it.

``airline_shaped._make`` makes the table in blocks of ``BLOCK`` rows, block
``i`` from ``fold_in(k_rows, i)``: a block is a function of the seed and of
its GLOBAL index alone. Here the padded rows are split evenly over
``devices`` chips, as the platform's data axis splits them, and every chip
makes the blocks that cover its own range and keeps its slice of them, so
the table never exists on one chip (123.5M x 8 floats are 3.95 GB, and as
much again in temporaries). What comes back is (X [padded, 8], y [padded])
sharded by rows over a one-axis mesh of those chips: the split
``h2o3_tpu.parallel.mesh.data_sharding()`` makes over the same chips, so
handing it to the platform moves nothing. The level effects and the
popularity permutations come from the seed alone and are made on every chip.

``benchmark/tests/test_airline_mesh_cell.py`` holds the two generators equal
bit for bit at a size one device holds."""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from harness.generators import airline_shaped as one

COLUMNS = one.COLUMNS
key_of = one.key_of


def blocks(key, index, n: int):
    """Blocks ``index`` [k] of ``n`` rows each, X [k, n, 8] and y [k, n]:
    the body of ``airline_shaped._make``'s ``block``, which is local to
    that function and which this PR may not edit; the test named above
    holds the copy to it bit for bit."""
    k_eff, k_rows = jax.random.split(key)
    ke = jax.random.split(k_eff, 5)
    eff = {"carrier": 0.5 * jax.random.normal(ke[0], (22,)),
           "origin": 0.6 * jax.random.normal(ke[1], (300,)),
           "dest": 0.4 * jax.random.normal(ke[2], (300,)),
           "month": 0.3 * jax.random.normal(ke[3], (12,)),
           "weekday": 0.2 * jax.random.normal(ke[4], (7,))}
    k_ca, k_or, k_de = jax.random.split(jax.random.fold_in(k_eff, 7), 3)

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
        carrier = one._popular(k_ca, k[6], n, 22, 1.0)
        origin = one._popular(k_or, k[7], n, 300, 1.1)
        dest = one._popular(k_de, k[8], n, 300, 1.1)
        dist = jnp.clip(jnp.exp(6.3 + 0.75 * jax.random.normal(
            jax.random.fold_in(k[9], 1), (n,))), 11.0, 4962.0)
        dist = jnp.round(dist)
        logit = (one._effect(eff["carrier"], carrier)
                 + one._effect(eff["origin"], origin)
                 + one._effect(eff["dest"], dest)
                 + one._effect(eff["month"], month)
                 + one._effect(eff["weekday"], dow)
                 + 1.1 * (dep / 2400.0 - 0.55)
                 + 0.1 * (jnp.log(dist) - 6.3) - 1.65)
        y = jax.random.uniform(jax.random.fold_in(k[9], 2), (n,)) \
            < jax.nn.sigmoid(logit)
        X = jnp.stack([month, dom, dow, dep, carrier, origin, dest, dist],
                      axis=1).astype(jnp.float32)
        return X, y.astype(jnp.float32)

    return jax.lax.map(block, index)


def row_mesh(devices: int) -> Mesh:
    """The first ``devices`` chips as one axis, ``rows``."""
    found = jax.devices()
    if len(found) < devices:
        raise ValueError(f"{devices} devices asked for, JAX sees {len(found)}")
    return Mesh(np.array(found[:devices]), ("rows",))


@lru_cache(maxsize=8)
def _maker(devices: int, rows: int, padded: int):
    if padded % devices:
        raise ValueError(f"{padded} padded rows do not split evenly over "
                         f"{devices} chips")
    mesh = row_mesh(devices)
    per = padded // devices
    n = min(one.BLOCK, padded)              # airline_shaped's block length
    n_blk = -(-per // n) + 1                # blocks that cover any range

    def local(key):
        start = jax.lax.axis_index("rows") * per
        first = start // n
        X, y = blocks(key, first + jnp.arange(n_blk), n)
        at = start - first * n
        X = jax.lax.dynamic_slice_in_dim(X.reshape(-1, 8), at, per)
        y = jax.lax.dynamic_slice_in_dim(y.reshape(-1), at, per)
        real = (start + jnp.arange(per)) < rows
        return (jnp.where(real[:, None], X, jnp.nan),
                jnp.where(real, y, jnp.nan))

    # check_vma off: airline_shaped's loops start from carries that do not
    # vary over the mesh axis, and their bodies make them vary
    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=P(),
                                 out_specs=(P("rows"), P("rows")),
                                 check_vma=False)), mesh


def make(seed: int, rows: int, padded: int, features: int = 8,
         part: int = 0, devices: int = 1):
    """(X [padded, 8] f32, y [padded] f32), rows split evenly over the
    first ``devices`` chips; the values are ``airline_shaped.make``'s."""
    if int(features) != len(COLUMNS):
        raise ValueError(f"the airline table has {len(COLUMNS)} predictors, "
                         f"not {features}")
    fn, mesh = _maker(int(devices), int(rows), int(padded))
    key = jax.device_put(key_of(seed, part), NamedSharding(mesh, P()))
    return fn(key)
