"""Kernel variant shootout for the adaptive histogram level kernel.

Times the deepest level (N=32, the dominant cost) for several kernel
variants at 10M rows to find what to change in ops/hist_adaptive.py.
"""
import sys, os, time, functools
sys.path.insert(0, '/root/repo')

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 10_002_432
F, W = 28, 32
N = 32
TILE = int(os.environ.get("TILE", 4096))
REPS = 10
_VMEM_LIMIT = 100 * 1024 * 1024
HI = jax.lax.Precision.HIGHEST


def _route(x, nid, tabs_ref, n_prev, level_base, tile, F):
    prev_base = level_base - n_prev
    lid_p = nid - prev_base
    onp = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, tile), 0)
           == lid_p[None, :]).astype(jnp.float32)
    t4 = tabs_ref[:, :n_prev]
    lut = jax.lax.dot_general(t4, onp, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32,
                              precision=HI)
    f_r, t_r, nl_r, cn_r = lut[0], lut[1], lut[2], lut[3]
    fi = jax.lax.broadcasted_iota(jnp.int32, (tile, F), 1)
    xsel = jnp.sum(jnp.where(fi == f_r.astype(jnp.int32)[:, None], x, 0.0),
                   axis=1)
    gr_f = jnp.where(jnp.isnan(xsel), 1.0 - nl_r,
                     (xsel >= t_r).astype(jnp.float32))
    in_prev = (lid_p >= 0) & (lid_p < n_prev)
    child = 2 * nid + 1 + gr_f.astype(jnp.int32)
    return jnp.where(in_prev & (cn_r > 0.5), child, nid)


def _kernel(x_ref, nid_ref, ghw_ref, tabs_ref, loinv_ref, nid_out, hist_out,
            acc_ref, *, n_prev, n_nodes, F, W, tile, n_row_tiles, level_base,
            mxu_dtype, variant):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    nid = nid_ref[0, :]
    if n_prev > 0 and variant != "noroute":
        nid = _route(x, nid, tabs_ref, n_prev, level_base, tile, F)
    nid_out[0, :] = nid

    lid = nid - level_base
    in_lvl = (lid >= 0) & (lid < n_nodes)
    lidc = jnp.where(in_lvl, lid, 0)
    onh = (jax.lax.broadcasted_iota(jnp.int32, (n_nodes, tile), 0)
           == lidc[None, :])
    onh_f = onh.astype(jnp.float32) * in_lvl.astype(jnp.float32)[None, :]
    if variant == "noloinv":
        lo_r = jnp.full((tile, F), -4.0, jnp.float32)
        inv_r = jnp.full((tile, F), (W - 2) / 8.0, jnp.float32)
    else:
        loinv_r = jax.lax.dot_general(onh_f, loinv_ref[...],
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32,
                                      precision=HI)
        lo_r = loinv_r[:, :F]
        inv_r = loinv_r[:, F:]
    bin_f = jnp.floor(jnp.clip((x - lo_r) * inv_r, 0.0, float(W - 2)))
    bin_v = jnp.where(jnp.isnan(x), float(W - 1), bin_f)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, F * W), 1)
    if variant in ("base", "noroute", "noloinv", "nohist"):
        sel = (jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 1) // W
               == jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 0)
               ).astype(jnp.float32)
        b_all = jax.lax.dot_general(bin_v, sel, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    elif variant == "bf16sel":
        sel = (jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 1) // W
               == jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 0)
               ).astype(jnp.bfloat16)
        b_all = jax.lax.dot_general(bin_v.astype(jnp.bfloat16), sel,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    elif variant == "bcast":
        b_all = jnp.broadcast_to(bin_v[:, :, None], (tile, F, W)
                                 ).reshape(tile, F * W)
    elif variant == "repeat":
        b_all = jnp.repeat(bin_v, W, axis=1)
    oh = ((lane % W) == b_all.astype(jnp.int32)).astype(mxu_dtype)
    ghw = ghw_ref[...]
    left = jnp.concatenate(
        [onh_f.astype(mxu_dtype) * ghw[k, :][None, :].astype(mxu_dtype)
         for k in range(3)], axis=0)
    if variant == "nohist":
        acc_ref[...] += jnp.broadcast_to(
            jnp.sum(oh.astype(jnp.float32), axis=0, keepdims=True)[:, :acc_ref.shape[1]],
            acc_ref.shape) * left[0, 0]
    else:
        acc_ref[...] += jax.lax.dot_general(
            left, oh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=(HI if mxu_dtype == jnp.float32
                       else jax.lax.Precision.DEFAULT))

    @pl.when(r == n_row_tiles - 1)
    def _flush():
        hist_out[...] = acc_ref[...]


def level(x, nid, ghw, tables, lo, inv, n_prev, n_nodes, level_base, W,
          tile, variant, mxu_dtype=jnp.bfloat16):
    rows, F = x.shape
    n_row_tiles = rows // tile
    tabs = jnp.stack(tables, axis=0)
    np1 = tabs.shape[1]
    loinv = jnp.concatenate([lo, inv], axis=1)
    kern = functools.partial(_kernel, n_prev=n_prev, n_nodes=n_nodes, F=F,
                             W=W, tile=tile, n_row_tiles=n_row_tiles,
                             level_base=level_base, mxu_dtype=mxu_dtype,
                             variant=variant)
    nid2, hist = pl.pallas_call(
        kern,
        grid=(n_row_tiles,),
        in_specs=[
            pl.BlockSpec((tile, F), lambda r: (r, 0)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((3, tile), lambda r: (0, r)),
            pl.BlockSpec((4, np1), lambda r: (0, 0)),
            pl.BlockSpec((n_nodes, 2 * F), lambda r: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((3 * n_nodes, F * W), lambda r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows), jnp.int32),
            jax.ShapeDtypeStruct((3 * n_nodes, F * W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((3 * n_nodes, F * W), jnp.float32)],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
    )(x, nid[None, :], ghw, tabs, loinv)
    return nid2[0], hist


def main():
    rows = ROWS - (ROWS % TILE)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(rows, F)).astype(np.float32))
    ghw = jnp.stack([jnp.asarray(rng.normal(size=rows).astype(np.float32)),
                     jnp.ones(rows, jnp.float32), jnp.ones(rows, jnp.float32)])
    # realistic nids: uniformly in the previous level
    n_prev = N // 2
    base = N - 1
    prev_base = base - n_prev
    nid = jnp.asarray(prev_base
                      + rng.integers(0, n_prev, rows).astype(np.int32))
    tables = (jnp.asarray(rng.integers(0, F, n_prev).astype(np.float32)),
              jnp.zeros(n_prev, jnp.float32), jnp.zeros(n_prev, jnp.float32),
              jnp.ones(n_prev, jnp.float32))
    lo = jnp.full((N, F), -4.0, jnp.float32)
    inv = jnp.full((N, F), (W - 2) / 8.0, jnp.float32)
    jax.device_get(jnp.sum(X[0]))

    ref_hist = None
    variants = os.environ.get(
        "VARIANTS", "base,bf16sel,bcast,repeat,noroute,noloinv").split(",")
    for variant in variants:
        try:
            def loop(X, nid, ghw, tables, lo, inv, variant=variant):
                def body(i, carry):
                    nid_c, acc = carry
                    nid2, hist = level(X, nid_c, ghw, tables, lo, inv,
                                       n_prev, N, base, W, TILE, variant)
                    return (jnp.where(nid2 > 0, nid_c, nid_c),
                            acc + hist[0, :8].sum())
                return jax.lax.fori_loop(0, REPS, body, (nid, 0.0))

            f = jax.jit(loop)
            out = f(X, nid, ghw, tables, lo, inv)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            out = f(X, nid, ghw, tables, lo, inv)
            jax.block_until_ready(out)
            t = (time.perf_counter() - t0) / REPS
            # correctness vs base (single call, full hist)
            nid2, hist = jax.jit(functools.partial(
                level, n_prev=n_prev, n_nodes=N, level_base=base, W=W,
                tile=TILE, variant=variant))(X, nid, ghw, tables, lo, inv)
            hs = np.asarray(jax.device_get(hist))
            if variant == "base":
                ref_hist = hs
                match = "ref"
            else:
                match = ("OK" if ref_hist is not None and
                         np.allclose(hs, ref_hist, rtol=2e-2, atol=1.0)
                         else "DIFF")
            print(f"{variant:10s}: {t*1000:7.2f} ms/level  [{match}]",
                  flush=True)
        except Exception as e:
            print(f"{variant:10s}: FAILED {type(e).__name__}: "
                  f"{str(e)[:200]}", flush=True)


if __name__ == "__main__":
    main()
