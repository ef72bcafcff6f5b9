"""Ablation timing of the adaptive level kernel (deepest level, N=32).

Feeds nid2 back between fori_loop iterations so XLA can't hoist/CSE.
Each ablation removes one phase; the delta vs base is that phase's cost.

Multi-level mode (``LEVELS=1,2,4``): times the PACKED-code level body
(_kernel_bt shape: int8 codes, one-hot off the sublane repeat, ghw
contraction) chained L levels inside ONE jitted dispatch — the fused
window the streamed grower issues when H2O3_LEVELS_PER_PASS > 1. Per L
it reports ms/level plus the phase split from ablations: the one-hot
build share, the MXU contraction share (vs everything-else = VPU), the
routing share, and — comparing per-level time across L — the
dispatch-overhead share the fusion amortizes away. Runs under
H2O3_PALLAS_INTERPRET=1 at reduced ROWS for CPU smoke checks.
"""
import sys, os, time, functools
sys.path.insert(0, '/root/repo')

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


ROWS = 10_002_432
F, W, N = 28, 32, 32
TILE = 4096
REPS = 10
_VM = 100 * 1024 * 1024


def make_kernel(ablate):
    def kern(x_ref, nid_ref, ghw_ref, tabs_ref, loinv_ref, nid_out, hist_out,
             acc_ref):
        r = pl.program_id(0)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        nid = nid_ref[0, :]
        n_prev = N // 2
        base = N - 1
        if ablate != "route":
            prev_base = base - n_prev
            lid_p = nid - prev_base
            onp = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, TILE), 0)
                   == lid_p[None, :]).astype(jnp.bfloat16)
            lut3 = jax.lax.dot_general(tabs_ref[:, :n_prev], onp,
                                       (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            lut = lut3[0:4] + lut3[4:8] * (1/256.) + lut3[8:12] * (1/65536.)
            f_r, t_r, nl_r, cn_r = lut[0], lut[1], lut[2], lut[3]
            fi = jax.lax.broadcasted_iota(jnp.int32, (TILE, F), 1)
            xsel = jnp.sum(jnp.where(fi == f_r.astype(jnp.int32)[:, None],
                                     x, 0.0), axis=1)
            gr_f = jnp.where(jnp.isnan(xsel), 1.0 - nl_r,
                             (xsel >= t_r).astype(jnp.float32))
            in_prev = (lid_p >= 0) & (lid_p < n_prev)
            child = 2 * nid + 1 + gr_f.astype(jnp.int32)
            nid = jnp.where(in_prev & (cn_r > 0.5), child, nid)
        nid_out[0, :] = nid

        lid = nid - base
        in_lvl = (lid >= 0) & (lid < N)
        lidc = jnp.where(in_lvl, lid, 0)
        onh = (jax.lax.broadcasted_iota(jnp.int32, (N, TILE), 0)
               == lidc[None, :])
        onh_f = onh.astype(jnp.float32) * in_lvl.astype(jnp.float32)[None, :]
        if ablate == "loinv":
            lo_r = jnp.full((TILE, F), -4.0, jnp.float32)
            inv_r = jnp.full((TILE, F), (W - 2) / 8.0, jnp.float32)
        else:
            onh_b = onh_f.astype(jnp.bfloat16)
            lr3 = jax.lax.dot_general(onh_b, loinv_ref[...],
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            lr = lr3[:, :2*F] + lr3[:, 2*F:4*F] * (1/256.) + lr3[:, 4*F:] * (1/65536.)
            lo_r = lr[:, :F]
            inv_r = lr[:, F:]
        bin_f = jnp.floor(jnp.clip((x - lo_r) * inv_r, 0.0, float(W - 2)))
        bin_v = jnp.where(jnp.isnan(x), float(W - 1), bin_f)
        if ablate == "sel":
            # skip the selector matmul: bogus b_all from a cheap broadcast
            b_all = jnp.broadcast_to(bin_v[:, :1], (TILE, F * W))
        else:
            sel = (jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 1) // W
                   == jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 0)
                   ).astype(jnp.bfloat16)
            b_all = jax.lax.dot_general(bin_v.astype(jnp.bfloat16), sel,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        lane = jax.lax.broadcasted_iota(jnp.int32, (TILE, F * W), 1)
        if ablate == "onehot":
            oh = b_all.astype(jnp.bfloat16)  # skip compare, keep shape
        else:
            oh = ((lane % W).astype(jnp.float32) == b_all
                  ).astype(jnp.bfloat16)
        ghw = ghw_ref[...]
        if ablate == "left":
            left = jnp.broadcast_to(ghw[0, :].astype(jnp.bfloat16)[None, :],
                                    (3 * N, TILE))
        else:
            left = jnp.concatenate(
                [onh_f.astype(jnp.bfloat16) * ghw[k, :][None, :
                 ].astype(jnp.bfloat16) for k in range(3)], axis=0)
        if ablate == "matmul":
            acc_ref[...] += jnp.broadcast_to(
                oh[:1, :acc_ref.shape[1]] + left[0, 0], acc_ref.shape)
        else:
            acc_ref[...] += jax.lax.dot_general(
                left, oh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(r == ROWS // TILE - 1)
        def _flush():
            hist_out[...] = acc_ref[...]
    return kern


def run(ablate, X, nid0, ghw, tabs, loinv):
    kern = make_kernel(ablate)
    n_tiles = X.shape[0] // TILE

    def level(X, nid, ghw, tabs, loinv):
        nid2, hist = pl.pallas_call(
            kern,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((TILE, F), lambda r: (r, 0)),
                pl.BlockSpec((1, TILE), lambda r: (0, r)),
                pl.BlockSpec((3, TILE), lambda r: (0, r)),
                pl.BlockSpec((12, N // 2), lambda r: (0, 0)),
                pl.BlockSpec((N, 6 * F), lambda r: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, TILE), lambda r: (0, r)),
                pl.BlockSpec((3 * N, F * W), lambda r: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, X.shape[0]), jnp.int32),
                jax.ShapeDtypeStruct((3 * N, F * W), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((3 * N, F * W), jnp.float32)],
            cost_estimate=(pl.CostEstimate(
                flops=2 * 3 * N * F * W * X.shape[0],
                bytes_accessed=X.shape[0] * F * 4 + X.shape[0] * 16,
                transcendentals=0) if os.environ.get("COST") else None),
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VM),
        )(X, nid[None, :], ghw, tabs, loinv)
        return nid2[0], hist

    def loop(X, nid, ghw, tabs, loinv):
        def body(i, carry):
            nid_c, acc = carry
            nid2, hist = level(X, nid_c, ghw, tabs, loinv)
            return (jnp.abs(nid2) % (2 * N - 1) + (N - 1) - N // 2,
                    acc + hist[0, 0])
        return jax.lax.fori_loop(0, REPS, body, (nid0, 0.0))

    f = jax.jit(loop)
    out = f(X, nid0, ghw, tabs, loinv)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = f(X, nid0, ghw, tabs, loinv)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / REPS


# ------------------------------------------------------------- levels
# Multi-level fused ablation (packed codes): the production streamed
# grower's window shape — L binned levels traced into one executable,
# nid carried on device between them.

LN, LF, LW = 32, 28, 16          # deepest level, features, packed bins


def make_packed_kernel(ablate, tile, n_tiles, mxu_dtype=jnp.bfloat16):
    from h2o3_tpu.ops.hist_adaptive import _route_bt

    def kern(c_ref, nid_ref, ghw_ref, tabs_ref, nid_out, hist_out, acc_ref):
        r = pl.program_id(0)

        @pl.when(r == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        cf = c_ref[...].astype(jnp.int32).astype(jnp.float32)  # [F, tile]
        nid = nid_ref[0, :]
        if ablate != "route":
            nid = _route_bt(cf, nid, tabs_ref, LN // 2, LN - 1, tile,
                            LF, LW)
        nid_out[0, :] = nid
        lid = nid - (LN - 1)
        in_lvl = (lid >= 0) & (lid < LN)
        lidm = jnp.where(in_lvl, lid, -1)
        onh_m = (jax.lax.broadcasted_iota(jnp.int32, (LN, tile), 0)
                 == lidm[None, :]).astype(mxu_dtype)
        b_all = jnp.repeat(cf, LW, axis=0)                 # [F*W, tile]
        if ablate == "onehot":
            oh_t = b_all.astype(mxu_dtype)   # keep repeat, skip compare
        else:
            brow = jax.lax.broadcasted_iota(jnp.int32, (LF * LW, tile), 0)
            oh_t = ((brow % LW).astype(jnp.float32) == b_all
                    ).astype(mxu_dtype)
        ghw_m = ghw_ref[...].astype(mxu_dtype)
        left = jnp.concatenate(
            [onh_m * ghw_m[k, :][None, :] for k in range(3)], axis=0)
        if ablate == "matmul":
            acc_ref[...] += jnp.broadcast_to(oh_t[0, 0] + left[0, 0],
                                             acc_ref.shape)
        else:
            acc_ref[...] += jax.lax.dot_general(
                left, oh_t, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)

        @pl.when(r == n_tiles - 1)
        def _flush():
            hist_out[...] = acc_ref[...]
    return kern


def run_levels(L, ablate, ct, nid0, ghw, tabs, tile, interp):
    rows = ct.shape[1]
    n_tiles = rows // tile
    kern = make_packed_kernel(ablate, tile, n_tiles)
    np1 = tabs.shape[1]

    def level(ct, nid, ghw, tabs):
        nid2, hist = pl.pallas_call(
            kern,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((LF, tile), lambda r: (0, r)),
                pl.BlockSpec((1, tile), lambda r: (0, r)),
                pl.BlockSpec((3, tile), lambda r: (0, r)),
                pl.BlockSpec((12, np1), lambda r: (0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, tile), lambda r: (0, r)),
                pl.BlockSpec((3 * LN, LF * LW), lambda r: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((1, rows), jnp.int32),
                jax.ShapeDtypeStruct((3 * LN, LF * LW), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((3 * LN, LF * LW), jnp.float32)],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VM),
            interpret=interp,
        )(ct, nid[None, :], ghw, tabs)
        return nid2[0], hist

    def window(ct, nid, ghw, tabs):
        # L levels, ONE dispatch: nid feeds forward (renormalized into
        # the parent band so routing stays live and XLA can't CSE)
        hist = None
        for _ in range(L):
            nid2, hist = level(ct, nid, ghw, tabs)
            nid = (jnp.abs(nid2) % (2 * LN - 1)
                   + (LN - 1) - LN // 2)
        return nid, hist[0, 0]

    f = jax.jit(window)
    reps = max(1, REPS // L)
    nid, s = f(ct, nid0, ghw, tabs)
    jax.block_until_ready((nid, s))
    t0 = time.perf_counter()
    for _ in range(reps):
        nid, s = f(ct, nid, ghw, tabs)   # one host dispatch per window
    jax.block_until_ready((nid, s))
    return (time.perf_counter() - t0) / (reps * L)


def main_levels(levels):
    from h2o3_tpu.ops.hist_adaptive import _pack_tables, pallas_interpret
    interp = pallas_interpret()
    tile = int(os.environ.get("LTILE", 512 if interp else 8192))
    rows_d = 8 * tile if interp else 2_502_656
    rows = int(os.environ.get("LROWS", rows_d))
    rows -= rows % tile
    rng = np.random.default_rng(0)
    ct = jnp.asarray(rng.integers(0, LW - 1, size=(LF, rows)).astype(np.int8))
    ghw = jnp.stack([jnp.asarray(rng.normal(size=rows).astype(np.float32)),
                     jnp.ones(rows, jnp.float32),
                     jnp.ones(rows, jnp.float32)])
    n_prev = LN // 2
    nid0 = jnp.asarray((LN - 1 - n_prev
                        + rng.integers(0, n_prev, rows)).astype(np.int32))
    tabs = _pack_tables((
        jnp.asarray(rng.integers(0, LF, n_prev).astype(np.float32)),
        jnp.asarray(rng.integers(1, LW - 1, n_prev).astype(np.float32)),
        jnp.asarray((rng.random(n_prev) < 0.5).astype(np.float32)),
        jnp.ones(n_prev, jnp.float32)))
    per_l1 = None
    for L in levels:
        t = {}
        for ab in ("none", "route", "onehot", "matmul"):
            t[ab] = run_levels(L, ab, ct, nid0, ghw, tabs, tile, interp)
        base = t["none"]
        mxu = max(0.0, 1 - t["matmul"] / base)
        oneh = max(0.0, 1 - t["onehot"] / base)
        rout = max(0.0, 1 - t["route"] / base)
        extra = ""
        if L == 1:
            per_l1 = base
        elif per_l1:
            extra = (f"  dispatch-overhead saved vs L=1: "
                     f"{max(0.0, 1 - base / per_l1) * 100:5.1f}%")
        print(f"L={L}: {base*1000:8.3f} ms/level  "
              f"mxu {mxu:.2f} / vpu {1-mxu:.2f}  "
              f"onehot {oneh:.2f}  route {rout:.2f}{extra}", flush=True)


def main():
    from h2o3_tpu.ops.hist_adaptive import _split3_bf16
    rows = ROWS - (ROWS % TILE)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(rows, F)).astype(np.float32))
    ghw = jnp.stack([jnp.asarray(rng.normal(size=rows).astype(np.float32)),
                     jnp.ones(rows, jnp.float32), jnp.ones(rows, jnp.float32)])
    n_prev = N // 2
    nid0 = jnp.asarray((N - 1 - n_prev
                        + rng.integers(0, n_prev, rows)).astype(np.int32))
    t4 = jnp.asarray(np.stack([
        rng.integers(0, F, n_prev).astype(np.float32),
        rng.normal(size=n_prev).astype(np.float32),
        (rng.random(n_prev) < 0.5).astype(np.float32),
        np.ones(n_prev, np.float32)]))
    tabs = _split3_bf16(t4, axis=0)
    lo = np.full((N, F), -4.0, np.float32)
    inv = np.full((N, F), (W - 2) / 8.0, np.float32)
    loinv = _split3_bf16(jnp.asarray(np.concatenate([lo, inv], 1)), axis=1)
    jax.device_get(jnp.sum(X[0]))
    base = None
    for ab in os.environ.get(
            "ABLATE", "none,route,loinv,sel,onehot,left,matmul").split(","):
        try:
            t = run(ab, X, nid0, ghw, tabs, loinv)
            if ab == "none":
                base = t
            delta = f"  (saves {1000*(base-t):6.2f} ms)" if base and ab != "none" else ""
            print(f"{ab:8s}: {t*1000:7.2f} ms/level{delta}", flush=True)
        except Exception as e:
            print(f"{ab:8s}: FAILED {type(e).__name__} {str(e)[:150]}",
                  flush=True)


if __name__ == "__main__":
    lv = os.environ.get("LEVELS")
    if lv:
        main_levels([max(1, int(x)) for x in lv.split(",")])
    else:
        main()
