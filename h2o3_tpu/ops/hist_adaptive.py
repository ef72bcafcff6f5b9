"""The tree trainers' level kernels: route a level's rows, bin them and
build the level's (g, h, w) histograms, one Pallas call a tree level.

What the file holds, by who reaches it (models/tree.py ``tree_path``
picks the grower; the dispatchers here pick the body):

- PACKED BINNED levels, ``binned_level`` / ``binned_route_only``
  (grow_tree_binned on int8/int16 codes from ops/binning.pack_codes).
  The benchmark's cells run exactly two bodies, named as the device
  trace prints them: ``binned_level_tpu_t`` (body ``_kernel_bt``) for
  levels 0..D-1 and ``binned_route_only_tpu_t`` (``_route_kernel_bt``)
  for the leaves' routing. Below the root a packed level accumulates ONE
  child of every previous-level node and the grower takes the sibling
  by subtraction (the conventions above ``code_dtype``). Off their path:
  ``binned_level_tpu_stripe`` (``_kernel_bt_stripe``) at W == 16
  (nbins <= 14) where its probe passes or ``H2O3_STRIPE`` says so, and
  the scatter references ``binned_level_xla`` /
  ``binned_route_only_xla`` off the TPU.
- f32 ADAPTIVE levels, ``adaptive_level`` / ``route_only``
  (grow_tree_adaptive on raw features): ``packed_codes=False``,
  ``histogram_type="random"``, categorical domains too wide to pack.
  Transposed bodies ``_kernel_t`` / ``_route_kernel_t``; scatter
  references ``adaptive_level_xla`` / ``route_only_xla``.
- ROW-MAJOR f32 bodies ``_kernel`` / ``_route_kernel``: reached only
  without a transposed operand: grow_tree_adaptive_streamed, tests.

Reference semantics of the adaptive levels (hex/tree/DHistogram.java):
H2O's default ``histogram_type=UniformAdaptive`` re-bins every feature
PER NODE over the node's value range with ``nbins`` uniform bins
(DHistogram.java:48; ScoreBuildHistogram2.java:121-301 builds (w, wY,
wYY) per bin), so after d levels a feature's effective resolution is
~nbins·2^d; the packed levels are XGBoost's global sketch, binned once.

One level, in the TRANSPOSED layout x_t [F, rows] (rows on the 128-lane
axis: a [rows, F] array tiles F onto the lanes, so F=28 reads waste
100/128 of the HBM bandwidth; [F, rows] pads F only 28→32 sublanes):
  1. ROUTE: each row steps through the previous level's split tables
     (bf16-split [12, n_prev] = feat/thr/na_left/can, exact via
     _split3_bf16). The lookup is ONE merged one-hot matmul; the
     split-feature value is selected by compare-accumulate over F
     sublanes.
  2. BIN (adaptive only; a packed code IS its bin):
     b = isnan(x) ? W-1 : floor(clip((x - lo[n,f]) * inv[n,f]))
     with per-(node, feature) range tables, one merged [6F, N] lookup
     matmul against the node one-hot.
  3. HIST: the bin row broadcasts to [F*W, tile] with a SUBLANE repeat
     (the row-major layout needs a selector matmul and a 14MB f32
     intermediate here), one-hots against a sublane iota, then
     contracts against node-onehot x (g,h,w) on the MXU (lane-dim
     contraction both sides), accumulating in VMEM.

The cross-shard reduction (the MRTask reduce tree / Rabit ring analog)
is a single ``lax.psum`` of the returned histogram by the caller.

Deviation from the reference, documented: child ranges are derived from
the parent's split point (split feature — exact) and the parent's
occupied-bin range (other features — within one bin width), instead of
re-measuring exact per-child min/max; and routing compares raw
``x >= thr``, so training-time routing is bit-identical to scoring-time
tree walks. W (bin lanes per feature) is static per compile: 16 / 32 /
64 / 128 / 256 cover nbins <= 14 / 30 / 62 / 126 / 254; the last lane
is NA.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

import os as _os

TILE = int(_os.environ.get("H2O3_HIST_TILE", 8192))
# default scoped-vmem stack limit is 16MB; the accumulator + one-hot want
# more at deeper levels / larger tiles (v5e has 128MB VMEM)
_VMEM_LIMIT = 100 * 1024 * 1024


_SPLIT_S1 = 256.0        # 2^8  — exact bf16 scaling
_SPLIT_S2 = 65536.0      # 2^16


def _split3_bf16(t: jnp.ndarray, axis: int = 0) -> jnp.ndarray:
    """Exact 3-term bf16 decomposition of an f32 array, concatenated along
    ``axis``: t == hi + mid/2^8 + lo/2^16 bit-for-bit (8+8+8 mantissa bits
    >= f32's 24; the residual after two splits has <= 8 significant bits so
    the third term is exact). A one-hot matmul against the concatenated
    bf16 table then reproduces the f32 lookup EXACTLY with one 1-pass bf16
    MXU product per term — ~6x cheaper than a HIGHEST (f32 6-pass) matmul.

    The mid/lo terms are PRE-SCALED by 2^8 / 2^16 (exact power-of-two
    bf16 ops) and the kernel multiplies the partial results back down
    before summing. The residuals are computed with lax.reduce_precision,
    NOT astype(bf16).astype(f32): under jit, XLA's default
    --xla_allow_excess_precision legally elides f32->bf16->f32 round
    trips, which would zero the residuals and collapse every table entry
    to its bf16 rounding (observed on v5e: t_r == bf16(thr), flipping
    routing for rows within a bf16 ulp of a split threshold)."""
    t = t.astype(jnp.float32)
    hi_v = jax.lax.reduce_precision(t, 8, 7)          # bf16-valued f32
    r1 = (t - hi_v) * _SPLIT_S1
    mid_v = jax.lax.reduce_precision(r1, 8, 7)
    lo_v = (r1 - mid_v) * _SPLIT_S1                   # exact in bf16 already
    return jnp.concatenate([hi_v.astype(jnp.bfloat16),
                            mid_v.astype(jnp.bfloat16),
                            lo_v.astype(jnp.bfloat16)], axis=axis)


def _unsplit3(p_hi, p_mid, p_lo):
    """Recombine partial one-hot lookups of a _split3_bf16 table (f32)."""
    return p_hi + (p_mid * (1.0 / _SPLIT_S1) + p_lo * (1.0 / _SPLIT_S2))


def _route(x, nid, tabs_ref, n_prev, level_base, tile, F):
    """Shared routing block: step rows through the previous level's split
    tables (bf16-split [12, np] = 3 exact terms x feat/thr/na_left/can)
    with ONE merged 1-pass bf16 LUT matmul. The one-hot RHS makes the
    3-term reconstruction exact (see _split3_bf16) — a plain bf16-rounded
    threshold WOULD flip routing for rows near the split boundary."""
    prev_base = level_base - n_prev
    lid_p = nid - prev_base
    onp = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, tile), 0)
           == lid_p[None, :]).astype(jnp.bfloat16)
    t12 = tabs_ref[:, :n_prev]                        # [12, n_prev] bf16
    lut3 = jax.lax.dot_general(t12, onp, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # [12, tile]
    lut = _unsplit3(lut3[0:4], lut3[4:8], lut3[8:12])  # exact f32 rebuild
    f_r, t_r, nl_r, cn_r = lut[0], lut[1], lut[2], lut[3]
    # x[r, feat_r] via compare-accumulate (f_r is an exact int-valued
    # float: one-hot matmul of ints < 2^24)
    fi = jax.lax.broadcasted_iota(jnp.int32, (tile, F), 1)
    xsel = jnp.sum(jnp.where(fi == f_r.astype(jnp.int32)[:, None],
                             x, 0.0), axis=1)
    # float selects only: bool-branch select_n lowers to an i8->i1
    # truncation Mosaic rejects
    gr_f = jnp.where(jnp.isnan(xsel), 1.0 - nl_r,
                     (xsel >= t_r).astype(jnp.float32))
    in_prev = (lid_p >= 0) & (lid_p < n_prev)
    child = 2 * nid + 1 + gr_f.astype(jnp.int32)
    return jnp.where(in_prev & (cn_r > 0.5), child, nid)


def _kernel(x_ref, nid_ref, ghw_ref, tabs_ref, loinv_ref, nid_out, hist_out,
            acc_ref, *, n_prev: int, n_nodes: int, F: int, W: int, tile: int,
            n_row_tiles: int, level_base: int, mxu_dtype):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                   # [tile, F] f32
    nid = nid_ref[0, :]                              # [tile] i32 global ids
    if n_prev > 0:
        nid = _route(x, nid, tabs_ref, n_prev, level_base, tile, F)
    nid_out[0, :] = nid

    lid = nid - level_base
    in_lvl = (lid >= 0) & (lid < n_nodes)
    lidc = jnp.where(in_lvl, lid, 0)
    onh = (jax.lax.broadcasted_iota(jnp.int32, (n_nodes, tile), 0)
           == lidc[None, :])
    onh_f = onh.astype(jnp.float32) * in_lvl.astype(jnp.float32)[None, :]
    # per-row ranges in ONE merged [N, 6F] bf16-split lookup matmul. Bin
    # boundaries must match the split-side threshold arithmetic exactly;
    # the 3-term bf16 reconstruction against the one-hot LHS is exact
    # (see _split3_bf16) while a rounded lo breaks deep narrowed ranges
    # (|lo| >> span).
    onh_b = onh_f.astype(jnp.bfloat16)
    loinv_r3 = jax.lax.dot_general(onh_b, loinv_ref[...],
                                   (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)  # [tile, 6F]
    loinv_r = _unsplit3(loinv_r3[:, :2 * F], loinv_r3[:, 2 * F:4 * F],
                        loinv_r3[:, 4 * F:])
    lo_r = loinv_r[:, :F]
    inv_r = loinv_r[:, F:]
    bin_f = jnp.floor(jnp.clip((x - lo_r) * inv_r, 0.0, float(W - 2)))
    bin_v = jnp.where(jnp.isnan(x), float(W - 1), bin_f)   # [tile, F] f32
    # bin one-hot via a selector matmul: b_all[r, j] = bin of feature j//W
    # (an F-way lane-offset concatenate costs ~20% of the level at F=28).
    # Exact in ONE bf16 pass: bins and the 0/1 selector are integers
    # <= 254, within bf16's exact-integer range (<= 256).
    sel = (jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 1) // W
           == jax.lax.broadcasted_iota(jnp.int32, (F, F * W), 0)
           ).astype(jnp.bfloat16)
    b_all = jax.lax.dot_general(bin_v.astype(jnp.bfloat16), sel,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile, F * W), 1)
    oh = ((lane % W).astype(jnp.float32) == b_all).astype(mxu_dtype)
    ghw = ghw_ref[...]
    left = jnp.concatenate(
        [onh_f.astype(mxu_dtype) * ghw[k, :][None, :].astype(mxu_dtype)
         for k in range(3)], axis=0)                      # [3N, tile]
    acc_ref[...] += jax.lax.dot_general(
        left, oh, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if mxu_dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))       # [3N, FW]

    @pl.when(r == n_row_tiles - 1)
    def _flush():
        hist_out[...] = acc_ref[...]


def _pack_tables(tables):
    feat, thr, nal, can = tables
    t4 = jnp.stack([feat, thr, nal, can], axis=0)         # [4, np1] f32
    return _split3_bf16(t4, axis=0)                       # [12, np1] bf16


def adaptive_level_tpu(x, nid, ghw, tables, lo, inv, n_prev: int,
                       n_nodes: int, level_base: int, W: int,
                       tile: int = TILE, interpret: bool = False,
                       mxu_dtype=jnp.bfloat16):
    """One tree level on one shard. x [rows, F] f32 (NaN=NA; rows % tile
    == 0), nid [rows] i32, ghw [3, rows] f32, tables = (feat, thr,
    na_left, can) each [max(n_prev,1)] f32, lo/inv [n_nodes, F] f32.
    Returns (nid' [rows] i32, hist [3, n_nodes, F, W] f32 — caller psums
    across shards)."""
    rows, F = x.shape
    assert rows % tile == 0, (rows, tile)
    n_row_tiles = rows // tile
    tabs = _pack_tables(tables)
    np1 = tabs.shape[1]
    loinv = _split3_bf16(jnp.concatenate([lo, inv], axis=1),
                         axis=1)                          # [N, 6F] bf16
    kern = functools.partial(_kernel, n_prev=n_prev, n_nodes=n_nodes, F=F,
                             W=W, tile=tile, n_row_tiles=n_row_tiles,
                             level_base=level_base, mxu_dtype=mxu_dtype)
    nid2, hist = pl.pallas_call(
        kern,
        grid=(n_row_tiles,),
        in_specs=[
            pl.BlockSpec((tile, F), lambda r: (r, 0)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((3, tile), lambda r: (0, r)),
            pl.BlockSpec((12, np1), lambda r: (0, 0)),
            pl.BlockSpec((n_nodes, 6 * F), lambda r: (0, 0)),
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
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * n_nodes * F * W * rows,
            bytes_accessed=rows * F * 4 + rows * 16, transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, nid[None, :], ghw, tabs, loinv)
    return nid2[0], hist.reshape(3, n_nodes, F, W)


def adaptive_level_xla(x, nid, ghw, tables, lo, inv, n_prev: int,
                       n_nodes: int, level_base: int, W: int):
    """Pure-XLA reference/CPU path with identical semantics (scatter-add
    histogram). Used off-TPU and by parity tests."""
    rows, F = x.shape
    feat, thr, nal, can = tables
    if n_prev > 0:
        prev_base = level_base - n_prev
        lid_p = jnp.clip(nid - prev_base, 0, n_prev - 1)
        in_prev = (nid >= prev_base) & (nid < prev_base + n_prev)
        f_r = feat[lid_p].astype(jnp.int32)
        t_r = thr[lid_p]
        nl_r = nal[lid_p]
        cn_r = can[lid_p]
        xsel = jnp.take_along_axis(x, f_r[:, None], axis=1)[:, 0]
        go_right = jnp.where(jnp.isnan(xsel), nl_r < 0.5, xsel >= t_r)
        child = 2 * nid + 1 + go_right.astype(jnp.int32)
        nid = jnp.where(in_prev & (cn_r > 0.5), child, nid)
    lid = nid - level_base
    in_lvl = (lid >= 0) & (lid < n_nodes)
    lidc = jnp.where(in_lvl, lid, 0)
    lo_r = lo[lidc]                                   # [rows, F]
    inv_r = inv[lidc]
    bin_f = jnp.floor(jnp.clip((x - lo_r) * inv_r, 0.0, float(W - 2)))
    bin_i = jnp.where(jnp.isnan(x), W - 1, bin_f.astype(jnp.int32))
    flat = (lidc[:, None] * F + jnp.arange(F)[None, :]) * W + bin_i
    vw = jnp.where(in_lvl, 1.0, 0.0)
    out = jnp.zeros((n_nodes * F * W, 3), jnp.float32)
    out = out.at[flat.reshape(-1), :].add(
        (ghw.T * vw[:, None])[:, None, :].repeat(F, axis=1).reshape(-1, 3))
    hist = out.reshape(n_nodes, F, W, 3)
    return nid, jnp.moveaxis(hist, -1, 0)


def pallas_interpret() -> bool:
    """H2O3_PALLAS_INTERPRET=1 runs the pallas kernels through the
    interpreter — lets the multichip dryrun execute the kernel
    path (routing + histogram + cross-shard psum) on the virtual CPU
    mesh, where compiled Mosaic is TPU-only (read at trace time)."""
    return _os.environ.get("H2O3_PALLAS_INTERPRET", "") == "1"


def _resolve_method(method: str) -> str:
    if method != "auto":
        return method
    return "pallas" if (jax.default_backend() == "tpu"
                        or pallas_interpret()) else "scatter"


def adaptive_level(x, nid, ghw, tables, lo, inv, n_prev: int, n_nodes: int,
                   level_base: int, W: int, method: str = "auto",
                   mxu_dtype=jnp.bfloat16, xt=None):
    """Dispatch: pallas on TPU (padding rows to the tile size), scatter-XLA
    elsewhere. ``mxu_dtype`` picks the histogram contraction precision
    (tree._hist_mxu_dtype). ``xt`` ([F, rows], rows in LANES) selects the
    bandwidth-packed transposed kernel (callers materialize the transpose
    once per tree loop)."""
    method = _resolve_method(method)
    if method == "pallas":
        if xt is not None:
            rows = xt.shape[1]
            pad = (-rows) % TILE
            if pad:
                xt = jnp.pad(xt, ((0, 0), (0, pad)),
                             constant_values=jnp.nan)
                nid = jnp.pad(nid, (0, pad))
                ghw = jnp.pad(ghw, ((0, 0), (0, pad)))
            nid2, hist = adaptive_level_tpu_t(xt, nid, ghw, tables, lo, inv,
                                              n_prev, n_nodes, level_base,
                                              W, mxu_dtype=mxu_dtype,
                                              interpret=pallas_interpret())
            return nid2[:rows], hist
        rows = x.shape[0]
        pad = (-rows) % TILE
        if pad:
            # pad rows: NaN features (NA bin) with zero ghw mass — they
            # route but contribute nothing
            x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=jnp.nan)
            nid = jnp.pad(nid, (0, pad))
            ghw = jnp.pad(ghw, ((0, 0), (0, pad)))
        nid2, hist = adaptive_level_tpu(x, nid, ghw, tables, lo, inv, n_prev,
                                        n_nodes, level_base, W,
                                        mxu_dtype=mxu_dtype,
                                        interpret=pallas_interpret())
        return nid2[:rows], hist
    return adaptive_level_xla(x, nid, ghw, tables, lo, inv, n_prev,
                              n_nodes, level_base, W)


def pick_W(nbins: int) -> int:
    """Smallest supported lane width for nbins real bins (+1 NA lane).
    W=32 covers the reference's default nbins=20 at half the one-hot
    build cost of W=64; W=16 (nbins<=14) additionally halves the MXU
    passes (F*W drops below one 512-lane stripe at F=28) — per-node
    adaptive re-binning recovers the resolution with depth (AUC parity
    measured on the HIGGS bench, see bench.py)."""
    for w in (16, 32, 64, 128, 256):
        if nbins <= w - 2:
            return w
    raise ValueError(f"nbins {nbins} exceeds the adaptive kernel's 254-bin "
                     f"cap; use histogram_type='quantiles_global'")


# ---------- TRANSPOSED-LAYOUT kernels (why: the module docstring) ------

def _route_t(xt, nid, tabs_ref, n_prev, level_base, tile, F):
    """Transposed routing: xt [F, tile] (rows in lanes)."""
    prev_base = level_base - n_prev
    lid_p = nid - prev_base
    onp = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, tile), 0)
           == lid_p[None, :]).astype(jnp.bfloat16)
    t12 = tabs_ref[:, :n_prev]
    lut3 = jax.lax.dot_general(t12, onp, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    lut = _unsplit3(lut3[0:4], lut3[4:8], lut3[8:12])
    f_r, t_r, nl_r, cn_r = lut[0], lut[1], lut[2], lut[3]
    fi = jax.lax.broadcasted_iota(jnp.int32, (F, tile), 0)
    xsel = jnp.sum(jnp.where(fi == f_r.astype(jnp.int32)[None, :], xt, 0.0),
                   axis=0)
    gr_f = jnp.where(jnp.isnan(xsel), 1.0 - nl_r,
                     (xsel >= t_r).astype(jnp.float32))
    in_prev = (lid_p >= 0) & (lid_p < n_prev)
    child = 2 * nid + 1 + gr_f.astype(jnp.int32)
    return jnp.where(in_prev & (cn_r > 0.5), child, nid)


def _kernel_t(x_ref, nid_ref, ghw_ref, tabs_ref, loinv_ref, nid_out,
              hist_out, acc_ref, *, n_prev: int, n_nodes: int, F: int,
              W: int, tile: int, n_row_tiles: int, level_base: int,
              mxu_dtype):
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xt = x_ref[...]                                  # [F, tile] f32
    nid = nid_ref[0, :]
    if n_prev > 0:
        nid = _route_t(xt, nid, tabs_ref, n_prev, level_base, tile, F)
    nid_out[0, :] = nid

    lid = nid - level_base
    in_lvl = (lid >= 0) & (lid < n_nodes)
    # fold the in-level mask into the index (-1 matches no iota row), so
    # ONE fused compare+select builds the masked one-hot directly in the
    # MXU dtype (the old path went compare → f32 astype → mask multiply →
    # bf16 astype: three extra [N, tile] passes; an explicit `& in_lvl`
    # broadcast trips a Mosaic i1 relayout error)
    lidm = jnp.where(in_lvl, lid, -1)
    onh_m = (jax.lax.broadcasted_iota(jnp.int32, (n_nodes, tile), 0)
             == lidm[None, :]).astype(mxu_dtype)
    if n_nodes == 1:
        # root level: every row shares ONE range row — recombine the
        # [6F, 1] table first and broadcast, skipping the per-row lookup
        # matmul and the [2F, tile] three-term recombine entirely
        lr1 = loinv_ref[...].astype(jnp.float32)           # [6F, 1]
        lr = _unsplit3(lr1[:2 * F], lr1[2 * F:4 * F], lr1[4 * F:])
        lo_r = jnp.broadcast_to(lr[:F], (F, tile))
        inv_r = jnp.broadcast_to(lr[F:], (F, tile))
    else:
        onh_b = onh_m.astype(jnp.bfloat16) if mxu_dtype != jnp.bfloat16 \
            else onh_m
        # per-row ranges: [6F, N] @ [N, tile] -> [6F, tile] (exact 3-term
        # bf16 split, see _split3_bf16)
        lr3 = jax.lax.dot_general(loinv_ref[...], onh_b,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        lr = _unsplit3(lr3[:2 * F], lr3[2 * F:4 * F], lr3[4 * F:])
        lo_r = lr[:F]
        inv_r = lr[F:]
    bin_f = jnp.floor(jnp.clip((xt - lo_r) * inv_r, 0.0, float(W - 2)))
    bin_v = jnp.where(jnp.isnan(xt), float(W - 1), bin_f)  # [F, tile]
    # bin broadcast to [F*W, tile]: in the transposed layout this is a
    # SUBLANE repeat (each feature row replicated W times) — a cheap
    # Mosaic relayout, vs the row-major layout where the same broadcast
    # needed a selector MATMUL writing a [tile, F*W] f32 intermediate
    # (the repeat alone was worth ~1.5x end-to-end on the bench)
    b_all = jnp.repeat(bin_v, W, axis=0)
    brow = jax.lax.broadcasted_iota(jnp.int32, (F * W, tile), 0)
    oh_t = ((brow % W).astype(jnp.float32) == b_all).astype(mxu_dtype)
    ghw = ghw_ref[...]
    ghw_m = ghw.astype(mxu_dtype)
    left = jnp.concatenate(
        [onh_m * ghw_m[k, :][None, :] for k in range(3)], axis=0)  # [3N, tile]
    # contraction over LANES on both sides: [3N, tile] x [FW, tile]^T
    acc_ref[...] += jax.lax.dot_general(
        left, oh_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if mxu_dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))       # [3N, FW]

    @pl.when(r == n_row_tiles - 1)
    def _flush():
        hist_out[...] = acc_ref[...]


def adaptive_level_tpu_t(xt, nid, ghw, tables, lo, inv, n_prev: int,
                         n_nodes: int, level_base: int, W: int,
                         tile: int = TILE, interpret: bool = False,
                         mxu_dtype=jnp.bfloat16):
    """Transposed-layout level: xt is [F, rows] (rows % tile == 0)."""
    F, rows = xt.shape
    assert rows % tile == 0, (rows, tile)
    n_row_tiles = rows // tile
    tabs = _pack_tables(tables)
    np1 = tabs.shape[1]
    # loinv stored [6F, N]: 3-term split of [2F, N]
    loinv = _split3_bf16(jnp.concatenate([lo, inv], axis=1).T, axis=0)
    kern = functools.partial(_kernel_t, n_prev=n_prev, n_nodes=n_nodes, F=F,
                             W=W, tile=tile, n_row_tiles=n_row_tiles,
                             level_base=level_base, mxu_dtype=mxu_dtype)
    nid2, hist = pl.pallas_call(
        kern,
        grid=(n_row_tiles,),
        in_specs=[
            pl.BlockSpec((F, tile), lambda r: (0, r)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((3, tile), lambda r: (0, r)),
            pl.BlockSpec((12, np1), lambda r: (0, 0)),
            pl.BlockSpec((6 * F, n_nodes), lambda r: (0, 0)),
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
        cost_estimate=pl.CostEstimate(
            flops=2 * 3 * n_nodes * F * W * rows,
            bytes_accessed=rows * F * 4 + rows * 16, transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xt, nid[None, :], ghw, tabs, loinv)
    return nid2[0], hist.reshape(3, n_nodes, F, W)


def _route_kernel_t(x_ref, nid_ref, tabs_ref, nid_out, *, n_prev: int,
                    level_base: int, F: int, tile: int):
    xt = x_ref[...]
    nid = nid_ref[0, :]
    nid = _route_t(xt, nid, tabs_ref, n_prev, level_base, tile, F)
    nid_out[0, :] = nid


def route_only_tpu_t(xt, nid, tables, n_prev: int, level_base: int,
                     tile: int = TILE, interpret: bool = False):
    F, rows = xt.shape
    assert rows % tile == 0
    tabs = _pack_tables(tables)
    np1 = tabs.shape[1]
    kern = functools.partial(_route_kernel_t, n_prev=n_prev,
                             level_base=level_base, F=F, tile=tile)
    nid2 = pl.pallas_call(
        kern,
        grid=(rows // tile,),
        in_specs=[
            pl.BlockSpec((F, tile), lambda r: (0, r)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((12, np1), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, rows), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(xt, nid[None, :], tabs)
    return nid2[0]


def _route_kernel(x_ref, nid_ref, tabs_ref, nid_out, *, n_prev: int,
                  level_base: int, F: int, tile: int):
    """Route one level, nothing else — the deepest-level pass when leaf
    values come from the last histogram's selected splits (~3x cheaper
    than a full level since the whole [tile, F*W] one-hot stage is
    skipped)."""
    x = x_ref[...]
    nid = nid_ref[0, :]
    nid = _route(x, nid, tabs_ref, n_prev, level_base, tile, F)
    nid_out[0, :] = nid


def route_only_tpu(x, nid, tables, n_prev: int, level_base: int,
                   tile: int = TILE, interpret: bool = False):
    rows, F = x.shape
    assert rows % tile == 0
    tabs = _pack_tables(tables)
    np1 = tabs.shape[1]
    kern = functools.partial(_route_kernel, n_prev=n_prev,
                             level_base=level_base, F=F, tile=tile)
    nid2 = pl.pallas_call(
        kern,
        grid=(rows // tile,),
        in_specs=[
            pl.BlockSpec((tile, F), lambda r: (r, 0)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((12, np1), lambda r: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, rows), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(x, nid[None, :], tabs)
    return nid2[0]


def route_only_xla(x, nid, tables, n_prev: int, level_base: int):
    feat, thr, nal, can = tables
    prev_base = level_base - n_prev
    lid_p = jnp.clip(nid - prev_base, 0, n_prev - 1)
    in_prev = (nid >= prev_base) & (nid < prev_base + n_prev)
    f_r = feat[lid_p].astype(jnp.int32)
    xsel = jnp.take_along_axis(x, f_r[:, None], axis=1)[:, 0]
    go_right = jnp.where(jnp.isnan(xsel), nal[lid_p] < 0.5,
                         xsel >= thr[lid_p])
    child = 2 * nid + 1 + go_right.astype(jnp.int32)
    return jnp.where(in_prev & (can[lid_p] > 0.5), child, nid)


def route_only(x, nid, tables, n_prev: int, level_base: int,
               method: str = "auto", xt=None):
    method = _resolve_method(method)
    if method == "pallas":
        if xt is not None:
            rows = xt.shape[1]
            pad = (-rows) % TILE
            if pad:
                xt = jnp.pad(xt, ((0, 0), (0, pad)),
                             constant_values=jnp.nan)
                nid = jnp.pad(nid, (0, pad))
            return route_only_tpu_t(xt, nid, tables, n_prev, level_base,
                                    interpret=pallas_interpret())[:rows]
        rows = x.shape[0]
        pad = (-rows) % TILE
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)), constant_values=jnp.nan)
            nid = jnp.pad(nid, (0, pad))
        return route_only_tpu(x, nid, tables, n_prev, level_base,
                              interpret=pallas_interpret())[:rows]
    return route_only_xla(x, nid, tables, n_prev, level_base)


# ---------------- PACKED BINNED-CODE kernels ---------------------------
#
# The global-sketch path bins features ONCE per train (ops/binning.py)
# into small integer codes, so the level kernel no longer needs the
# per-node lo/inv range machinery at all: the bin IS the code. Streaming
# int8/int16 codes instead of f32 features cuts the hot loop's HBM
# traffic 4x/2x — the lever the roofline data says matters in the
# memory-bound regime — and the whole [6F, N] range-table stage (one
# bf16 LUT matmul + 3-term recombine per level) drops out of the
# kernel body. Conventions:
#   - codes ride TRANSPOSED [F, rows] like the f32 kernels (rows in
#     lanes; int8 tiles 32x128, so F=28 pads to 32 sublanes either
#     way); values in [0, W-2], NA = the RESERVED LAST LANE W-1 (pad
#     rows are all-NA with zero ghw mass);
#   - split tables carry the split BIN as an integer-valued f32
#     (left <=> code < bin), packed through the same exact 3-term bf16
#     split as the raw-threshold tables (_pack_tables): integers
#     reconstruct exactly, so in-kernel routing is bit-identical to
#     the scatter reference and to predict_binned's host walk;
#   - the histogram contraction is the f32 kernel's lane contraction
#     ([rows, tile] x [lanes, tile]^T), so the bf16 / f32-HIGHEST choice
#     (histogram_precision) composes unchanged; its ROWS are not the
#     level's nodes, see below.
#
# A level below the root accumulates ONE child of each previous-level
# node, the BUILT child: 3*n_prev accumulator rows where the level has
# 2*n_prev nodes. The left operand is the routing's own parent one-hot
# masked by "this row stepped into its parent's built child", times
# (g, h, w). Which child is built rides in the ``can`` table entry
# (NO_SPLIT, BUILD_LEFT, BUILD_RIGHT below; :func:`can_entry` writes it and
# ``can_splits`` / ``can_builds_right`` / ``can_other_child`` read it).
# The caller takes the sibling as parent - built where the histograms are
# bf16 sums, and builds it by a second call where they are float32
# (models/tree.py:level_child_sums, sibling_level_hist).
#
# PRECISION of a level's histogram. A cell is the f32 sum of its rows'
# addends, each rounded to ``mxu_dtype`` first, so a built and a derived
# cell are sums of the same rounded addends a direct build would add.
# Summing n addends a_i in f32, in any order, errs by at most
# n * 2^-24 * sum|a_i| and, its roundings being independent, by about
# sqrt(n) * 2^-24 * sum|a_i|: E(cell), a BUILT cell's error over its own
# rows. A DERIVED cell is its last built ancestor A less the built
# siblings b_1..b_k on the way down (k derived levels running), so its
# ABSOLUTE error is
#     E(A) + E(b_1) + ... + E(b_k)  <=  3 * sqrt(n_A) * 2^-24 * sum_A|a|
# whatever its own size (the grower builds the child with the smaller w,
# so where mass follows w each b_i holds at most half its parent's and
# the sum is a geometric series under 2 E(A); each subtraction adds half
# an ulp of a result no larger than A's). Relative to its OWN sums that
# is up to 2^k times a direct build's: every derived level keeps at least
# half its parent's w, no more. The other choice has no bound at all: a
# 10-row child derived from a 10M-row parent would be noise
# (tests/test_packed_binned.py holds one and three derived levels to the
# bound above, and shows a 10-row child DERIVED from a 200k-row parent
# fail it).
# - bf16 addends (``histogram_precision`` bfloat16, 'auto' from 2^18 rows)
#   carry 2^-9 each, which is what the compared numbers of such a train
#   read (PERF.md section 6, PR 34: equal to the last digit with and
#   without derivation at 10M-40M rows): the sibling is DERIVED.
# - float32 histograms (``histogram_precision`` float32, 'auto' under 2^18
#   rows) promise a node's sums to the f32 rounding of its OWN rows, and a
#   one-row cell exactly its row (gain ties then break as a float64 search
#   breaks them). No difference from an ancestor's sums keeps that: a
#   20-row node at depth 9 of a 4,096-row frame read 10-100x a direct
#   build's error derived. There the sibling is BUILT, by the same kernel
#   called again with ``can_other_child``: the trees are the direct
#   formulation's bit for bit, at twice the calls of frames whose levels
#   cost microseconds.

NO_SPLIT, BUILD_LEFT, BUILD_RIGHT = 0.0, 1.0, 2.0


def can_entry(can, build_right):
    """The ``can`` table entry of a packed level: NO_SPLIT where ``can``
    is false (the node's rows stay), else which child the next level's
    kernel accumulates. Small integers, exact through the bf16 LUT."""
    return jnp.where(can, jnp.where(build_right, BUILD_RIGHT, BUILD_LEFT),
                     NO_SPLIT)


def can_splits(entry):
    return entry > NO_SPLIT + 0.5


def can_builds_right(entry):
    return entry > BUILD_LEFT + 0.5


def can_other_child(entry):
    """The entry that builds the sibling of the child ``entry`` builds."""
    return jnp.where(can_splits(entry), BUILD_LEFT + BUILD_RIGHT - entry,
                     NO_SPLIT)


def code_dtype(W: int):
    """Smallest kernel-legal integer dtype for codes in [0, W-1]:
    int8 holds W <= 128 (max code 127), int16 the 256-lane case."""
    return jnp.int8 if W <= 128 else jnp.int16


def _step_bt(cf, nid, tabs_ref, n_prev, level_base, tile, F, W,
             sets_ref=None):
    """Transposed binned routing: cf [F, tile] f32-valued CODES (NA =
    W-1). The split-bin compare ``code >= bin`` happens on exact
    integer-valued floats — no lo/inv rebinning anywhere. With
    ``sets_ref`` ([W, n_prev] bf16 0/1, the previous level's left sets
    over the local codes) a row goes left iff its code is in its node's
    set. Returns (the rows' node ids after the step, their parent one-hot
    [n_prev, tile] bf16, 1. where a row stepped into its parent's BUILT
    child else 0.)."""
    prev_base = level_base - n_prev
    lid_p = nid - prev_base
    onp = (jax.lax.broadcasted_iota(jnp.int32, (n_prev, tile), 0)
           == lid_p[None, :]).astype(jnp.bfloat16)
    t12 = tabs_ref[:, :n_prev]                        # [12, n_prev] bf16
    lut3 = jax.lax.dot_general(t12, onp, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)
    lut = _unsplit3(lut3[0:4], lut3[4:8], lut3[8:12])  # exact ints
    f_r, b_r, nl_r, cn_r = lut[0], lut[1], lut[2], lut[3]
    fi = jax.lax.broadcasted_iota(jnp.int32, (F, tile), 0)
    csel = jnp.sum(jnp.where(fi == f_r.astype(jnp.int32)[None, :], cf, 0.0),
                   axis=0)
    if sets_ref is None:
        gr_f = jnp.where(csel == float(W - 1), 1.0 - nl_r,
                         (csel >= b_r).astype(jnp.float32))
    else:
        # ROUTING BY SET (a frame with enum features; every feature of it,
        # a threshold being the set "bins below t"): the node's left set
        # as a column over the W local codes, by the node one-hot the
        # table lookup already built, then the row's own entry selected
        # on its code. ``cf`` holds GLOBAL lanes here, ``b_r`` the split
        # feature's lane offset; the NA code's entry is ``na_left``.
        col = jax.lax.dot_general(sets_ref[:, :n_prev], onp,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        ci = (csel - b_r).astype(jnp.int32)                  # local code
        wi = jax.lax.broadcasted_iota(jnp.int32, col.shape, 0)
        gr_f = 1.0 - jnp.sum(jnp.where(wi == ci[None, :], col, 0.0), axis=0)
    step = (lid_p >= 0) & (lid_p < n_prev) & can_splits(cn_r)
    child = 2 * nid + 1 + gr_f.astype(jnp.int32)
    # the entry less BUILD_LEFT is the built child's direction (0 left,
    # 1 right), and -1, which no direction equals, where no row steps
    built = (gr_f == cn_r - BUILD_LEFT).astype(jnp.float32)
    return jnp.where(step, child, nid), onp, built


def _route_bt(cf, nid, tabs_ref, n_prev, level_base, tile, F, W,
              sets_ref=None):
    """The rows' node ids after :func:`_step_bt`."""
    return _step_bt(cf, nid, tabs_ref, n_prev, level_base, tile, F, W,
                    sets_ref)[0]


def _built_rows(cf, nid, ghw, tabs_ref, n_prev, level_base, tile, F, W,
                mxu_dtype, sets_ref=None):
    """What a packed level accumulates, from the routing: (the rows' new
    node ids, the histogram's left operand [3 * max(n_prev, 1), tile]).
    Below the root that operand is the parent one-hot times (g, h, w)
    masked to the rows that stepped into their parent's built child; at
    the root, the rows of node 0."""
    if n_prev == 0:
        rows = (jax.lax.broadcasted_iota(jnp.int32, (1, tile), 0)
                == (nid - level_base)[None, :]).astype(mxu_dtype)
    else:
        nid, onp, built = _step_bt(cf, nid, tabs_ref, n_prev, level_base,
                                   tile, F, W, sets_ref)
        ghw = ghw * built[None, :]
        rows = onp.astype(mxu_dtype)
    ghw_m = ghw.astype(mxu_dtype)
    return nid, jnp.concatenate(
        [rows * ghw_m[k, :][None, :] for k in range(3)], axis=0)


def level_acc_rows(n_prev: int) -> int:
    """Accumulator rows of a packed level whose previous level has
    ``n_prev`` nodes (0: the root): (g, h, w) of one child a parent."""
    return 3 * max(n_prev, 1)


def lane_offsets(widths) -> tuple:
    """Where each feature's lanes start on the global lane axis."""
    off, out = 0, []
    for w in widths:
        out.append(off)
        off += int(w)
    return tuple(out)


def _lane_onehot(cf, widths, W: int, tile: int, dtype):
    """[lanes, tile] one-hot of every feature's bin. A feature's lanes lie
    at its offset on one global lane axis. With equal widths (``widths``
    empty: no enum feature, every feature ``W`` lanes) the codes are local
    and a sublane repeat against ``lane % W`` builds it; else the codes
    are global lanes and each feature's row is broadcast over its own
    lanes."""
    F = cf.shape[0]
    if not widths:
        b_all = jnp.repeat(cf, W, axis=0)                    # [F*W, tile]
        brow = jax.lax.broadcasted_iota(jnp.int32, (F * W, tile), 0)
        return ((brow % W).astype(jnp.float32) == b_all).astype(dtype)
    b_all = jnp.concatenate(
        [jnp.broadcast_to(cf[f:f + 1, :], (w, tile))
         for f, w in enumerate(widths)], axis=0)             # [L, tile]
    brow = jax.lax.broadcasted_iota(jnp.int32, b_all.shape, 0)
    return (brow.astype(jnp.float32) == b_all).astype(dtype)


def _kernel_bt(c_ref, nid_ref, ghw_ref, tabs_ref, *rest, n_prev: int,
               F: int, W: int, tile: int, n_row_tiles: int,
               level_base: int, mxu_dtype, widths: tuple = ()):
    sets_ref = rest[0] if widths else None
    nid_out, hist_out, acc_ref = rest[-3:]
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8/int16 -> f32 once per tile in VMEM (int->float is legal in
    # Mosaic through an i32 widening)
    cf = c_ref[...].astype(jnp.int32).astype(jnp.float32)    # [F, tile]
    nid, left = _built_rows(cf, nid_ref[0, :], ghw_ref[...], tabs_ref,
                            n_prev, level_base, tile, F, W, mxu_dtype,
                            sets_ref)
    nid_out[0, :] = nid
    # the code IS the bin: the one-hot builds straight off the codes —
    # no range lookup, no floor/clip stage
    oh_t = _lane_onehot(cf, widths, W, tile, mxu_dtype)
    acc_ref[...] += jax.lax.dot_general(
        left, oh_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if mxu_dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))       # [acc rows, lanes]

    @pl.when(r == n_row_tiles - 1)
    def _flush():
        hist_out[...] = acc_ref[...]


def binned_level_tpu_t(ct, nid, ghw, tables, n_prev: int, level_base: int,
                       W: int, tile: int = TILE, interpret: bool = False,
                       mxu_dtype=jnp.bfloat16, widths: tuple = ()):
    """Packed binned level: ct is [F, rows] int8/int16 codes (rows %
    tile == 0; NA/pad = W-1), ``tables`` the previous level's (feat, bin,
    na_left, can), ``can`` saying which child of a node is built (the
    conventions above). Returns (nid' [rows] i32, hist [3, max(n_prev, 1),
    F, W] f32: row p the BUILT child of previous-level node p, zeros where
    p does not split; at the root the root's own. The caller psums it
    across shards and takes the siblings by subtraction).

    ``widths`` (per-feature lane counts; a frame with enum features):
    ct holds GLOBAL lanes (a feature's code plus its lane offset),
    ``tables`` carries the lane offset of a node's feature where the
    split bin rides and a fifth entry, the nodes' left sets [n, W] over
    the local codes; the hist comes back flat, [3, max(n_prev, 1),
    lanes]."""
    F, rows = ct.shape
    assert rows % tile == 0, (rows, tile)
    n_row_tiles = rows // tile
    tabs = _pack_tables(tables[:4])
    np1 = tabs.shape[1]
    lanes = sum(widths) if widths else F * W
    n_acc = level_acc_rows(n_prev)
    kern = functools.partial(_kernel_bt, n_prev=n_prev, F=F, W=W, tile=tile,
                             n_row_tiles=n_row_tiles, level_base=level_base,
                             mxu_dtype=mxu_dtype, widths=widths)
    itemsize = jnp.dtype(ct.dtype).itemsize
    operands = [ct, nid[None, :], ghw, tabs]
    in_specs = [
        pl.BlockSpec((F, tile), lambda r: (0, r)),
        pl.BlockSpec((1, tile), lambda r: (0, r)),
        pl.BlockSpec((3, tile), lambda r: (0, r)),
        pl.BlockSpec((12, np1), lambda r: (0, 0)),
    ]
    if widths:
        operands.append(tables[4].T.astype(jnp.bfloat16))     # [W, np1]
        in_specs.append(pl.BlockSpec((W, np1), lambda r: (0, 0)))
    nid2, hist = pl.pallas_call(
        kern,
        grid=(n_row_tiles,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((n_acc, lanes), lambda r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows), jnp.int32),
            jax.ShapeDtypeStruct((n_acc, lanes), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_acc, lanes), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * n_acc * lanes * rows,
            bytes_accessed=rows * F * itemsize + rows * 16,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="binned_level_tpu_t",
    )(*operands)
    if widths:
        return nid2[0], hist.reshape(3, n_acc // 3, lanes)
    return nid2[0], hist.reshape(3, n_acc // 3, F, W)


def _kernel_bt_stripe(c_ref, nid_ref, ghw_ref, tabs_ref, nid_out, hist_out,
                      acc_ref, *, n_prev: int, F2: int, W: int, tile: int,
                      n_row_tiles: int, level_base: int, mxu_dtype):
    """STRIPE-PACKED binned level (W=16): two features share one 32-lane
    stripe of the one-hot — feature 2p's bins in sub-lanes 0..W-1,
    feature 2p+1's in W..2W-1 (codes offset by +W in-register). The
    resulting selector matrix is ELEMENT-IDENTICAL to _kernel_bt's
    (row q = W·f + b holds the same {0,1} for every lane), so the MXU
    contraction produces bit-identical histograms; what changes is the
    lowering — the iota compare runs modulo 2W = 32 aligned to the int8
    (32, 128) native tile, so each compare stripe is a full sublane
    group instead of two half-filled W=16 groups. Capability-gated
    (stripe_supported): Mosaic builds that lack the aligned i8 select
    fall back to _kernel_bt."""
    r = pl.program_id(0)

    @pl.when(r == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    cf = c_ref[...].astype(jnp.int32).astype(jnp.float32)    # [2*F2, tile]
    nid, left = _built_rows(cf, nid_ref[0, :], ghw_ref[...], tabs_ref,
                            n_prev, level_base, tile, 2 * F2, W, mxu_dtype)
    nid_out[0, :] = nid
    # stripe offset: the pair's odd feature lives in the upper W lanes —
    # one add on the [2*F2, tile] codes, then a single repeat builds
    # both features' lanes of every stripe at once
    frow = jax.lax.broadcasted_iota(jnp.int32, (2 * F2, tile), 0)
    cs = cf + ((frow % 2) * W).astype(jnp.float32)
    b_all = jnp.repeat(cs, W, axis=0)                        # [F2*2W, tile]
    brow = jax.lax.broadcasted_iota(jnp.int32, (2 * F2 * W, tile), 0)
    oh_t = ((brow % (2 * W)).astype(jnp.float32) == b_all).astype(mxu_dtype)
    acc_ref[...] += jax.lax.dot_general(
        left, oh_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST if mxu_dtype == jnp.float32
                   else jax.lax.Precision.DEFAULT))    # [acc rows, F2*2W]

    @pl.when(r == n_row_tiles - 1)
    def _flush():
        hist_out[...] = acc_ref[...]


def binned_level_tpu_stripe(ct, nid, ghw, tables, n_prev: int,
                            level_base: int, W: int, tile: int = TILE,
                            interpret: bool = False,
                            mxu_dtype=jnp.bfloat16, F: int = None):
    """Stripe-packed binned level, :func:`binned_level_tpu_t`'s contract:
    ct is the stripe operand [2*F2, rows] (ops/binning.stripe_pair_codes —
    an odd F pads one all-NA feature row). ``F`` is the REAL feature
    count; the returned hist is sliced back to [3, max(n_prev, 1), F,
    W]."""
    F_op, rows = ct.shape
    assert F_op % 2 == 0, F_op
    F2 = F_op // 2
    F = F_op if F is None else F
    assert rows % tile == 0, (rows, tile)
    n_row_tiles = rows // tile
    tabs = _pack_tables(tables)
    np1 = tabs.shape[1]
    n_acc = level_acc_rows(n_prev)
    kern = functools.partial(_kernel_bt_stripe, n_prev=n_prev, F2=F2, W=W,
                             tile=tile, n_row_tiles=n_row_tiles,
                             level_base=level_base, mxu_dtype=mxu_dtype)
    itemsize = jnp.dtype(ct.dtype).itemsize
    nid2, hist = pl.pallas_call(
        kern,
        grid=(n_row_tiles,),
        in_specs=[
            pl.BlockSpec((2 * F2, tile), lambda r: (0, r)),
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((3, tile), lambda r: (0, r)),
            pl.BlockSpec((12, np1), lambda r: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, tile), lambda r: (0, r)),
            pl.BlockSpec((n_acc, 2 * F2 * W), lambda r: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, rows), jnp.int32),
            jax.ShapeDtypeStruct((n_acc, 2 * F2 * W), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((n_acc, 2 * F2 * W), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * n_acc * 2 * F2 * W * rows,
            bytes_accessed=rows * 2 * F2 * itemsize + rows * 16,
            transcendentals=0),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="binned_level_tpu_stripe",
    )(ct, nid[None, :], ghw, tabs)
    return nid2[0], hist.reshape(3, n_acc // 3, 2 * F2, W)[:, :, :F, :]


@functools.lru_cache(maxsize=1)
def _stripe_probe() -> bool:
    """Hardware capability probe for the stripe kernel, run ONCE: the
    interpreter always supports it; on a real TPU a tiny stripe kernel
    is compiled and executed, and any Mosaic lowering failure (builds
    lacking the aligned i8 select the stripe compare needs) demotes to
    the _kernel_bt layout."""
    if pallas_interpret():
        return True
    if jax.default_backend() != "tpu":
        return False
    try:
        # binned_level asks from inside the chunk step's trace, where
        # these ops would only be staged and nothing would reach Mosaic
        with jax.core.eval_context():
            ct = jnp.full((2, TILE), 15, jnp.int8)
            nid = jnp.zeros(TILE, jnp.int32)
            ghw = jnp.zeros((3, TILE), jnp.float32)
            z1 = jnp.zeros(1, jnp.float32)
            nid2, hist = binned_level_tpu_stripe(
                ct, nid, ghw, (z1, z1, z1, z1), 0, 0, 16)
            jax.block_until_ready((nid2, hist))  # h2o3-lint: allow[transfer-seam] once-per-process capability probe: the block IS the probe (Mosaic lowering failures surface at execute)
        return True
    except Exception as e:  # noqa: BLE001 — any Mosaic refusal demotes
        from h2o3_tpu.log import warn
        warn("binned_level_tpu_stripe failed its Mosaic probe (%s: %s) — "
             "W=16 levels run binned_level_tpu_t instead",
             type(e).__name__, e)
        return False


def stripe_supported() -> bool:
    """Whether binned W=16 levels use the stripe-packed one-hot kernel.
    H2O3_STRIPE=0/1 overrides the probe (tests, A/B ablation)."""
    env = _os.environ.get("H2O3_STRIPE", "")
    if env == "0":
        return False
    if env == "1":
        return True
    return _stripe_probe()


def _step_xla(ci, nid, tables, n_prev: int, level_base: int, W: int):
    """(the rows' node ids after the step, their previous-level local ids,
    which rows stepped into their parent's BUILT child) through the
    previous level's tables, by per-row lookups: the scatter references'
    routing. Five tables: routing by set on the local codes (``tables[4]``
    [n, W])."""
    feat, sbin, nal, can = tables[:4]
    prev_base = level_base - n_prev
    lid_p = jnp.clip(nid - prev_base, 0, n_prev - 1)
    in_prev = (nid >= prev_base) & (nid < prev_base + n_prev)
    f_r = feat[lid_p].astype(jnp.int32)
    csel = jnp.take_along_axis(ci, f_r[:, None], axis=1)[:, 0]
    if len(tables) > 4:
        go_right = tables[4][lid_p, csel] < 0.5
    else:
        go_right = jnp.where(csel == W - 1, nal[lid_p] < 0.5,
                             csel.astype(jnp.float32) >= sbin[lid_p])
    step = in_prev & can_splits(can[lid_p])
    child = 2 * nid + 1 + go_right.astype(jnp.int32)
    return (jnp.where(step, child, nid), lid_p,
            step & (go_right == can_builds_right(can[lid_p])))


def binned_level_xla(codes, nid, ghw, tables, n_prev: int, level_base: int,
                     W: int, widths: tuple = ()):
    """Pure-XLA reference/CPU path for the binned level
    (:func:`binned_level_tpu_t`'s contract: the BUILT child of every
    previous-level node; scatter-add histogram, [rows, F] int codes, NA =
    W-1). Rows add in row order, like ops/histogram._hist_scatter3. With
    ``widths`` (the codes stay LOCAL here) the hist is flat,
    [3, max(n_prev, 1), lanes]."""
    rows, F = codes.shape
    ci = codes.astype(jnp.int32)
    n_acc = max(n_prev, 1)
    if n_prev > 0:
        nid, seg, built = _step_xla(ci, nid, tables, n_prev, level_base, W)
    else:
        seg = jnp.zeros_like(nid)
        built = nid == level_base
    if widths:
        lanes = sum(widths)
        flat = seg[:, None] * lanes + jnp.asarray(lane_offsets(widths)
                                                  )[None, :] + ci
    else:
        lanes = F * W
        flat = (seg[:, None] * F + jnp.arange(F)[None, :]) * W + ci
    vw = jnp.where(built, 1.0, 0.0)
    out = jnp.zeros((n_acc * lanes, 3), jnp.float32)
    out = out.at[flat.reshape(-1), :].add(
        (ghw.T * vw[:, None])[:, None, :].repeat(F, axis=1).reshape(-1, 3))
    hist = out.reshape((n_acc, lanes, 3) if widths else (n_acc, F, W, 3))
    return nid, jnp.moveaxis(hist, -1, 0)


def _route_kernel_bt(c_ref, nid_ref, tabs_ref, *rest, n_prev: int,
                     level_base: int, F: int, W: int, tile: int):
    sets_ref = rest[0] if len(rest) > 1 else None
    cf = c_ref[...].astype(jnp.int32).astype(jnp.float32)
    nid = nid_ref[0, :]
    nid = _route_bt(cf, nid, tabs_ref, n_prev, level_base, tile, F, W,
                    sets_ref)
    rest[-1][0, :] = nid


def binned_route_only_tpu_t(ct, nid, tables, n_prev: int, level_base: int,
                            W: int, tile: int = TILE,
                            interpret: bool = False):
    """The leaves' routing. A fifth table (the left sets, see
    :func:`binned_level_tpu_t`) selects routing by set on global lanes."""
    F, rows = ct.shape
    assert rows % tile == 0
    tabs = _pack_tables(tables[:4])
    np1 = tabs.shape[1]
    kern = functools.partial(_route_kernel_bt, n_prev=n_prev,
                             level_base=level_base, F=F, W=W, tile=tile)
    operands = [ct, nid[None, :], tabs]
    in_specs = [
        pl.BlockSpec((F, tile), lambda r: (0, r)),
        pl.BlockSpec((1, tile), lambda r: (0, r)),
        pl.BlockSpec((12, np1), lambda r: (0, 0)),
    ]
    if len(tables) > 4:
        operands.append(tables[4].T.astype(jnp.bfloat16))     # [W, np1]
        in_specs.append(pl.BlockSpec((W, np1), lambda r: (0, 0)))
    nid2 = pl.pallas_call(
        kern,
        grid=(rows // tile,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, tile), lambda r: (0, r)),
        out_shape=jax.ShapeDtypeStruct((1, rows), jnp.int32),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="binned_route_only_tpu_t",
    )(*operands)
    return nid2[0]


def binned_route_only_xla(codes, nid, tables, n_prev: int, level_base: int,
                          W: int):
    return _step_xla(codes.astype(jnp.int32), nid, tables, n_prev,
                     level_base, W)[0]


def _binned_pad(ct, nid, ghw, W):
    """Pad the kernel operands to the tile width: pad rows are all-NA
    (code W-1) with nid 0 — at the root they one-hot into node 0 but
    carry zero ghw mass, at deeper levels they fall outside the level
    window, exactly like the f32 kernels' NaN pad rows."""
    padc = (-ct.shape[1]) % TILE
    if padc:
        ct = jnp.pad(ct, ((0, 0), (0, padc)), constant_values=W - 1)
    pad = ct.shape[1] - nid.shape[0]
    if pad:
        nid = jnp.pad(nid, (0, pad))
        if ghw is not None:
            ghw = jnp.pad(ghw, ((0, 0), (0, pad)))
    return ct, nid, ghw


def binned_level_kernel(W: int, F: int, method: str = "auto",
                        widths: tuple = ()) -> str:
    """Name of the body :func:`binned_level` dispatches a bf16/f32 level
    to — the one rule both the dispatch and its reporters
    (chip_smoke.py) read, so what is printed is what ran."""
    if _resolve_method(method) != "pallas":
        return "binned_level_xla"
    if W == 16 and F >= 2 and not widths and stripe_supported():
        return "binned_level_tpu_stripe"
    return "binned_level_tpu_t"


def binned_level_plan(W: int, F: int, method: str = "auto",
                      widths: tuple = ()) -> dict:
    """What the levels of a dense packed train run, for its records
    (``model.output["packed_codes"]``, the ``train.loop`` span): the
    kernel as the device trace names it, its blocking (every body
    takes all F features in one block, ``TILE`` rows a grid step) and
    its lanes: ``lanes`` histogram lanes a level, laid out
    ``lane_layout`` "uniform" (F x W) or "ragged" (a feature's own
    width at its offset)."""
    body = binned_level_kernel(W, F, method, widths)
    return {"kernel": body, "feature_block": F,
            "row_tile": 0 if body == "binned_level_xla" else TILE,
            "lanes": sum(widths) if widths else F * W,
            "lane_layout": "ragged" if widths else "uniform"}


def binned_level(codes_rm, nid, ghw, tables, n_prev: int, level_base: int,
                 W: int, method: str = "auto", mxu_dtype=jnp.bfloat16,
                 ct=None, widths: tuple = ()):
    """Dispatch the packed binned level (the contract:
    :func:`binned_level_tpu_t`): the scatter reference off the TPU (or
    where ``method`` says so), the stripe kernel at W == 16 where its
    probe passed, else ``binned_level_tpu_t``. ``ct`` is the
    pre-transposed [F, rows_p] code matrix (built once per train by
    ops/binning.pack_codes); without it the pallas path transposes on
    the fly (streamed chunks). ``widths``: the lane layout of a frame
    with enum features; ``ct`` is then given, on global lanes."""
    body = binned_level_kernel(
        W, codes_rm.shape[1] if ct is None else ct.shape[0], method,
        widths)
    if body == "binned_level_xla":
        return binned_level_xla(codes_rm, nid, ghw, tables, n_prev,
                                level_base, W, widths)
    if ct is None:
        ct = codes_rm.T
    rows = nid.shape[0]
    ct, nid, ghw = _binned_pad(ct, nid, ghw, W)
    if body == "binned_level_tpu_stripe":
        from h2o3_tpu.ops.binning import stripe_pair_codes
        nid2, hist = binned_level_tpu_stripe(
            stripe_pair_codes(ct, W), nid, ghw, tables, n_prev, level_base,
            W, mxu_dtype=mxu_dtype, interpret=pallas_interpret(),
            F=ct.shape[0])
    else:
        nid2, hist = binned_level_tpu_t(
            ct, nid, ghw, tables, n_prev, level_base, W,
            mxu_dtype=mxu_dtype, interpret=pallas_interpret(),
            widths=widths)
    return nid2[:rows], hist


def binned_route_only(codes_rm, nid, tables, n_prev: int, level_base: int,
                      W: int, method: str = "auto", ct=None):
    method = _resolve_method(method)
    if method == "pallas":
        if ct is None:
            ct = codes_rm.T
        rows = nid.shape[0]
        ct, nid, _ = _binned_pad(ct, nid, None, W)
        return binned_route_only_tpu_t(ct, nid, tables, n_prev, level_base,
                                       W, interpret=pallas_interpret()
                                       )[:rows]
    return binned_route_only_xla(codes_rm, nid, tables, n_prev, level_base,
                                 W)
