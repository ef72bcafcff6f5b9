"""Global quantile binning — feature values → small int bin codes.

Reference: the tree algos bin features per-node with DHistogram
(hex/tree/DHistogram.java:48; QuantilesGlobal/UniformAdaptive histogram
types in GBM), and the vendored XGBoost's ``tree_method=hist`` builds a
global quantile sketch once. The TPU design follows the global-sketch
shape: one pass computes per-feature quantile edges, a second digitises
every value into a uint8/int16 code. All later tree work touches only the
code matrix — int codes stream through HBM at 1-2 bytes/value and feed the
MXU one-hot histogram kernel (SURVEY.md §7.3).

Layout: codes[rows, F] with values in [0, n_bins_f); the NA bin is a
dedicated last index ``n_bins`` shared across features (uniform shape for
XLA). Split "bin t" means: left ⇔ code < t ⇔ raw < edges[t-1].
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class PackedCodes(NamedTuple):
    """Kernel-ready PACKED bin codes — the representation the training
    hot path computes on (ops/hist_adaptive binned kernels). ``rm``
    [rows, F] int8/int16 with the NA code remapped from ``n_bins`` to
    the kernel's RESERVED LAST LANE ``W-1`` (predict_binned walks it
    with na_bin=W-1); ``t`` [F, rows_p] same dtype, transposed and
    tile-padded PER SHARD (pad value W-1 = all-NA rows) — the pallas
    hot-loop operand, built once per train so the 1-2 byte/value codes
    are what streams through HBM every level. ``t`` is None off-TPU
    (the scatter reference reads ``rm``)."""
    rm: jax.Array
    t: Optional[jax.Array]
    W: int
    # a frame with enum features (:func:`lane_widths`): each feature's
    # lane count, its NA code its own last lane; ``rm`` keeps the local
    # codes, ``t`` holds GLOBAL lanes (code + the feature's lane offset),
    # ``W`` is the widest feature's count
    widths: tuple = ()

    @property
    def na_bin(self):
        """The NA code: one number, or one a feature under ``widths``."""
        if self.widths:
            return tuple(w - 1 for w in self.widths)
        return self.W - 1

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.rm.dtype).itemsize


class CodesView(NamedTuple):
    """Bin codes in both layouts. ``rm`` [rows, F] (compact, for routing/
    predict gathers); ``t`` [Fp, rows_p] int32 (transposed + padded, the
    pallas histogram kernel operand — transposing once here instead of per
    level saves ~40ms/level at 1M rows). ``t`` may be None off-TPU."""
    rm: jax.Array
    t: Optional[jax.Array]

    @property
    def shape(self):
        return self.rm.shape

    @property
    def dtype(self):
        return self.rm.dtype


@dataclass
class BinnedMatrix:
    codes: CodesView           # NA bin = n_bins
    n_bins: int                # bins per feature excluding the NA bin
    edges: List[np.ndarray]    # per-feature raw-value split edges (len <= n_bins-1)
    names: List[str]
    is_categorical: List[bool]
    nrow: int
    # where the edges were made (bin_matrix_device): "mesh" (per-shard
    # statistics reduced over the data axis), "device" (one device's
    # extremes and, for the columns that need ranks, its sort) or "host"
    # (a host copy of the matrix), and how many columns the device sorted
    sketch: str = "host"
    ranked_features: int = 0

    @property
    def n_features(self) -> int:
        return self.codes.rm.shape[1]

    @property
    def na_bin(self) -> int:
        return self.n_bins


def quantile_edges(col: np.ndarray, nbins: int) -> np.ndarray:
    """Unique quantile cut points for one numeric feature (host-side; the
    sketch is O(sample) — full exact quantiles are fine at these scales)."""
    vals = col[np.isfinite(col)]
    if vals.size == 0:
        return np.empty(0, dtype=np.float32)
    qs = np.quantile(vals, np.linspace(0.0, 1.0, nbins + 1)[1:-1])
    return np.unique(qs.astype(np.float32))


def uniform_edges(col: np.ndarray, nbins: int) -> np.ndarray:
    """Equal-width cut points (histogram_type='uniform_adaptive' analog:
    the reference re-adapts ranges per tree level; a global uniform grid is
    the static-shape equivalent)."""
    vals = col[np.isfinite(col)]
    if vals.size == 0:
        return np.empty(0, dtype=np.float32)
    lo, hi = float(vals.min()), float(vals.max())
    if lo == hi:
        return np.empty(0, dtype=np.float32)
    return np.linspace(lo, hi, nbins + 1)[1:-1].astype(np.float32)


def _counted(X, row0, nrow):
    """[rows, F] bool: the values of a block of rows (its first is row
    ``row0`` of the matrix) that a sketch counts: finite, and not in a pad
    row (index >= nrow) - the host path's ``col[np.isfinite(col)]``."""
    return (((row0 + jnp.arange(X.shape[0])) < nrow)[:, None]
            & jnp.isfinite(X))


def _column_extremes(X, row0, nrow):
    """Per column the (count, min, max) of a block's counted values
    (:func:`_counted`); a column with none reads (0, +inf, -inf). The ONE
    statement of the rule: one device runs it whole
    (:func:`_device_extremes`), a mesh a shard with the three reduced over
    the ``data`` axis (:func:`_mesh_extremes`). One pass of reductions
    over ``X``; no row-sized array outlives it."""
    ok = _counted(X, row0, nrow)
    x = X.astype(jnp.float32)
    return (jnp.sum(ok, axis=0, dtype=jnp.int32),
            jnp.min(jnp.where(ok, x, jnp.inf), axis=0),
            jnp.max(jnp.where(ok, x, -jnp.inf), axis=0))


@jax.jit
def _device_extremes(X, nrow):
    """:func:`_column_extremes` of a whole matrix on one device."""
    return _column_extremes(X, 0, nrow)


@jax.jit
def _sort_finite(X, nrow):
    """Each column's counted values ascending, NaN after them: what
    :func:`_column_extremes` counts, in rank order."""
    return jnp.sort(jnp.where(_counted(X, 0, nrow), X.astype(jnp.float32),
                              jnp.nan), axis=0)


@jax.jit
def _gather_rank_pairs(Xs, lo_idx, hi_idx):
    """Pure gathers of the quantile neighbour ranks — the float64 lerp
    happens on host so the result is bit-identical to np.quantile."""
    a = jnp.take_along_axis(Xs, lo_idx, axis=0)
    b = jnp.take_along_axis(Xs, hi_idx, axis=0)
    return a, b


def _np_quantile_lerp(a: np.ndarray, b: np.ndarray, t: np.ndarray) -> np.ndarray:
    """numpy's _lerp on float32 neighbours with float64 t — replicated so
    device-sketch edges match ``np.quantile(vals, qs)`` bit-for-bit
    (verified by tests/test_train_perf.py parity tests)."""
    diff = np.subtract(b, a)                 # float32, like numpy's _lerp
    out = np.add(a, diff * t)                # promotes to float64
    hi = t >= 0.5
    if hi.any():
        out[hi] = (b - diff * (1.0 - t))[hi]
    return out


def bin_matrix_device(X, names: Sequence[str], is_cat: Sequence[bool],
                      nrow: int, nbins: int = 255, nbins_cats: int = 1024,
                      histogram_type: str = "quantiles_global",
                      with_t: bool = True, prof=None) -> BinnedMatrix:
    """Device-side global sketch: the same edges as :func:`bin_matrix`
    (bit-exact — parity-tested) WITHOUT a ``device_get`` of the full X.

    A trainer's ``prof`` (its ``log.Profile``) times the two halves as
    phases ``bin.sketch`` and ``bin.digitize`` (spans
    ``train.bin.sketch``, ``train.bin.digitize``). The sketch ends at the
    fetch of its stats, a sync already; the digitise ends at a fence on
    the codes, which costs one dispatch latency and tells the two device
    programs' times apart.

    One reduction pass gives every column's finite count, min and max
    (:func:`_column_extremes`), :func:`_rank_grids` says from them which
    columns' edges read ranks (quantile edges, an enum past
    ``nbins_cats``), and the device sorts those columns alone
    (:func:`_device_sketch_edges`): ``uniform_adaptive`` / ``uniform``
    numerics and identity-bin enums are never sorted. The host fetches
    only O(F) stats plus the 2·(nbins-1) quantile neighbour values of a
    ranked feature; the float64 lerp and the unique/truncate bookkeeping
    stay on host where they are exact and cheap. Digitisation then runs
    on device as usual. This is the "no host round-trips" rule applied to
    binning itself — the sketch half of XGBoost's ``tree_method=hist``.

    On a mesh with more than one data shard (the rule is by MESH, not by
    backend, so the CPU test mesh runs it) edges that need only a
    column's extremes come from per-shard statistics reduced over the
    ``data`` axis (:func:`_mesh_sketch_edges`: ``uniform_adaptive`` /
    ``uniform`` numeric columns, an enum's identity bins up to
    ``nbins_cats``): O(F) numbers are fetched, no row-sized array goes to
    the host or onto one chip, and the edges are bit-equal to the
    one-device path's (a min and a max do not depend on the order of
    reduction). QUANTILE edges on a multi-shard mesh still need ranks:
    XLA lowers the cross-shard column sort to an all-gather, so every
    chip would hold the FULL [padded, F] matrix and its sorted copy, and
    a frame sized for the mesh's aggregate HBM would OOM. On an
    accelerator mesh they therefore come from a host copy
    (``device_get`` + ``np.quantile``, identical edges; the span says
    ``where="host"`` and ``d2h_bytes`` the table's size); the CPU test
    mesh's virtual shards share one host RAM and keep the device sort. A
    sharded quantile sketch is the open lever (ROADMAP). The digitise
    always runs on the sharded device matrix.

    The sketch span's attrs ``where`` (``mesh`` / ``device`` / ``host``),
    ``d2h_bytes`` and ``ranked_features`` say where the edges were made,
    what the host fetched for them and how many columns the device sorted
    (0 on the mesh and through the host); ``BinnedMatrix.sketch`` and
    ``.ranked_features`` carry the first and the last."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards
    phase = (prof.phase if prof is not None
             else lambda name: contextlib.nullcontext())
    mesh = current_mesh()
    uniform = histogram_type in ("uniform_adaptive", "uniform")
    sharded = n_data_shards(mesh) > 1
    with phase("bin.sketch") as sp:
        found, where = (_mesh_sketch_edges(mesh, X, is_cat, nrow, nbins,
                                           nbins_cats, uniform)
                        if sharded else None), "mesh"
        ranked = 0          # columns sorted on the device for their ranks
        if found is None and sharded and jax.default_backend() != "cpu":
            X_host = np.asarray(telemetry.device_get(X, pipeline="train"),
                                np.float32)
            found, where = (*_edges_host(X_host, nrow, is_cat, nbins,
                                         nbins_cats, histogram_type),
                            X_host.nbytes), "host"
            del X_host
        elif found is None:
            *found, ranked = _device_sketch_edges(
                X, is_cat, nrow, nbins, nbins_cats, uniform)
            where = "device"
        edges, n_bins_eff, d2h_bytes = found
        if sp is not None:
            # which edge rule the sketch served, the widest edge list,
            # where the edges were made, what the host fetched for them
            # and how many columns the device sorted for their ranks
            sp.attrs.update(
                edges="uniform" if uniform else "quantile",
                n_edges=max((len(e) for e in edges), default=0),
                enum_features=int(sum(bool(c) for c in is_cat)),
                numeric_features=int(sum(not c for c in is_cat)),
                where=where, d2h_bytes=int(d2h_bytes),
                ranked_features=ranked)
    with phase("bin.digitize"):
        codes = make_codes_view(digitize_with_edges(X, edges, n_bins_eff),
                                with_t=with_t)
        jax.block_until_ready(codes)  # h2o3-lint: allow[transfer-seam] digitise timing fence: between two device programs on one stream, so bin.digitize and bin.pack each carry their own
    return BinnedMatrix(codes=codes, n_bins=n_bins_eff, edges=edges,
                        names=list(names), is_categorical=list(is_cat),
                        nrow=nrow, sketch=where, ranked_features=ranked)


def _rank_grids(nfin, fmax, is_cat: Sequence[bool], nbins: int,
                nbins_cats: int, uniform: bool) -> List[Optional[np.ndarray]]:
    """Per feature the float64 virtual rank indexes its edges need, or
    None where they need none: an enum of at most ``nbins_cats`` levels
    has identity bins, a uniform grid reads min and max alone."""
    grids: List[Optional[np.ndarray]] = [None] * len(is_cat)
    for f, cat in enumerate(is_cat):
        n = int(nfin[f])
        if n == 0:
            continue
        if cat:
            if int(fmax[f]) + 1 <= nbins_cats:
                continue                     # identity bins — no quantiles
            qs = np.linspace(0.0, 1.0, nbins_cats + 1)[1:-1]
        elif uniform:
            continue                         # min/max only
        else:
            qs = np.linspace(0.0, 1.0, nbins + 1)[1:-1]
        grids[f] = qs * (n - 1)
    return grids


def _edges_of_stats(nfin, fmin, fmax, quant_vals, is_cat: Sequence[bool],
                    nbins: int, nbins_cats: int, uniform: bool):
    """(edges, effective bin count) from a column's finite count, min
    and max, and the quantile values of the columns that need them."""
    edges: List[np.ndarray] = []
    for f, cat in enumerate(is_cat):
        n = int(nfin[f])
        if cat:
            card = int(fmax[f]) + 1 if n > 0 else 1
            if card <= nbins_cats:
                e = (np.arange(1, card, dtype=np.float32) - 0.5)
            else:
                e = np.unique(quant_vals[f].astype(np.float32))
        elif n == 0:
            e = np.empty(0, dtype=np.float32)
        elif uniform:
            lo, hi = float(fmin[f]), float(fmax[f])
            e = (np.empty(0, dtype=np.float32) if lo == hi
                 else np.linspace(lo, hi, nbins + 1)[1:-1].astype(np.float32))
            e = e[: nbins - 1]
        else:
            e = np.unique(quant_vals[f].astype(np.float32))
            e = e[: nbins - 1]
        edges.append(e)
    n_bins_eff = max(nbins, max((len(e) + 1 for e in edges), default=2))
    if n_bins_eff > 16382:
        raise ValueError(
            f"effective bin count {n_bins_eff} exceeds the 14-bit routing "
            f"limit; lower nbins_cats (reference default is 1024)")
    return edges, n_bins_eff


@lru_cache(maxsize=8)
def _mesh_extremes(mesh):
    """Cached builder of :func:`_column_extremes` of a row-sharded matrix:
    every data shard reduces its own rows, then one psum / pmin / pmax of
    F numbers over the ``data`` axis."""
    from jax.sharding import PartitionSpec as P

    def local(X, nrow):
        row0 = jax.lax.axis_index("data") * X.shape[0]
        nfin, fmin, fmax = _column_extremes(X, row0, nrow)
        return (jax.lax.psum(nfin, "data"), jax.lax.pmin(fmin, "data"),
                jax.lax.pmax(fmax, "data"))

    return jax.jit(jax.shard_map(local, mesh=mesh, in_specs=(P("data"), P()),
                                 out_specs=P()))


def _mesh_sketch_edges(mesh, X, is_cat: Sequence[bool], nrow: int,
                       nbins: int, nbins_cats: int, uniform: bool):
    """(edges, effective bin count, bytes fetched) of a row-sharded
    matrix whose every column's edges need only its extremes, from
    :func:`_mesh_extremes`; None where a column needs ranks (quantile
    edges, an enum past ``nbins_cats``) or the rows do not split evenly
    over the data axis."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.parallel.mesh import n_data_shards
    if X.shape[0] % n_data_shards(mesh):
        return None
    # ONE counted fetch of O(F) numbers (transfer-seam)
    nfin, fmin, fmax = (np.asarray(v) for v in telemetry.device_get(
        _mesh_extremes(mesh)(X, jnp.int32(nrow)), pipeline="train"))
    if any(g is not None for g in _rank_grids(nfin, fmax, is_cat, nbins,
                                              nbins_cats, uniform)):
        return None
    return (*_edges_of_stats(nfin, fmin, fmax, None, is_cat, nbins,
                             nbins_cats, uniform),
            nfin.nbytes + fmin.nbytes + fmax.nbytes)


def _device_sketch_edges(X, is_cat: Sequence[bool], nrow: int, nbins: int,
                         nbins_cats: int, uniform: bool):
    """(edges, effective bin count, bytes fetched, columns sorted) of
    :func:`bin_matrix_device` on one device. The extremes come first (a
    column's max says whether an enum is past ``nbins_cats``), then
    :func:`_rank_grids` names the columns whose edges read ranks, and the
    device sorts those alone: where it names none no sort is dispatched;
    where it names them all ``X`` is sorted as it is, with no column
    copy."""
    from h2o3_tpu import telemetry
    F = X.shape[1]
    # ONE counted fetch of the O(F) sketch stats (transfer-seam)
    nfin, fmin, fmax = (np.asarray(v) for v in telemetry.device_get(
        _device_extremes(X, jnp.int32(nrow)), pipeline="train"))
    d2h_bytes = nfin.nbytes + fmin.nbytes + fmax.nbytes
    # per-feature quantile grids (numeric: nbins; over-wide cats:
    # nbins_cats) — build one padded rank-index matrix for a single gather
    qgrids = _rank_grids(nfin, fmax, is_cat, nbins, nbins_cats, uniform)
    ranked = [f for f, virt in enumerate(qgrids) if virt is not None]
    quant_vals: List[Optional[np.ndarray]] = [None] * F
    if ranked:
        qmax = max(len(qgrids[f]) for f in ranked)
        lo_idx = np.zeros((qmax, len(ranked)), np.int32)
        hi_idx = np.zeros((qmax, len(ranked)), np.int32)
        for j, f in enumerate(ranked):
            virt = qgrids[f]
            lo_idx[: len(virt), j] = np.floor(virt).astype(np.int32)
            hi_idx[: len(virt), j] = np.ceil(virt).astype(np.int32)
        Xs = _sort_finite(X if len(ranked) == F else X[:, np.asarray(ranked)],
                          jnp.int32(nrow))
        a, b = (np.asarray(v) for v in telemetry.device_get(
            _gather_rank_pairs(Xs, jnp.asarray(lo_idx),
                               jnp.asarray(hi_idx)), pipeline="train"))
        del Xs  # release the sorted copy before digitize allocates
        d2h_bytes += a.nbytes + b.nbytes
        for j, f in enumerate(ranked):
            virt = qgrids[f]
            t = virt - np.floor(virt)
            quant_vals[f] = _np_quantile_lerp(a[: len(virt), j],
                                              b[: len(virt), j], t)
    return (*_edges_of_stats(nfin, fmin, fmax, quant_vals, is_cat, nbins,
                             nbins_cats, uniform), d2h_bytes, len(ranked))


def bin_matrix(X, names: Sequence[str], is_cat: Sequence[bool], nrow: int,
               nbins: int = 255, nbins_cats: int = 1024,
               histogram_type: str = "quantiles_global",
               with_t: bool = True, X_host=None) -> BinnedMatrix:
    """Digitise a dense [padded_rows, F] float matrix (NaN = NA) into codes.
    The edges come from a host copy (``X_host`` when the caller already
    holds one); the digitise runs on ``X`` where it lives, so a sharded
    device matrix is digitised shard by shard.

    Categorical columns with cardinality <= nbins_cats use identity binning
    (code = category id) — group-per-category splits, the reference's
    nbins_cats semantics (hex/tree/DHistogram nbins_cats=1024). When a
    categorical needs more bins than ``nbins``, the matrix-wide bin count
    grows to fit it (histograms are [*, F, B+1, *] with one shared B;
    numeric features simply leave the extra bins empty). Cardinalities
    beyond nbins_cats fall back to quantile grouping of the code space.
    """
    if X_host is None:
        X_host = np.asarray(X, dtype=np.float32)
    edges, n_bins_eff = _edges_host(X_host, nrow, is_cat, nbins,
                                    nbins_cats, histogram_type)
    codes = make_codes_view(digitize_with_edges(X, edges, n_bins_eff),
                            with_t=with_t)
    return BinnedMatrix(codes=codes, n_bins=n_bins_eff, edges=edges,
                        names=list(names), is_categorical=list(is_cat),
                        nrow=nrow)


def _edges_host(X_host: np.ndarray, nrow: int, is_cat: Sequence[bool],
                nbins: int, nbins_cats: int, histogram_type: str):
    """The host edge rules shared by :func:`bin_matrix` and the
    memory-pressure sketch (:func:`digitize_codes_host`). Returns
    (edges, n_bins_eff)."""
    F = X_host.shape[1]
    edge_fn = (uniform_edges if histogram_type in ("uniform_adaptive", "uniform")
               else quantile_edges)
    edges: List[np.ndarray] = []
    for f in range(F):
        col = X_host[:nrow, f]
        if is_cat[f]:
            card = int(np.nanmax(col)) + 1 if np.isfinite(col).any() else 1
            if card <= nbins_cats:
                e = (np.arange(1, card, dtype=np.float32) - 0.5)
            else:
                e = quantile_edges(col, nbins_cats)
        else:
            e = edge_fn(col, nbins)
            e = e[: nbins - 1]
        edges.append(e)
    # shared bin count = the widest feature's need (>= nbins only if a
    # categorical demands group-per-category resolution). Capped by the
    # 14-bit packed-word routing field (models/tree.py BIN_BITS).
    n_bins_eff = max(nbins, max((len(e) + 1 for e in edges), default=2))
    if n_bins_eff > 16382:
        raise ValueError(
            f"effective bin count {n_bins_eff} exceeds the 14-bit routing "
            f"limit; lower nbins_cats (reference default is 1024)")
    return edges, n_bins_eff


def digitize_codes_host(X_host, edges: List[np.ndarray], n_bins_eff: int):
    """Host digitise of precomputed edges straight to the packed kernel
    convention (NA = reserved bin W-1, dtype from
    hist_adaptive.code_dtype so host and device packing can never
    diverge) — the memory-pressure half of the streamed packed path:
    the full X never uploads. Searchsorts (numpy, side="right") the
    same inf-PADDED edge matrix whose edges <= x the device
    :func:`digitize_with_edges` counts - the same number - so +inf
    values land in the shared lane ``max_e`` on every feature
    (bit-matching the dense packed codes — a per-feature unpadded
    searchsorted would merge +inf with the top finite bin on short-edge
    features and break streamed-vs-dense parity AND train-vs-score
    routing).
    Column-at-a-time so the temporaries stay O(rows). Returns
    (codes [rows, F], W)."""
    from h2o3_tpu.ops.hist_adaptive import code_dtype, pick_W
    X_host = np.asarray(X_host, dtype=np.float32)
    W = pick_W(n_bins_eff)
    np_dt = np.dtype(code_dtype(W))
    rows, F = X_host.shape
    max_e = max((len(e) for e in edges), default=0)
    emat = np.full((F, max(max_e, 1)), np.inf, dtype=np.float32)
    for f, e in enumerate(edges):
        emat[f, : len(e)] = e
    codes = np.empty((rows, F), np_dt)
    for f in range(F):
        col = X_host[:, f]
        c = np.searchsorted(emat[f], col, side="right")
        codes[:, f] = np.where(np.isnan(col), W - 1, c).astype(np_dt)
    return codes, W


def packed_codes_record(enabled: bool, dtype=None, W: int = None,
                        bytes_per_value: int = None,
                        n_bins: int = None, plan: dict = None,
                        set_features: int = 0) -> dict:
    """The ONE spelling of ``model.output['packed_codes']`` — GBM dense,
    GBM streamed and DRF all emit it through here so bench.py /
    profile_train.py key parsing can never meet a drifted copy. ``plan``
    (``hist_adaptive.binned_level_plan``) names the level kernel as the
    device trace does, with its feature block and row tile."""
    if not enabled:
        return {"enabled": False}
    return {"enabled": True, "dtype": str(np.dtype(dtype)), "W": int(W),
            "bytes_per_value": int(bytes_per_value), "n_bins": int(n_bins),
            "kernel": "binned_level", "set_features": int(set_features),
            **(plan or {})}


def make_codes_view(codes_rm, tile: int = 2048, mesh=None,
                    with_t: bool = True) -> CodesView:
    """Build both layouts; the transposed int32 copy only on TPU (it only
    serves the pallas kernel). Both layouts are sharded over the mesh
    'data' axis (rows): rm as [rows@data, F]; t as [Fp, rows_p@data],
    transposed and tile-padded PER SHARD (shard i's t columns are shard
    i's rm rows — a global end-pad would misalign the row sets).
    ``with_t=False`` skips the transposed build — the packed hot path
    (pack_codes) supersedes it with the int8/int16 operand, and
    building the rows*F*4-byte int32 copy just to drop it would cost
    the very HBM the packing saves."""
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.resilience import resilient_device_put

    mesh = mesh or current_mesh()
    nd = n_data_shards(mesh)
    rows, F = codes_rm.shape
    if rows % nd == 0:
        codes_rm = resilient_device_put(
            codes_rm, NamedSharding(mesh, P("data")), pipeline="train")
    if not with_t or jax.default_backend() != "tpu":
        return CodesView(rm=codes_rm, t=None)
    from h2o3_tpu.ops.hist_pallas import FBLK

    def build_t(rm_local):
        rows_l = rm_local.shape[0]
        pad_r = (-rows_l) % tile
        pad_f = (-F) % FBLK
        return jnp.pad(rm_local.astype(jnp.int32).T, ((0, pad_f), (0, pad_r)))

    if rows % nd == 0 and nd > 1:
        t = jax.jit(jax.shard_map(build_t, mesh=mesh, in_specs=P("data"),
                                  out_specs=P(None, "data")))(codes_rm)
    else:
        t = build_t(codes_rm)
        t = resilient_device_put(t, NamedSharding(mesh, P(None, "data")),
                                 pipeline="train")
    return CodesView(rm=codes_rm, t=t)


@partial(jax.jit, static_argnames=("na", "W", "dt"))
def _repack_codes(c, *, na: int, W: int, dt):
    """NA code n_bins -> reserved lane W-1, narrowed to the kernel
    dtype. Module-level jit (static na/W/dt) so a warm retrain reuses
    the executable — no per-call wrapper, no stray recompile."""
    ci = c.astype(jnp.int32)
    return jnp.where(ci == na, W - 1, ci).astype(dt)


def lane_widths(bin_counts: Sequence[int]) -> tuple:
    """Lanes a feature on the level kernel's global lane axis, from its
    real bin count: its bins, one NA lane (the last), rounded up to a
    whole sublane group of 8."""
    return tuple(-(-(int(n) + 1) // 8) * 8 for n in bin_counts)


def lanes_code_dtype(widths: tuple):
    """Smallest kernel-legal integer dtype for global lanes."""
    return jnp.int8 if sum(widths) <= 128 else jnp.int16


@partial(jax.jit, static_argnames=("na", "widths", "dt"))
def _repack_codes_ragged(c, *, na: int, widths: tuple, dt):
    """NA code n_bins -> the feature's own last lane, kernel dtype."""
    ci = c.astype(jnp.int32)
    last = jnp.asarray([w - 1 for w in widths], jnp.int32)
    return jnp.where(ci == na, last[None, :], ci).astype(dt)


@lru_cache(maxsize=32)
def _pack_t_ragged(mesh, widths: tuple, tile: int):
    """Cached builder of the [F, rows_p@data] operand on GLOBAL lanes,
    padded per shard like :func:`_pack_t_sharded`."""
    from jax.sharding import PartitionSpec as P
    return jax.jit(jax.shard_map(
        partial(_global_lanes_t, widths=widths, tile=tile), mesh=mesh,
        in_specs=P("data"), out_specs=P(None, "data")))


def _global_lanes_t(rm, *, widths: tuple, tile: int):
    """[F, rows_p] GLOBAL lanes: local code + lane offset; pad rows NA."""
    from h2o3_tpu.ops.hist_adaptive import lane_offsets
    off = jnp.asarray(lane_offsets(widths), rm.dtype)
    t = rm.T + off[:, None]
    pad_r = (-t.shape[1]) % tile
    if pad_r:
        na = off + jnp.asarray([w - 1 for w in widths], rm.dtype)
        t = jnp.concatenate(
            [t, jnp.broadcast_to(na[:, None], (t.shape[0], pad_r))], axis=1)
    return t


@partial(jax.jit, static_argnames=("W", "tile"))
def _pack_t_single(rm, *, W: int, tile: int):
    rows_l = rm.shape[0]
    pad_r = (-rows_l) % tile
    return jnp.pad(rm.T, ((0, 0), (0, pad_r)), constant_values=W - 1)


@lru_cache(maxsize=32)
def _pack_t_sharded(mesh, W: int, tile: int):
    """Cached shard_map transpose builder per (mesh, W): shard i's t
    columns are shard i's rm rows, padded per shard."""
    from jax.sharding import PartitionSpec as P

    def build_t(rm_local):
        rows_l = rm_local.shape[0]
        pad_r = (-rows_l) % tile
        return jnp.pad(rm_local.T, ((0, 0), (0, pad_r)),
                       constant_values=W - 1)

    return jax.jit(jax.shard_map(build_t, mesh=mesh, in_specs=P("data"),
                                 out_specs=P(None, "data")))


def pack_codes(bm: "BinnedMatrix", mesh=None,
               widths: tuple = ()) -> PackedCodes:
    """Pack a BinnedMatrix's codes for the binned pallas level kernel:
    remap NA (code == n_bins) to the reserved lane W-1, narrow to the
    smallest kernel dtype (int8 for W <= 128, else int16), and build
    the transposed tile-padded hot-loop operand on TPU (or under the
    interpret escape). Sharding mirrors make_codes_view: rm stays
    [rows@data, F]; t is [F, rows_p@data] padded PER SHARD so shard
    i's t columns are shard i's rm rows. ``widths``: the per-feature
    lane layout of a frame with enum features (:func:`lane_widths`);
    ``t`` then holds global lanes."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from h2o3_tpu.ops.hist_adaptive import (TILE, code_dtype,
                                            pallas_interpret, pick_W)
    from h2o3_tpu.parallel.mesh import current_mesh, n_data_shards

    if widths:
        dt = lanes_code_dtype(widths)
        rm = _repack_codes_ragged(bm.codes.rm, na=bm.n_bins, widths=widths,
                                  dt=dt)
        t = None
        if jax.default_backend() == "tpu" or pallas_interpret():
            t = _pack_t_ragged(mesh or current_mesh(), widths, TILE)(rm)
        return PackedCodes(rm=rm, t=t, W=max(widths), widths=widths)
    W = pick_W(bm.n_bins)
    dt = code_dtype(W)
    rm = _repack_codes(bm.codes.rm, na=bm.n_bins, W=W, dt=dt)
    if not (jax.default_backend() == "tpu" or pallas_interpret()):
        return PackedCodes(rm=rm, t=None, W=W)
    mesh = mesh or current_mesh()
    nd = n_data_shards(mesh)
    rows = rm.shape[0]
    if rows % nd == 0 and nd > 1:
        t = _pack_t_sharded(mesh, W, TILE)(rm)
    else:
        from h2o3_tpu.resilience import resilient_device_put
        t = _pack_t_single(rm, W=W, tile=TILE)
        t = resilient_device_put(t, NamedSharding(mesh, P(None, "data")),
                                 pipeline="train")
    return PackedCodes(rm=rm, t=t, W=W)


def stripe_pair_codes(ct, W: int):
    """Stripe-aware relayout of the transposed packed operand for the
    W=16 stripe kernel (ops/hist_adaptive._kernel_bt_stripe): features
    pair up two-per-32-lane stripe, so an ODD feature count pads one
    all-NA feature row (code W-1 — zero split mass; the kernel slices
    its histogram columns away). Even F passes through untouched — the
    pairing itself needs no data movement, adjacent rows already form
    the stripes."""
    F = ct.shape[0]
    if F % 2 == 0:
        return ct
    return jnp.pad(ct, ((0, 1), (0, 0)), constant_values=W - 1)


def pack_codes_for(X, bm: "BinnedMatrix", W: Optional[int] = None,
                   widths: tuple = ()):
    """Digitise a NEW matrix (validation / scoring frame) with the
    training sketch's edges and pack it to the kernel convention
    (NA = reserved bin W-1, or each feature's last lane under
    ``widths``; kernel dtype). Row-major only — predict_binned walks it
    with the packed codes' ``na_bin``."""
    from h2o3_tpu.ops.hist_adaptive import code_dtype, pick_W
    c = digitize_with_edges(X, bm.edges, bm.n_bins)
    if widths:
        return _repack_codes_ragged(c, na=bm.n_bins, widths=widths,
                                    dt=lanes_code_dtype(widths))
    W = W or pick_W(bm.n_bins)
    return _repack_codes(c, na=bm.n_bins, W=W, dt=code_dtype(W))


# Edges counted per pass over the matrix: up to this many compares are
# unrolled into one elementwise fusion; a wider edge matrix adds one pass
# (x read, count read and written) per further block of this many.
_EDGE_BLOCK = 32


@partial(jax.jit, static_argnames=("dtype",))
def _digitize(x, emat, nbins, *, dtype):
    """f32 [rows, F] matrix and inf-padded edges [F, E] -> codes [rows, F]
    of ``dtype``, as ONE device program: code = the number of the
    feature's edges <= x (ties go right, +inf counts every pad lane and
    lands in the shared lane ``max_e``), NaN -> ``nbins`` (a traced
    scalar: one executable serves every bin count of a dtype).

    A count, not a search: compares and adds are elementwise along rows,
    so the program has no gather and, up to ``_EDGE_BLOCK`` edges, no
    loop; ``jnp.searchsorted``'s default method is a ``while`` of
    log2(E) gathers, and a gather over 10M rows costs ~10 ns an element
    on a TPU (14 s of a 20 s default GBM train, PERF.md PR 28). Wider
    edge matrices (identity-binned categoricals, E up to 1023) add the
    same compares a block of edges at a time, so temporaries stay
    O(rows * F) on every backend - a broadcast [rows, F, E] compare
    reduced over E is materialised whole by XLA's CPU backend."""
    E = emat.shape[1]

    def count(acc, cols):                  # cols [F, k]: k unrolled compares
        for j in range(cols.shape[1]):
            acc = acc + (cols[:, j] <= x)
        return acc

    acc = jnp.zeros(x.shape, jnp.int32)
    full = (E - 1) // _EDGE_BLOCK          # whole blocks before the last
    if full:
        acc = jax.lax.fori_loop(
            0, full, lambda i, a: count(a, jax.lax.dynamic_slice_in_dim(
                emat, i * _EDGE_BLOCK, _EDGE_BLOCK, axis=1)), acc)
    acc = count(acc, emat[:, full * _EDGE_BLOCK:])
    return jnp.where(jnp.isnan(x), nbins, acc).astype(dtype)


def digitize_with_edges(X, edges: List[np.ndarray], nbins: int) -> jax.Array:
    """Digitise a new matrix with previously-computed edges (validation /
    scoring frames share the training sketch, like XGBoost's global hist).
    The device counts, per value, the feature's inf-padded edges <= it
    (:func:`_digitize`); the result equals ``np.searchsorted(edges_f,
    col, side="right")`` with NaN -> ``nbins`` for every element, which
    is what the host digitise (:func:`digitize_codes_host`) computes."""
    F = len(edges)
    max_e = max((len(e) for e in edges), default=0)
    emat = np.full((F, max(max_e, 1)), np.inf, dtype=np.float32)
    for f, e in enumerate(edges):
        emat[f, : len(e)] = e
    dtype = jnp.uint8 if nbins < 256 else jnp.int32
    return _digitize(jnp.asarray(X, dtype=jnp.float32), jnp.asarray(emat),
                     np.int32(nbins), dtype=dtype)


def split_threshold(bm: BinnedMatrix, feature: int, bin_idx: int) -> float:
    """Raw-value threshold for 'left ⇔ code < bin_idx'. A split bin beyond
    the edge list means 'all non-NA left' → +inf (see
    models.tree.bins_to_thresholds)."""
    e = bm.edges[feature]
    if len(e) == 0 or bin_idx - 1 >= len(e):
        return float("inf")
    return float(e[bin_idx - 1])
