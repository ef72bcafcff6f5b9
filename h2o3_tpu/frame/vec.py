"""Vec — one column of a distributed Frame.

Reference: water/fvec/Vec.java:157 — a Vec is a named column whose rows are
split into compressed Chunks stored in the DKV, with an ESPC row layout and
lazily-computed RollupStats. TPU re-design:

- the ~20 chunk compressor subtypes (water/fvec/C*.java, chosen by
  NewChunk.compress()) collapse into dtype choice on a single padded,
  row-sharded ``jax.Array`` — XLA wants flat dense typed buffers, not
  per-chunk byte-packing;
- the ESPC layout (water/fvec/Vec.java:163-171) becomes an even row
  partition over the mesh 'data' axis (static shapes for XLA), padded at
  the tail; validity is derived from ``row_index < nrow`` plus NA
  sentinels;
- types mirror Vec.T_* (water/fvec/Vec.java:207-212): real/int/enum/time/
  str. Enum domains are host-side tuples (the reference's String[] domain).

NA encoding: NaN for float data, -1 for enum codes. Time is stored on
device as float32 epoch-seconds (exact int64 millis kept host-side when
available). Strings are host-only (no device representation — same as the
reference, which never computes on strings distributedly except via Rapids
string ops, which we run host-side).
"""
from __future__ import annotations

import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.parallel.mesh import current_mesh, padded_len
from h2o3_tpu.telemetry import record_d2h, record_h2d

T_REAL = "real"
T_INT = "int"
T_ENUM = "enum"
T_TIME = "time"
T_STR = "string"

ENUM_NA = -1

# reference default percentiles: water/fvec/Vec.java PERCENTILES
PERCENTILES = (0.001, 0.01, 0.1, 0.25, 1.0 / 3.0, 0.5, 2.0 / 3.0, 0.75, 0.9, 0.99, 0.999)


class Vec:
    def __init__(self, data, nrow: int, vtype: str = T_REAL,
                 domain: Optional[Sequence[str]] = None, host_data=None):
        self._dev = data            # padded, row-sharded jax.Array (None for str vecs)
        self._spilled = None        # (padded numpy, sharding) when evicted
        self._memblock = None
        self.nrow = int(nrow)
        self.type = vtype
        self.domain = tuple(domain) if domain is not None else None
        self.host_data = host_data  # numpy: exact values for str/time
        self._rollups = None
        if data is not None:
            self._register_mem()

    # -- device-memory management (water/Cleaner.java swap-to-disk
    #    analog: HBM payloads spill to host numpy under pressure and
    #    re-materialize on next access; see h2o3_tpu/memman.py) --------

    def _register_mem(self):
        import weakref
        from h2o3_tpu import memman
        ref = weakref.ref(self)

        def spill():
            v = ref()
            if v is not None:
                v._spill()

        try:
            nbytes = int(self._dev.nbytes)
        except (AttributeError, TypeError):
            nbytes = self.nrow * 4
        # allocation gate: evict LRU payloads if this one crosses the
        # watermark (the payload itself is already on device — XLA
        # allocated it — but the budget accounting evicts peers so the
        # NEXT allocation has room; MemoryManager.java's malloc gate)
        memman.manager().request(nbytes)
        self._memblock = memman.manager().register(nbytes, spill)

    def _spill(self):
        """Move the device payload to host and release the device ref."""
        if self._dev is None:
            return
        arr = np.asarray(jax.device_get(self._dev))
        record_d2h(arr.nbytes, fallback="frame")
        # _spill runs as the memman spill callback, i.e. UNDER
        # memman._LOCK (manager().request holds it while evicting) —
        # the writes are lock-protected interprocedurally, which the
        # per-module lock-discipline analysis cannot see
        self._spilled = (arr, getattr(self._dev, "sharding", None))  # h2o3-lint: allow[lock-discipline] runs under memman._LOCK via the spill callback
        self._dev = None  # h2o3-lint: allow[lock-discipline] runs under memman._LOCK via the spill callback
        self._memblock = None

    @property
    def data(self):
        # lock-free fast path: capture the reference FIRST — a
        # concurrent spill (another thread's memman.request) may null
        # _dev after the check, but the captured device array stays
        # valid (the spill only drops the Vec's own reference)
        dev = self._dev
        if dev is None and self._spilled is not None:
            from h2o3_tpu import memman
            with memman._LOCK:           # serialize vs concurrent spills
                dev = self._dev
                if dev is None and self._spilled is not None:
                    arr, sh = self._spilled
                    memman.manager().request(arr.nbytes)
                    try:
                        # the unspill upload deliberately happens under
                        # the memman lock: a concurrent request() must
                        # not evict the block being restored mid-flight
                        dev = (jax.device_put(arr, sh) if sh is not None  # h2o3-lint: allow[lock-discipline] unspill must serialize vs concurrent eviction
                               else jnp.asarray(arr))
                    except Exception:   # mesh changed: replicate
                        dev = jnp.asarray(arr)
                    self._dev = dev
                    self._spilled = None
                    self._register_mem()
        blk = self._memblock
        if blk is not None:
            from h2o3_tpu import memman
            memman.manager().touch(blk)
        return dev

    @data.setter
    def data(self, v):
        # setter races are the CALLER's contract (a Vec is published to
        # other threads only after construction/mutation completes —
        # frame ops build new Vecs, they do not mutate shared ones)
        self._dev = v  # h2o3-lint: allow[lock-discipline] single-owner mutation before publication
        self._spilled = None  # h2o3-lint: allow[lock-discipline] single-owner mutation before publication
        self._memblock = None
        if v is not None:
            self._register_mem()

    # ---------------- construction ----------------

    TIME_NA = np.iinfo(np.int64).min  # host sentinel for missing timestamps

    @staticmethod
    def from_numpy(arr: np.ndarray, vtype: Optional[str] = None,
                   domain: Optional[Sequence[str]] = None, mesh=None) -> "Vec":
        mesh = mesh or current_mesh()
        arr = np.asarray(arr)
        explicit = vtype is not None
        if vtype is None:
            if arr.dtype.kind in "OUS":
                return Vec._from_strings(arr, mesh)
            vtype = T_INT if arr.dtype.kind in "iub" else T_REAL
        nrow = len(arr)
        if vtype == T_STR:
            return Vec(None, nrow, T_STR, host_data=np.asarray(arr, dtype=object))
        if vtype == T_ENUM:
            codes = np.asarray(arr, dtype=np.int32)
            dev = _pad_and_put(codes, nrow, np.int32(ENUM_NA), mesh)
            return Vec(dev, nrow, T_ENUM, domain=domain)
        if vtype == T_TIME:
            host = np.asarray(arr, dtype=np.int64)
            sec = np.where(host == Vec.TIME_NA, np.nan, host / 1000.0).astype(np.float32)
            dev = _pad_and_put(sec, nrow, np.float32(np.nan), mesh)
            return Vec(dev, nrow, T_TIME, host_data=host)
        # wide int64 input (beyond float64's exact 2^53): the float64
        # round-trip would silently munge values, so the exact int64
        # array itself becomes the host copy (water/fvec/C8Chunk)
        if (vtype == T_INT and arr.dtype.kind in "iu" and arr.size
                and np.abs(arr, dtype=np.float64).max() >= float(1 << 53)
                # uint64 above int64 max can't ride the exact shadow —
                # asarray would wrap it negative; let it degrade to the
                # approximate float64 path below instead
                and (arr.dtype.kind == "i"
                     or arr.max() <= np.uint64(np.iinfo(np.int64).max))):
            f64 = np.asarray(arr, dtype=np.int64)
            dev = _pad_and_put(f64.astype(np.float32), nrow,
                               np.float32(np.nan), mesh)
            return Vec(dev, nrow, T_INT, host_data=f64.copy())
        f64 = np.asarray(arr, dtype=np.float64)
        f = f64.astype(np.float32)
        if not explicit and vtype == T_INT and not _is_integral(f64):
            vtype = T_REAL
        dev = _pad_and_put(f, nrow, np.float32(np.nan), mesh)
        return Vec(dev, nrow, vtype, host_data=_numeric_host_copy(f64, vtype))

    @staticmethod
    def _from_strings(arr: np.ndarray, mesh) -> "Vec":
        """String column → enum (codes + domain), mirroring the parser's
        categorical handling (water/parser/ParseDataset.java PackedDomains)."""
        arr = np.asarray(arr, dtype=object)
        isna = np.array([x is None or (isinstance(x, float) and np.isnan(x)) or x == ""
                         for x in arr])
        vals = np.array(["" if m else str(v) for v, m in zip(arr, isna)])
        domain = np.unique(vals[~isna]) if (~isna).any() else np.array([], dtype=str)
        codes = np.searchsorted(domain, vals).astype(np.int32)
        codes[isna] = ENUM_NA
        dev = _pad_and_put(codes, len(arr), np.int32(ENUM_NA), mesh)
        return Vec(dev, len(arr), T_ENUM, domain=[str(d) for d in domain])

    @staticmethod
    def constant(value: float, nrow: int, mesh=None) -> "Vec":
        return Vec.from_numpy(np.full(nrow, value, dtype=np.float32), mesh=mesh)

    # ---------------- properties ----------------

    def __len__(self) -> int:
        return self.nrow

    @property
    def is_numeric(self) -> bool:
        return self.type in (T_REAL, T_INT)

    @property
    def is_categorical(self) -> bool:
        return self.type == T_ENUM

    @property
    def cardinality(self) -> int:
        return len(self.domain) if self.domain is not None else -1

    def valid_mask(self):
        """Device bool mask of real (non-pad, non-NA) rows."""
        if self.data is None:
            raise ValueError("string Vec has no device representation")
        n = self.data.shape[0]
        inrange = jnp.arange(n) < self.nrow
        if self.type == T_ENUM:
            return inrange & (self.data >= 0)
        return inrange & ~jnp.isnan(self.data)

    def asfactor(self) -> "Vec":
        """Numeric → categorical conversion (h2o-py ``vec.asfactor()``;
        water/rapids/ast/prims/operators/AstAsFactor semantics): distinct
        finite values become the sorted domain, NA stays NA."""
        return self.factor()[0]

    def factor(self):
        """(``asfactor()``'s Vec, the path that made it): ``none`` for an
        enum, else counted in ``h2o3_factor_total{path}``. A device payload
        of enough rows whose finite values are a small integer range is
        factored on the device (``frame/factor.py``: ``device_range``);
        strings, a Vec whose values live on the host (an exact wide-int or
        time copy, a spilled payload) and every other column take the host
        formula."""
        if self.type == T_ENUM:
            return self, "none"
        made = None
        if self.host_data is None and self._dev is not None:
            from h2o3_tpu.frame.factor import factor
            made = factor(self.data, self.nrow)
        if made is not None:
            codes, domain = made
            out = Vec(codes, self.nrow, T_ENUM, domain=domain)
            path = "device_range"
        elif self.type == T_STR:
            out, path = Vec._from_strings(self.host_data, current_mesh()), "host"
        else:
            out, path = self._factor_host(), "host"
        from h2o3_tpu import telemetry
        telemetry.counter("h2o3_factor_total", {"path": path},
                          help="numeric and string Vecs made enums, by "
                               "the path that made them").inc()
        return out, path

    def _factor_host(self) -> "Vec":
        raw = self.to_numpy()
        finite = np.isfinite(raw)
        vals = np.unique(raw[finite])
        domain = tuple(level_label(v) for v in vals)
        codes = np.searchsorted(vals, raw).astype(np.int32)
        codes[~finite] = ENUM_NA
        return Vec.from_numpy(codes, vtype=T_ENUM, domain=domain)

    def asnumeric(self) -> "Vec":
        """Categorical → numeric (h2o-py ``vec.asnumeric()``): domain labels
        parse back to numbers when possible, else the codes are used."""
        if self.type != T_ENUM:
            return self
        codes = self.to_numpy()
        try:
            lut = np.array([float(d) for d in self.domain], dtype=np.float32)
            out = np.where(codes >= 0, lut[np.maximum(codes, 0)], np.nan)
        except (ValueError, TypeError):
            out = np.where(codes >= 0, codes.astype(np.float32), np.nan)
        return Vec.from_numpy(out.astype(np.float32))

    def as_float(self):
        """Device float32 view with NA→NaN (enums become their codes)."""
        if self.data is None:
            raise ValueError("string Vec has no device representation; "
                             "drop or re-type string columns before compute")
        if self.type == T_ENUM:
            return jnp.where(self.data >= 0, self.data.astype(jnp.float32), jnp.nan)
        return self.data

    # ---------------- rollups ----------------

    def rollups(self) -> dict:
        """Lazy cached per-column stats — the RollupStats contract
        (water/fvec/RollupStats.java:7-16): computed on first ask, cached,
        invalidated on write. The reference races a DKV CAS to pick the
        computing node; single-controller JAX just computes once here."""
        if self._rollups is None:
            from h2o3_tpu.frame.rollups import compute_rollups
            self._rollups = compute_rollups(self)
        return self._rollups

    def invalidate_rollups(self):
        self._rollups = None

    def mean(self):
        return self.rollups()["mean"]

    def sigma(self):
        return self.rollups()["sigma"]

    def min(self):
        return self.rollups()["min"]

    def max(self):
        return self.rollups()["max"]

    def na_count(self):
        return self.rollups()["na_count"]

    def percentiles(self, probs=PERCENTILES):
        from h2o3_tpu.frame.rollups import compute_percentiles
        return compute_percentiles(self, probs)

    # ---------------- materialisation ----------------

    def to_numpy(self) -> np.ndarray:
        """Unpadded host copy. Enum → int codes (use .domain to decode);
        time → int64 millis; str → object array."""
        if self.type == T_STR:
            return self.host_data.copy()
        if self.host_data is not None:
            if self.type == T_TIME:
                return self.host_data.copy()
            # exact wide-int copy, NA as NaN (float64 holds ints to 2^53)
            return self.host_data.copy()
        if self._dev is None and self._spilled is not None:
            # spilled payload: serve the host copy directly instead of
            # re-uploading to device only to download again (that would
            # also churn the LRU in the exact memory-pressure paths)
            return np.asarray(self._spilled[0])[: self.nrow].copy()
        full = np.asarray(jax.device_get(self.data))
        # the transfer moves the PADDED device buffer — count what
        # actually crossed, not the sliced view (padding dominates on
        # small sharded frames)
        record_d2h(full.nbytes, fallback="frame")
        return full[: self.nrow]

    def to_strings(self) -> np.ndarray:
        """Decoded object array (enum codes → labels)."""
        if self.type == T_STR:
            return self.host_data.copy()
        raw = self.to_numpy()
        if self.type == T_ENUM:
            dom = np.array(list(self.domain) + [None], dtype=object)
            return dom[np.where(raw < 0, len(self.domain), raw)]
        return raw.astype(object)

    def with_data(self, new_data, vtype=None, domain=None) -> "Vec":
        v = Vec(new_data, self.nrow, vtype or self.type,
                domain if domain is not None else self.domain)
        return v


def level_label(v) -> str:
    """A numeric value's level in a factor's domain: ``"3"`` for 3.0."""
    return str(int(v)) if float(v).is_integer() else str(v)


def _is_integral(f: np.ndarray) -> bool:
    finite = f[np.isfinite(f)]
    return bool(finite.size == 0 or np.all(finite == np.round(finite)))


def _numeric_host_copy(f64: np.ndarray, vtype: str):
    """float32 mantissa is 24 bits: large ints (IDs, counts, epoch
    millis that arrive as REAL) would be silently rounded on device, so
    keep an exact float64 host copy whenever the values are integral and
    exceed the mantissa (the reference keeps exact long chunks —
    water/fvec/C8Chunk). Order matters: the cheap max check gates the
    O(n) integrality scan."""
    if f64.size:
        import warnings
        with np.errstate(invalid="ignore"), warnings.catch_warnings():
            # all-NaN columns (fully-missing numerics) warn via the
            # warnings module, which errstate does not cover
            warnings.simplefilter("ignore", RuntimeWarning)
            m = np.nanmax(np.abs(f64))       # one scan, no mask-copy
        if np.isnan(m):
            return None                      # all-NA column
        if np.isfinite(m) and m > (1 << 24):
            if vtype == T_INT or _is_integral(f64):
                return f64
        elif np.isinf(m):
            # ±inf hid the finite max: fall back to the exact mask path
            finite = f64[np.isfinite(f64)]
            if finite.size and np.abs(finite).max() > (1 << 24):
                if vtype == T_INT or _is_integral(f64):
                    return f64
    return None


_SPLIT_COLS_JIT = None


def split_columns(mat, ncol: int):
    """Every column slice of a 2-D device matrix in ONE compiled
    dispatch. ``ncol`` separate ``mat[:, j]`` expressions each bake
    their index into a distinct XLA program — a cold parse paid one
    compile PER COLUMN (ISSUE 14 found ~70 ms of the 29-column bench
    frame's assembly was exactly that). jit's shape cache makes repeat
    shapes free, and outputs follow the input's (row) sharding."""
    assert mat.shape[1] == ncol, (mat.shape, ncol)
    global _SPLIT_COLS_JIT
    if _SPLIT_COLS_JIT is None:
        _SPLIT_COLS_JIT = jax.jit(
            lambda m: tuple(m[:, j] for j in range(m.shape[1])))
    return list(_SPLIT_COLS_JIT(mat))


def batch_device_put(columns, fill, dtype, nrow: int, mesh=None):
    """One host→device transfer for a whole dtype group of columns.

    Columns land in a single padded row-sharded [plen, ncol] matrix —
    one DMA instead of ncol — and come back as per-column device arrays
    (on-device slices along the unsharded axis, so no resharding). The
    ingest pipeline overlaps the (async) transfer with the host-side
    encode of the remaining groups."""
    mesh = mesh or current_mesh()
    plen = padded_len(nrow, mesh)
    mat = np.empty((plen, len(columns)), dtype=dtype)
    if plen > nrow:
        mat[nrow:] = fill              # only the pad tail needs filling

    def _pack(j):
        # assignment converts dtype in the same pass as the copy (a
        # separate astype would write every column twice)
        mat[:nrow, j] = columns[j]

    if nrow * len(columns) >= (1 << 22):
        import concurrent.futures as cf
        with cf.ThreadPoolExecutor(
                max_workers=min(len(columns), os.cpu_count() or 4, 8)) as ex:
            list(ex.map(_pack, range(len(columns))))  # GIL-free memcpy
    else:
        for j in range(len(columns)):
            _pack(j)
    record_h2d(mat.nbytes, fallback="frame")
    dev = _resilient_put(mat, mesh)
    return split_columns(dev, len(columns))


def batch_device_put_local(columns, fill, dtype, row_lo: int, row_hi: int,
                           nrow_global: int, mesh=None,
                           simulate: bool = False):
    """Multihost spelling of :func:`batch_device_put`: this process packs
    and transfers ONLY its own padded row block ``[row_lo, row_hi)`` of
    the global ``[plen, ncol]`` matrix — the shard-local H2D target of
    the multi-host parse (``columns`` hold just the local data rows).
    The recorded H2D bytes are the LOCAL block, which is what per-process
    ``h2o3_ingest_h2d_bytes`` attribution asserts. ``simulate`` is the
    parity-test shape (a forced multi-process plan on a single-process
    mesh, where ``make_array_from_process_local_data`` cannot apply):
    the local block scatters into a fill-padded global matrix and takes
    the ordinary single-process sharded put — rows outside the local
    span are fill, never data, so a simulated process still only ever
    touches its own bytes."""
    from h2o3_tpu.resilience import resilient_shard_rows
    mesh = mesh or current_mesh()
    plen = padded_len(nrow_global, mesh)
    nloc = row_hi - row_lo
    mat = np.empty((nloc, len(columns)), dtype=dtype)
    real = max(0, min(row_hi, nrow_global) - row_lo)
    if real < nloc:
        mat[real:] = fill              # pad tail inside the local span
    for j in range(len(columns)):
        mat[:real, j] = columns[j]
    record_h2d(mat.nbytes, pipeline="ingest")
    if simulate:
        full = np.full((plen, len(columns)), fill, dtype=dtype)
        full[row_lo:row_hi] = mat
        dev = resilient_shard_rows(full, mesh, pipeline="ingest")
    else:
        dev = resilient_shard_rows(mat, mesh, pipeline="ingest",
                                   global_rows=plen)
    return split_columns(dev, len(columns))


def _resilient_put(arr, mesh):
    """Row-sharded placement behind the fault seam + shared transient
    retry (resilience.resilient_shard_rows → mesh.DataParallelPartitioner):
    a transient H2D failure (injected or organic) re-issues the DMA with
    backoff instead of failing the whole parse/train, and a multi-process
    mesh assembles the global array from process-local rows."""
    from h2o3_tpu.resilience import resilient_shard_rows
    return resilient_shard_rows(arr, mesh)


def _pad_and_put(arr: np.ndarray, nrow: int, fill, mesh):
    plen = padded_len(nrow, mesh)
    if plen != nrow:
        arr = np.concatenate([arr, np.full(plen - nrow, fill, dtype=arr.dtype)])
    record_h2d(arr.nbytes, fallback="frame")
    return _resilient_put(arr, mesh)
