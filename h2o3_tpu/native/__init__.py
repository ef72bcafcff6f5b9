"""ctypes binding + on-demand build of the native CSV tokenizer.

The shared object compiles once per machine into this package directory
(g++ -O3; ~1s). Import degrades gracefully: `lib()` returns None when no
toolchain is available and callers keep the Python path — the same
pluggable seam as the reference's ParserProvider SPI.

Zero-copy contract (ISSUE 14): every entry point takes any buffer numpy
can view — bytes, memoryview, an mmap slice — and hands the C scans a
raw pointer into it (``c_void_p``), so a byte-range worker tokenizes the
file's page cache directly with no per-range ``read()`` copy. The GIL is
released for the whole C call (ctypes), so a thread pool scales the scan
across cores.

``parse_bytes`` returns COLUMN-major cell arrays carved out of a
thread-local scratch arena that is REUSED across calls: callers must
finish (copy out or consume) every returned array before the same
thread calls ``parse_bytes`` again — ``encode_chunk_native`` does
exactly that within one call. Declines come back as a *reason string*
(``ragged_rows`` / ``unterminated_quote`` / ``trailing_after_quote`` /
``no_toolchain``), and the parse seam falls back per-range, not
per-import, counting each reason in ``h2o3_ingest_fallback_total``."""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import warnings

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "fast_csv.cpp")
_SO = os.path.join(_DIR, "libfastcsv.so")
_HASH = _SO + ".srchash"  # stamp: source + host CPU the .so was built for
_COMPILER = "g++"
_LOCK = threading.Lock()
_LIB = None
_TRIED = False

# last failed build's diagnostic (compiler name + stderr tail); callers
# that degrade to the Python path can surface WHY the toolchain bailed
BUILD_ERROR = None

# csv_parse reason codes -> the fallback-counter label (parse.py)
DECLINE_REASONS = {1: "ragged_rows", 2: "unterminated_quote",
                   3: "trailing_after_quote"}


def _build_stamp() -> str:
    """sha256 over the source AND this host's CPU feature flags: the
    build uses ``-march=native``, so a .so carried to a host with another
    CPU (a copied working tree) must rebuild, not die of SIGILL inside
    the tokenizer with no Python error."""
    h = hashlib.sha256()
    with open(_SRC, "rb") as f:
        h.update(f.read())
    try:
        with open("/proc/cpuinfo") as f:
            h.update(next((ln for ln in f if ln.startswith("flags")),
                          "").encode())
    except OSError:
        pass                    # no /proc: the stamp covers the source only
    return h.hexdigest()


def _build() -> bool:
    """Compile the .so and stamp what it was built from and for. A
    failed compile records a clear error NAMING the compiler (the silent
    `return False` used to leave "why is ingest slow" undiagnosable)."""
    global BUILD_ERROR
    # per-process temporaries: several processes (xdist workers) may
    # build at once, and each renames a whole file into the fixed path
    tmp_so = f"{_SO}.{os.getpid()}.tmp"
    cmd = [_COMPILER, "-O3", "-march=native", "-shared", "-fPIC",
           "-o", tmp_so, _SRC]
    try:
        r = subprocess.run(cmd, capture_output=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        BUILD_ERROR = (f"native CSV build failed: compiler '{_COMPILER}' "
                       f"could not run ({e}); falling back to the Python "
                       f"tokenizer")
        warnings.warn(BUILD_ERROR, RuntimeWarning, stacklevel=2)
        return False
    if r.returncode != 0:
        tail = (r.stderr or b"").decode("utf-8", "replace").strip()[-800:]
        BUILD_ERROR = (f"native CSV build failed: '{_COMPILER}' exited "
                       f"{r.returncode} compiling {_SRC}:\n{tail}")
        warnings.warn(BUILD_ERROR, RuntimeWarning, stacklevel=2)
        return False
    os.replace(tmp_so, _SO)
    try:
        tmp_hash = f"{_HASH}.{os.getpid()}.tmp"
        with open(tmp_hash, "w") as f:
            f.write(_build_stamp())
        os.replace(tmp_hash, _HASH)
    except OSError:
        pass  # without its stamp the next process rebuilds
    BUILD_ERROR = None
    return True


def _stale() -> bool:
    """Rebuild-if-stale guard: the stamp written at build time against
    this checkout's source and this host's CPU. mtime alone served stale
    symbols when a checkout/copy stamped the .so newer than an edited
    source (git checkout, rsync, build caches) — with new entry points
    landing per PR that silently pinned callers to an old ABI. A .so
    without a stamp says nothing of the CPU it was built for: rebuild."""
    if not os.path.exists(_SO):
        return True
    try:
        with open(_HASH) as f:
            return f.read().strip() != _build_stamp()
    except OSError:
        return True


def lib():
    """The loaded native library, or None (Python fallback)."""
    global _LIB, _TRIED
    with _LOCK:
        if _LIB is not None or _TRIED:
            return _LIB
        _TRIED = True
        if _stale():
            if not _build():
                return None
        for attempt in range(2):
            try:
                L = ctypes.CDLL(_SO)
            except OSError:
                return None
            LL, VP = ctypes.c_longlong, ctypes.c_void_p
            pLL = ctypes.POINTER(ctypes.c_longlong)
            pI = ctypes.POINTER(ctypes.c_int)
            pD = ctypes.POINTER(ctypes.c_double)
            pU8 = ctypes.POINTER(ctypes.c_ubyte)
            try:
                L.csv_parse.restype = LL
                L.csv_parse.argtypes = [VP, LL, ctypes.c_char,
                                        ctypes.c_char, LL, LL, VP, pLL,
                                        pI, pD, pU8, pLL, pLL]
                L.csv_chunk_bounds.restype = LL
                L.csv_chunk_bounds.argtypes = [VP, LL, ctypes.c_char,
                                               ctypes.c_char, pLL, LL, pLL]
                L.csv_enum_encode.restype = LL
                L.csv_enum_encode.argtypes = [VP, pLL, pI, LL, pI, pLL, LL]
                L.csv_gather_tokens.restype = None
                L.csv_gather_tokens.argtypes = [VP, pLL, pI, LL, LL, VP]
                L.csv_match_any.restype = None
                L.csv_match_any.argtypes = [VP, pLL, pI, LL,
                                            VP, pLL, pI, LL, pU8]
                L.csv_numeric_stats.restype = None
                L.csv_numeric_stats.argtypes = [pD, LL, pLL, LL, LL, LL,
                                                pD, pD, pU8]
                L.csv_count_rows.restype = LL
                L.csv_count_rows.argtypes = [VP, LL, ctypes.c_char,
                                             ctypes.c_char]
                L.csv_enum_encode_full.restype = LL
                L.csv_enum_encode_full.argtypes = [
                    VP, pLL, pI, LL, VP, VP, pLL, pI, LL, LL,
                    ctypes.c_int, pI, pLL, pU8]
            except AttributeError:
                # a stale .so that slipped BOTH the hash sidecar and the
                # mtime check: missing symbols mean the binary is from
                # another era — rebuild once, then give up (the ABI
                # check is the SYMBOL SET; a same-symbol signature
                # change must ride a new symbol or this check is blind)
                if attempt == 0 and _build():
                    continue
                return None
            _LIB = L
            return _LIB
        return None


def _as_u8(data):
    """Zero-copy uint8 view of any buffer (bytes / memoryview / mmap
    slice). The returned array BORROWS the caller's buffer — keep the
    source alive across the native call."""
    import numpy as np
    return np.frombuffer(data, dtype=np.uint8)


# thread-local scratch arena for the csv_parse output arrays, grown to
# the high-water cell count and reused across calls (the per-range
# allocation was measurable at 24-way fan-out). Each worker thread owns
# its own arena; parse_bytes hands out views into it.
_TLS = threading.local()


def _scratch(ncells: int):
    import numpy as np
    bufs = getattr(_TLS, "bufs", None)
    if bufs is None or bufs[0].size < ncells:
        n = max(ncells, 1)
        bufs = (np.empty(n, np.int64), np.empty(n, np.int32),
                np.empty(n, np.float64), np.empty(n, np.uint8))
        _TLS.bufs = bufs
    return bufs


def _infer_ncols(data, sep: str, quote: str) -> int:
    """Column count from the first row (only for callers without a
    ParseSetup — the parse pipeline passes its setup's count)."""
    import csv
    import io
    buf = _as_u8(data)
    head = bytes(buf[:buf.size if buf.size < 65536 else 65536])
    txt = head.decode("utf-8", errors="replace")
    for row in csv.reader(io.StringIO(txt), delimiter=sep,
                          quotechar=quote or '"'):
        if row:
            return len(row)
    return 0


def parse_bytes(data, sep: str, quote: str = '"', ncols=None,
                want_offsets=None):
    """Tokenise a CSV buffer natively (RFC-4180 quotes included) in ONE
    quote-aware C pass — rows are bounded by the buffer's newline count
    (a vectorized popcount, not a byte-walk), and the scan itself
    validates every row against ``ncols`` (the ParseSetup column count;
    inferred from the first row when absent).

    Returns ``(starts, lens, vals, ok, esc)`` numpy arrays of shape
    ``[ncols, nrows]`` (column-major: one contiguous slice per column),
    or a decline-reason string when the native path cannot tokenize this
    range (``no_toolchain``, ``ragged_rows``, ``unterminated_quote``,
    ``trailing_after_quote``, ``empty_range``). ``esc`` marks cells
    whose raw bytes still carry RFC-4180 ``""`` escapes (unescape before
    using the token's text). ``want_offsets`` (uint8 per column, None =
    all) suppresses the starts/lens writes for columns whose offsets the
    caller will never read back (float64 columns: their value IS
    vals[idx]) — the skipped arena regions stay unfaulted, roughly
    halving the scan's write traffic on mostly-numeric files; the
    starts/lens slices of suppressed columns hold GARBAGE. All five
    arrays are views into a reused thread-local arena — consume them
    before the next call on this thread."""
    import numpy as np
    L = lib()
    if L is None:
        return "no_toolchain"
    if ncols is None:
        ncols = _infer_ncols(data, sep, quote)
    if ncols <= 0:
        return "empty_range"
    buf = _as_u8(data)
    ptr, n = buf.ctypes.data, buf.size
    sep_b, quote_b = sep.encode()[0:1], (quote or '"').encode()[0:1]
    # upper bound: quoted embedded newlines only ever REDUCE the true
    # row count below newlines+1, so the arena never overflows
    cap = int(np.count_nonzero(buf == 0x0A)) + 1
    c = int(ncols)
    want_ptr = 0
    if want_offsets is not None:
        want_offsets = np.ascontiguousarray(want_offsets, dtype=np.uint8)
        want_ptr = want_offsets.ctypes.data
    starts, lens, vals, ok = _scratch(cap * c)
    starts, lens = starts[:cap * c], lens[:cap * c]
    vals, ok = vals[:cap * c], ok[:cap * c]
    reason = ctypes.c_longlong(0)
    esc_count = ctypes.c_longlong(0)
    got = L.csv_parse(
        ptr, n, sep_b, quote_b, cap, c, want_ptr,
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        vals.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
        ctypes.byref(reason), ctypes.byref(esc_count))
    if got < 0:
        return DECLINE_REASONS.get(int(reason.value), "ragged_rows")
    if got == 0:
        return "empty_range"
    r = int(got)
    # column-major with cap as the stride: each column's filled prefix
    # [j, :r] is contiguous. The esc mask only materializes when the
    # scan actually saw "" escapes (esc_count) — the common quote-free
    # case skips three full passes over the ok array.
    if int(esc_count.value):
        esc_full = ok & 0x80
        np.bitwise_and(ok, 0x7F, out=ok)
        esc = esc_full.astype(bool).reshape(c, cap)[:, :r]
    else:
        esc = None
    return (starts.reshape(c, cap)[:, :r], lens.reshape(c, cap)[:, :r],
            vals.reshape(c, cap)[:, :r], ok.reshape(c, cap)[:, :r], esc)


def chunk_bounds(data, sep: str, quote: str, targets):
    """Quote-safe byte-range boundaries: for each ascending byte target,
    the offset just past the first newline at/after it that sits OUTSIDE
    any quoted field (one native state-machine pass over the buffer).
    Returns an int64 array (possibly shorter than ``targets`` when the
    tail targets fall past the last safe newline), or None without the
    toolchain."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    t = np.ascontiguousarray(targets, dtype=np.int64)
    out = np.empty(max(len(t), 1), np.int64)
    got = L.csv_chunk_bounds(
        buf.ctypes.data, buf.size, sep.encode()[0:1],
        (quote or '"').encode()[0:1],
        t.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)), len(t),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)))
    return out[:max(int(got), 0)]


def enum_encode(data, starts, lens, max_card: int):
    """Dictionary-encode one column's tokens natively.

    ``starts``/``lens`` are the column's per-cell offsets from
    ``parse_bytes``. Returns ``(codes int32, uniq_rows int64)`` where
    ``uniq_rows[k]`` is the row whose cell first used dictionary id
    ``k`` — or None when the native path declines (no toolchain,
    cardinality above ``max_card``)."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    n = len(starts)
    # cardinality can never exceed n cells, so cap the dictionary buffer
    # by n — max_card is ~1M (8 MB) and dozens of workers run at once
    max_card = min(max_card, n)
    codes = np.empty(n, np.int32)
    uniq = np.empty(max(max_card, 1), np.int64)
    card = L.csv_enum_encode(
        buf.ctypes.data,
        starts.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int)), n,
        codes.ctypes.data_as(ctypes.POINTER(ctypes.c_int)),
        uniq.ctypes.data_as(ctypes.POINTER(ctypes.c_longlong)),
        max_card)
    if card < 0:
        return None
    return codes, uniq[:card]


# ---- nogil encode plane (ISSUE 16) ----------------------------------

def _ptr(a, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


def _gather_arena(nbytes: int):
    """Thread-local gather arena (token S-arrays, match flags): reused
    across calls like the parse scratch, so a worker's per-column
    gathers stop round-tripping the allocator. Same contract: consume
    the returned view before the next gather on this thread."""
    import numpy as np
    buf = getattr(_TLS, "gather", None)
    if buf is None or buf.size < nbytes:
        buf = np.empty(max(nbytes, 1 << 16), np.uint8)
        _TLS.gather = buf
    return buf


def arena_bytes() -> int:
    """This thread's total scratch-arena footprint (parse + gather), for
    the profiler's per-worker memory attribution."""
    total = 0
    bufs = getattr(_TLS, "bufs", None)
    if bufs is not None:
        total += sum(b.nbytes for b in bufs)
    g = getattr(_TLS, "gather", None)
    if g is not None:
        total += g.nbytes
    return total


def _pack_patterns(pats):
    """Concatenate byte patterns (NA strings) into (buf, offs, lens)."""
    import numpy as np
    bs = [p if isinstance(p, bytes) else str(p).encode("utf-8")
          for p in pats]
    offs = np.zeros(max(len(bs), 1), np.int64)
    lens = np.zeros(max(len(bs), 1), np.int32)
    o = 0
    for k, b in enumerate(bs):
        offs[k] = o
        lens[k] = len(b)
        o += len(b)
    return b"".join(bs) or b"\0", offs, lens


def gather_tokens(data, starts, lens, width: int = None):
    """Fixed-width token gather into an ``S{width}`` array — the native
    spelling of the numpy slab loop (_tokens_sarr). Returns a view into
    the thread-local gather arena (consume before the next call on this
    thread), or None without the toolchain."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    n = len(starts)
    if n == 0:
        return np.empty(0, dtype="S1")
    if width is None:
        width = max(int(lens.max()), 1)
    out = _gather_arena(n * width)[:n * width]
    L.csv_gather_tokens(buf.ctypes.data, _ptr(starts, ctypes.c_longlong),
                        _ptr(lens, ctypes.c_int), n, width,
                        out.ctypes.data)
    return out.view(f"S{width}")


def match_any(data, starts, lens, patterns):
    """Per-cell membership flags (bool array): cell bytes equal to any
    pattern — the NA-string test, without materializing tokens. None
    without the toolchain."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    n = len(starts)
    out = np.zeros(n, np.uint8)
    if n and patterns:
        pat_buf, offs, plens = _pack_patterns(patterns)
        pat = np.frombuffer(pat_buf, np.uint8)
        L.csv_match_any(buf.ctypes.data, _ptr(starts, ctypes.c_longlong),
                        _ptr(lens, ctypes.c_int), n,
                        pat.ctypes.data, _ptr(offs, ctypes.c_longlong),
                        _ptr(plens, ctypes.c_int), len(patterns),
                        _ptr(out, ctypes.c_ubyte))
    return out.view(bool)


def numeric_stats(vals, col_stride: int, col_idx, r0: int, nrows: int):
    """Detach selected numeric columns from the column-major parse arena
    and reduce them in one nogil pass. Returns ``(block, fmax, allfin)``
    — an owned ``[k, nrows]`` float64 block, per-column finite |max|
    (-inf when none), and per-column all-finite flags — or None without
    the toolchain."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    col_idx = np.ascontiguousarray(col_idx, dtype=np.int64)
    k = len(col_idx)
    block = np.empty((k, nrows), np.float64)
    fmax = np.empty(k, np.float64)
    allfin = np.empty(k, np.uint8)
    L.csv_numeric_stats(_ptr(vals, ctypes.c_double), col_stride,
                        _ptr(col_idx, ctypes.c_longlong), k, r0, nrows,
                        _ptr(block, ctypes.c_double),
                        _ptr(fmax, ctypes.c_double),
                        _ptr(allfin, ctypes.c_ubyte))
    return block, fmax, allfin.view(bool)


def count_rows(data, sep: str, quote: str = '"'):
    """Quote-aware row count of a buffer (csv_parse's row accounting,
    no per-cell work) — the multi-host range planner's cheap pass.
    Returns the count, or None (toolchain missing / open quote)."""
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    got = L.csv_count_rows(buf.ctypes.data, buf.size, sep.encode()[0:1],
                           (quote or '"').encode()[0:1])
    return int(got) if got >= 0 else None


def enum_encode_full(data, starts, lens, nas, max_card: int,
                     na_code: int, esc=None):
    """Full native enum encode: dictionary build, ""-unescape, NA map,
    sorted-domain dedupe and final code remap in one released-GIL call.
    Returns ``(codes int32, dom_rows int64, dom_esc bool)`` where entry
    ``k`` of ``dom_rows``/``dom_esc`` locates a representative cell for
    the k-th SORTED domain label (the caller decodes card labels — the
    only per-label Python left). None when the native path declines
    (no toolchain, cardinality above ``max_card``, or a non-UTF-8 label
    whose sort order native bytes cannot reproduce)."""
    import numpy as np
    L = lib()
    if L is None:
        return None
    buf = _as_u8(data)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    n = len(starts)
    nas = list(nas or ())
    max_card = min(max_card, max(n, 1))
    codes = np.empty(n, np.int32)
    dom_rows = np.empty(max_card + 1, np.int64)
    dom_esc = np.empty(max_card + 1, np.uint8)
    esc_ptr = 0
    if esc is not None:
        esc = np.ascontiguousarray(esc, dtype=np.uint8)
        esc_ptr = esc.ctypes.data
    pat_buf, offs, plens = _pack_patterns(nas)
    pat = np.frombuffer(pat_buf, np.uint8)
    card = L.csv_enum_encode_full(
        buf.ctypes.data, _ptr(starts, ctypes.c_longlong),
        _ptr(lens, ctypes.c_int), n, esc_ptr,
        pat.ctypes.data, _ptr(offs, ctypes.c_longlong),
        _ptr(plens, ctypes.c_int), len(nas),
        max_card, na_code,
        _ptr(codes, ctypes.c_int), _ptr(dom_rows, ctypes.c_longlong),
        _ptr(dom_esc, ctypes.c_ubyte))
    if card < 0:
        return None
    return codes, dom_rows[:card], dom_esc[:card].view(bool)
