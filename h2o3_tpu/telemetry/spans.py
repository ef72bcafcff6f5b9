"""End-to-end spans: nested timing contexts with cross-thread handoff.

A ``span("train.gbm.loop", job=...)`` context manager times a stage and
records it in four sinks:

- **histogram**: a per-name duration histogram in the metrics registry
  (``h2o3_span_seconds{span=...}``), the aggregate view the profiler
  tools and /metrics read;
- **ring**: an entry in a bounded ring of finished spans, the raw view
  behind ``GET /3/Timeline?format=trace`` (Chrome-trace/Perfetto export)
  and the benchmark's ``span_ring`` reader;
- **timeline**: for ROOT spans (no parent), an event in the existing
  ``log.timeline_record`` ring, so Flow's /3/Timeline shows ingest and
  serve activity, not just model builds;
- **profiler**: a ``jax.profiler.TraceAnnotation`` of the same name held
  open for the block, so while a profiler session runs the span is an
  event on the calling thread's host line, on the clock the device
  planes share. A device-idle gap can then be named by the program's own
  span. With no session the annotation is a flag test.

Which call reaches which sink:

=================  =========  ====  ========  ========
call               histogram  ring  timeline  profiler
=================  =========  ====  ========  ========
``span()``         yes        yes   if root   yes
``open_span()``    yes        yes   if root   no
``record_span()``  yes        yes   if root   no
``fold_span()``    yes        yes   if root   no
=================  =========  ====  ========  ========

``open_span`` may finish on another thread and ``record_span`` writes an
interval that is already over; a profiler annotation has to be entered
and left on one thread while the work runs, so neither can make one.

What a process leaves before its first model (ISSUE 37): root
``boot.import`` (``record_span``: the package's own import, from the
first line of ``h2o3_tpu/__init__.py`` to its last, over when it can be
written); root ``boot.init`` (``span()`` around ``h2o3_tpu.init``);
then the first root ``train.*``, whose ``jit.*`` children say by name
what was traced, lowered, loaded and built (attr ``top``, below).

Parentage: within a thread, nesting is implicit (a thread-local stack).
Across threads — the micro-batcher's submit/batch/collect trio, the
training job thread — the parent is handed off EXPLICITLY: capture
``current_span()`` (or the ``Span`` yielded by the context manager) in
one thread and pass it as ``span(..., parent=handle)`` or
``record_span(..., parent=handle)`` in another. A ``Span`` handle stays
valid after it finishes; linking to a finished parent is fine (the
batcher's collector thread finishes children after the batch root).

Stage splits that travel with a result (``log.Profile``, and through it
gbm's ``train_profile``) take each stage's seconds from the span that
timed it, so the REST-reported and tool-reported splits cannot disagree.
``record_span`` is for intervals nobody could wrap: one that ends on
another thread (the batcher) or a duration reported after the fact.
``fold_span`` is ``record_span`` for reports that come by the hundred
under one span (the collectors' ``jit.*``: JAX reports each trace, lower
and load when it is over): they become ONE child a name of the span
they fell in, so the ring keeps the spans an operator looks for. The
child carries attr ``n``, the reports it folded, and, where they came
with a label (the collectors pass the program's name), attr ``top``: the
up to five ``[label, seconds]`` of most seconds among them. The names
live on spans, which the ring bounds; no series is labelled by them.
"""
from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Dict, List, Optional

from h2o3_tpu.telemetry.registry import on_reset, registry
from h2o3_tpu.telemetry.trace import current_trace_id


def _env_ring_cap() -> int:
    """Finished-span ring capacity (``H2O3_SPAN_RING``, default 8192).
    Bounded below at 16 so a typo cannot silently discard every span."""
    try:
        return max(int(os.environ.get("H2O3_SPAN_RING", "8192")), 16)
    except ValueError:
        return 8192


_RING_CAP = _env_ring_cap()
# eviction is EXPLICIT (no deque maxlen): a full ring pops the oldest
# span and counts it in h2o3_spans_dropped_total, so trace loss under
# load is a visible metric instead of a silent wraparound (PR-4 gap)
_RING: "collections.deque" = collections.deque()
_RING_LOCK = threading.Lock()
_DROPPED_HANDLE: List[object] = []


def _dropped_counter():
    if not _DROPPED_HANDLE:
        _DROPPED_HANDLE.append(registry().counter(
            "h2o3_spans_dropped_total",
            help="finished spans evicted from the full span ring "
                 "(raise H2O3_SPAN_RING to keep more)"))
    return _DROPPED_HANDLE[0]


def set_ring_capacity(cap: int) -> None:
    """Resize the finished-span ring (test/boot use; normally set once
    via H2O3_SPAN_RING). Shrinking drops-and-counts the oldest spans."""
    global _RING_CAP
    cap = max(int(cap), 16)
    dropped = 0
    with _RING_LOCK:
        _RING_CAP = cap
        while len(_RING) > cap:
            _RING.popleft()
            dropped += 1
    if dropped:
        _dropped_counter().inc(dropped)


_IDS = itertools.count(1)
_TLS = threading.local()
# jax.profiler.TraceAnnotation, imported by the first live span: this
# module stays importable without jax (tools/blackbox_read.py)
_ANNOTATION: List[type] = []


def _annotation(name: str):
    if not _ANNOTATION:
        from jax.profiler import TraceAnnotation
        _ANNOTATION.append(TraceAnnotation)
    return _ANNOTATION[0](name)

# span-duration histogram bounds: 10µs (a serve decode) … 1000s (a cold
# AutoML build)
_SPAN_BOUNDS = (1e-5, 1e-4, 1e-3, 0.01, 0.1, 1.0, 10.0, 100.0, 1000.0)

# per-name histogram handle cache: span finish sits on the serve hot
# path, and going through Registry._get would serialize every finishing
# thread on the registry-wide creation mutex. A racy double-create is
# harmless (Registry._get dedups to one instance). Cleared by
# Registry.reset() on the global registry.
_HIST_CACHE: Dict[str, object] = {}
on_reset(_HIST_CACHE.clear)
on_reset(_DROPPED_HANDLE.clear)


def _span_hist(name: str):
    h = _HIST_CACHE.get(name)
    if h is None:
        h = registry().histogram(
            "h2o3_span_seconds", {"span": name},
            help="finished span durations by span name",
            bounds=_SPAN_BOUNDS)
        _HIST_CACHE[name] = h
    return h


class Span:
    __slots__ = ("name", "attrs", "span_id", "parent_id", "thread_id",
                 "t_wall", "t0", "duration_s", "trace_id", "annotation",
                 "folded")

    def __init__(self, name: str, parent: Optional["Span"] = None,
                 attrs: Optional[Dict] = None):
        self.name = name
        self.attrs = attrs or {}
        self.span_id = next(_IDS)
        self.parent_id = parent.span_id if parent is not None else 0
        self.thread_id = threading.get_ident()
        self.t_wall = time.time()
        self.t0 = time.perf_counter()
        self.duration_s: Optional[float] = None
        self.annotation = None      # span(): its open profiler annotation
        # fold_span(): {name: [reports, [(start_wall, seconds, label), ...]]}
        self.folded: Optional[Dict[str, list]] = None
        # trace linkage: the thread's bound trace id wins (the REST
        # handler / job thread bound it), else inherit the parent's —
        # which is how a child recorded on the batcher's collector
        # thread keeps the submitting request's trace
        self.trace_id: Optional[str] = current_trace_id() or (
            parent.trace_id if parent is not None else None)

    def finish(self) -> "Span":
        if self.duration_s is None:
            self.duration_s = time.perf_counter() - self.t0
            if self.folded:
                for name, (n, kept) in self.folded.items():
                    record_span(name, kept[0][0], sum(k[1] for k in kept),
                                parent=self, n=n, **_top(kept))
                self.folded = None
            _record_finished(self)
        return self

    def __repr__(self):
        d = f"{self.duration_s * 1e3:.2f}ms" if self.duration_s else "open"
        return f"<Span {self.name}#{self.span_id} {d}>"


def _stack() -> List[Span]:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def current_span() -> Optional[Span]:
    """The innermost open span on THIS thread (the handoff handle)."""
    st = getattr(_TLS, "stack", None)
    return st[-1] if st else None


def _note_error_span(name: str, exc: BaseException) -> None:
    """Remember the INNERMOST span a given exception unwound through:
    the innermost context exits first, so only the first note per
    exception identity sticks — outer spans exiting with the same
    exception don't overwrite it. Job supervision reads this to report
    the failed pipeline stage on /3/Jobs."""
    cur = getattr(_TLS, "last_error", None)
    if cur is None or cur[0] != id(exc):
        _TLS.last_error = (id(exc), name)


def last_error_span(exc: Optional[BaseException] = None) -> Optional[str]:
    """Name of the innermost span the given (or most recent) exception
    failed inside on THIS thread; None if no span saw it."""
    cur = getattr(_TLS, "last_error", None)
    if cur is None:
        return None
    if exc is not None and cur[0] != id(exc):
        return None
    return cur[1]


# timeline throttle: the Flow ring is 2048 entries — at serve rates
# (hundreds of serve.request/serve.batch roots per second) unthrottled
# feeding would wrap it in seconds, evicting the train/ingest events the
# endpoint exists to show. One event per span NAME per second keeps
# serve activity visible without monopolizing the ring (the full-rate
# record stays in the span ring for ?format=trace). Racy reads are fine:
# worst case two threads both pass the gate and two events land.
_TL_LAST: Dict[str, float] = {}
_TL_MIN_INTERVAL_S = 1.0


def _record_finished(sp: Span) -> None:
    if not registry().enabled:
        return
    _span_hist(sp.name).observe(sp.duration_s)
    dropped = 0
    with _RING_LOCK:
        _RING.append(sp)
        while len(_RING) > _RING_CAP:
            _RING.popleft()
            dropped += 1
    if dropped:
        _dropped_counter().inc(dropped)
    if sp.parent_id == 0:
        # root spans feed the Flow timeline ring (train_start/train_done
        # style events now cover ingest and serve too)
        now = time.monotonic()   # rate-limit interval, not an epoch
        if now - _TL_LAST.get(sp.name, 0.0) < _TL_MIN_INTERVAL_S:
            return
        _TL_LAST[sp.name] = now
        from h2o3_tpu import log
        extra = " ".join(f"{k}={v}" for k, v in sp.attrs.items())
        if sp.trace_id:
            extra = (extra + " " if extra else "") + f"trace={sp.trace_id}"
        log.timeline_record(
            sp.name, f"{sp.duration_s * 1e3:.1f} ms"
            + (f" {extra}" if extra else ""))


class _SpanContext:
    """Context manager wrapper: pushes/pops the thread-local stack so
    nested ``span()`` calls parent implicitly, and holds the profiler
    annotation of the same name open for the block."""
    __slots__ = ("_span", "_name", "_parent", "_attrs")

    def __init__(self, name, parent, attrs):
        self._name = name
        self._parent = parent
        self._attrs = attrs
        self._span: Optional[Span] = None

    def __enter__(self) -> Optional[Span]:
        if not registry().enabled:
            return None
        parent = self._parent if self._parent is not None \
            else current_span()
        annotation = _annotation(self._name)
        annotation.__enter__()
        sp = Span(self._name, parent, self._attrs)
        sp.annotation = annotation
        _stack().append(sp)
        self._span = sp
        return sp

    def __exit__(self, exc_type=None, exc_value=None, tb=None):
        sp = self._span
        if sp is None:
            return False
        if exc_value is not None:
            sp.attrs["error"] = True
            _note_error_span(sp.name, exc_value)
        st = _stack()
        # pop by identity, innermost first: an exception may have skipped
        # inner exits, and each span leaves its own annotation
        while sp.annotation is not None:
            top = st.pop() if st else sp
            top.finish()
            top.annotation.__exit__(exc_type, exc_value, tb)
            top.annotation = None
        return False


def span(name: str, parent: Optional[Span] = None, **attrs) -> _SpanContext:
    """``with span("ingest.parse", rows=n) as sp: ...`` — times the
    block; nesting is implicit per thread, ``parent=`` makes it
    explicit (cross-thread handoff)."""
    return _SpanContext(name, parent, attrs)


def open_span(name: str, parent: Optional[Span] = None,
              **attrs) -> Optional[Span]:
    """Start a span WITHOUT entering the thread-local stack — for spans
    that end on a different thread (the batcher's per-batch root), which
    is also why it makes no profiler annotation.
    Finish with ``sp.finish()``. Returns None when telemetry is off."""
    if not registry().enabled:
        return None
    return Span(name, parent, attrs)


def record_span(name: str, start_wall: float, duration_s: float,
                parent: Optional[Span] = None, **attrs) -> Optional[Span]:
    """Record an already-measured interval as a finished span: one that
    ended on another thread, or a duration reported after the fact.
    ``parent`` defaults to the calling thread's current span. The
    interval is over, so it cannot be a profiler annotation: it reaches
    histogram, ring and timeline only."""
    if not registry().enabled:
        return None
    sp = Span(name, parent if parent is not None else current_span(), attrs)
    sp.t_wall = start_wall
    sp.duration_s = float(duration_s)
    _record_finished(sp)
    return sp


TOP_LABELS = 5


def _top(kept) -> Dict[str, list]:
    """Attr ``top`` of a folded child: the up to five ``[label, seconds]``
    of most seconds among the labelled reports it kept, a label's
    reports summed; nothing where no report carried a label."""
    by_label: Dict[str, float] = {}
    for _, seconds, label in kept:
        if label is not None:
            by_label[label] = by_label.get(label, 0.0) + seconds
    if not by_label:
        return {}
    ranked = sorted(by_label.items(), key=lambda kv: -kv[1])[:TOP_LABELS]
    return {"top": [[label, seconds] for label, seconds in ranked]}


def fold_span(name: str, start_wall: float, duration_s: float,
              label: Optional[str] = None) -> None:
    """Fold an already-measured interval into ONE child span ``name`` of
    the calling thread's current span. The child is written when that
    span finishes, with the intervals' summed seconds and their number as
    attr ``n``, so a report that comes by the hundred (a boost chunk's
    trace holds 1,534 nested ones) costs the ring one entry a parent. An
    interval that holds earlier ones of its name replaces them in the
    sum: JAX reports an outer function's trace after the inner traces it
    contains, and the seconds are host time, counted once. ``label``
    says what the interval was spent on (the collectors pass the
    program's name): the child then carries attr ``top``, the up to five
    ``[label, seconds]`` of most seconds among the reports it kept.
    With no open span the interval is recorded at once, as
    ``record_span`` does."""
    if not registry().enabled:
        return
    report = (start_wall, float(duration_s), label)
    parent = current_span()
    if parent is None:
        record_span(name, start_wall, duration_s, n=1, **_top([report]))
        return
    if parent.folded is None:
        parent.folded = {}
    acc = parent.folded.setdefault(name, [0, []])
    acc[0] += 1
    kept = acc[1]
    while kept and kept[-1][0] > start_wall:
        kept.pop()
    kept.append(report)


def finished_spans(n: Optional[int] = None) -> List[Span]:
    """The most recent ``n`` finished spans (default: the whole ring).
    ``n=0`` means ZERO spans — the spanless-snapshot spelling — not
    "everything"."""
    if n is None:
        n = _RING_CAP
    if n <= 0:
        return []
    with _RING_LOCK:
        return list(_RING)[-n:]


def clear_spans() -> None:
    """Test isolation only."""
    with _RING_LOCK:
        _RING.clear()


def stage_seconds(prefix: str = "",
                  samples: Optional[List[dict]] = None
                  ) -> Dict[str, Dict[str, float]]:
    """Aggregate stage totals from the span-duration histograms:
    ``{span_name: {count, seconds}}`` — the view the profiler tools
    read, identical by construction to what /metrics exports. Pass an
    existing ``registry().samples()`` list to avoid a second scrape
    (each scrape runs the collector views, incl. a device-memory
    walk)."""
    out: Dict[str, Dict[str, float]] = {}
    for s in (samples if samples is not None else registry().samples()):
        if s["name"] != "h2o3_span_seconds" or s["kind"] != "histogram":
            continue
        name = s["labels"].get("span", "")
        if prefix and not name.startswith(prefix):
            continue
        out[name] = {"count": s["count"], "seconds": round(s["sum"], 6)}
    return out
