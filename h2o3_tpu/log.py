"""Structured logging + per-phase timing.

Reference: water/util/Log.java (leveled log4j-backed logging, per-node
files, buffered pre-init, served at /3/Logs) and MRTask's MRProfile
(water/MRTask.java:190-194,321 — per-phase timings surfaced with the
task).

TPU re-design: one stdlib logger with an in-memory ring buffer (the
/3/Logs source — there is one controller process, no per-node files) and
a ``Profile`` that accumulates named phase durations; builders attach it
to ``model.output['profile']`` so timings travel with the model the way
MRProfile travels with the task."""
from __future__ import annotations

import collections
import logging
import os
import sys
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

_BUFFER = collections.deque(maxlen=10000)
_BUF_LOCK = threading.Lock()


class _RingHandler(logging.Handler):
    def emit(self, record):
        with _BUF_LOCK:
            _BUFFER.append(self.format(record))


def _build_logger() -> logging.Logger:
    lg = logging.getLogger("h2o3_tpu")
    if lg.handlers:
        return lg
    level = os.environ.get("H2O3_LOG_LEVEL", "INFO").upper()
    lg.setLevel(getattr(logging, level, logging.INFO))
    fmt = logging.Formatter(
        "%(asctime)s.%(msecs)03d %(levelname)-5s %(name)s: %(message)s",
        datefmt="%H:%M:%S")
    ring = _RingHandler()
    ring.setFormatter(fmt)
    lg.addHandler(ring)
    if os.environ.get("H2O3_LOG_STDERR", "1") != "0":
        sh = logging.StreamHandler(sys.stderr)
        sh.setFormatter(fmt)
        lg.addHandler(sh)
    lg.propagate = False
    return lg


logger = _build_logger()
debug = logger.debug
info = logger.info
warn = logger.warning
error = logger.error


def buffered_lines(n: int = 1000) -> List[str]:
    """Recent log lines (the /3/Logs source)."""
    with _BUF_LOCK:
        return list(_BUFFER)[-n:]


# ---------------------------------------------------------------- timeline

# water/TimeLine.java: a lock-free per-node ring buffer of runtime events
# snapshotted at /3/Timeline. Here: a bounded deque of (ts, kind, detail)
# fed by training drivers / REST handlers; thread-safe via one lock (the
# single-controller design has no per-node rings to merge).
_TIMELINE: "deque" = None  # type: ignore[assignment]
_TL_LOCK = threading.Lock()
_TL_CAP = 2048


def timeline_record(kind: str, detail: str) -> None:
    global _TIMELINE
    with _TL_LOCK:
        if _TIMELINE is None:
            from collections import deque
            _TIMELINE = deque(maxlen=_TL_CAP)
        _TIMELINE.append({"ts": time.time(), "kind": kind,
                          "detail": detail})


def timeline_events(n: int = 2048) -> List[Dict]:
    with _TL_LOCK:
        return list(_TIMELINE or [])[-n:]


class Profile:
    """Per-phase wall-time accumulator (MRProfile analog). Phases may
    repeat; durations accumulate. Not thread-safe by design — one Profile
    per training driver, like one MRProfile per MRTask.

    Telemetry: every phase IS a ``{prefix}{name}`` span of
    h2o3_tpu.telemetry, and the phase's seconds are that span's duration
    (the phase's own clock only where telemetry is off), so the stage
    split that travels with the model and the one /metrics exports are
    the same numbers. ``parent_span`` is the training driver's root span
    — set by ModelBuilder.train and handed across the job thread; with
    none, a phase nests under the calling thread's current span."""

    def __init__(self, prefix: str = "train.", parent_span=None):
        self.phases: Dict[str, float] = {}
        self._order: List[str] = []
        self.prefix = prefix
        self.parent_span = parent_span

    @contextmanager
    def phase(self, name: str):
        """Yields the phase's span (None where telemetry is off), for
        attributes known only at the end of the phase."""
        from h2o3_tpu import telemetry
        t0 = time.perf_counter()
        sp = None
        try:
            # a REAL span (thread-local) so nested stage spans inside the
            # phase (gbm's bin/loop/score/finalize) parent implicitly; an
            # exception passes through its exit, which notes the failed
            # stage (jobs.py reads it for /3/Jobs failed_stage)
            with telemetry.span(self.prefix + name,
                                parent=self.parent_span) as sp:
                yield sp
        finally:
            self._accumulate(name, sp.duration_s if sp is not None
                             else time.perf_counter() - t0)

    def _accumulate(self, name: str, dt: float):
        if name not in self.phases:
            self._order.append(name)
        self.phases[name] = self.phases.get(name, 0.0) + dt

    def add(self, name: str, seconds: float):
        from h2o3_tpu import telemetry
        telemetry.record_span(
            self.prefix + name,
            time.time() - seconds, seconds,  # h2o3-lint: allow[monotonic-durations] wall START anchor reconstructed from an already-measured duration, for span reporting
            parent=self.parent_span)
        self._accumulate(name, seconds)

    def to_dict(self) -> Dict[str, float]:
        return {k: round(self.phases[k], 4) for k in self._order}

    def summary(self) -> str:
        total = sum(self.phases.values())
        parts = [f"{k}={self.phases[k]:.2f}s" for k in self._order]
        return f"total={total:.2f}s " + " ".join(parts)


def stack_samples(depth: int = 10, samples: int = 20,
                  interval: float = 0.01) -> List[Dict]:
    """Aggregated thread-stack samples — the water/util/JProfile analog
    behind GET /3/Profiler (water/api/ProfilerHandler.java samples JVM
    stacktraces per node and aggregates identical traces with counts).
    Here: sys._current_frames() sampled `samples` times; identical
    truncated traces aggregate; entries sort by count descending."""
    import sys
    import traceback
    agg: Dict[str, int] = {}
    me = threading.get_ident()
    for _ in range(max(samples, 1)):
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            stack = traceback.extract_stack(frame)[-depth:]
            text = "\n".join(
                f"{f.filename}:{f.lineno} in {f.name}" for f in stack)
            agg[text] = agg.get(text, 0) + 1
        time.sleep(interval)
    return [{"stacktrace": k, "count": v}
            for k, v in sorted(agg.items(), key=lambda kv: -kv[1])]
