"""Shared xprof/jax.profiler capture helper (the SNIPPETS [1] shape).

``tools/profile_train.py --xprof-trace`` grew an inline trace-dir dance
(arg parsing, default dirs, graceful degradation when the profiler is
unavailable); the other profilers needed the same thing, so the pattern
lives here once:

    from h2o3_tpu.telemetry.profiling import profile
    with profile("warm_train"):            # no-op unless a dir resolves
        gbm.train(...)

``profile(name, trace_dir=...)`` wraps the block in a
``jax.profiler`` trace writing to ``<dir>/<name>`` — open the dump with
xprof/tensorboard (``python -m xprof.server DIR`` or
``tensorboard --logdir DIR``) for kernel-level attribution (per-level
fused-histogram kernels, the ICI psum all-reduce on the device
timeline). The trace is started with the Python tracer off and the host
tracer at level 2, as ``benchmark/run.py`` starts its own: the host
lines then hold the program's ``telemetry.span`` annotations, the span
tree an operator sees on ``/3/Timeline?format=trace``, beside the device
planes and not buried in one event per Python frame. Trace-dir
resolution, in priority order:

1. the explicit ``trace_dir=`` argument;
2. ``--xprof-trace [DIR]`` on ``sys.argv`` (the shared tools/ CLI
   contract; bare ``--xprof-trace`` mints a /tmp dir);
3. the ``XPROF_TRACE_DIR`` env var;
4. nothing → the context manager is a no-op (zero overhead).

Capture failures degrade to a warning — profiling must never sink the
run being profiled. An in-flight capture's directory is readable via
``last_trace_dir()`` (the tools put it in their JSON output).
"""
from __future__ import annotations

import os
import sys
import time
from typing import Optional

_LAST_DIR: list = [None]


def trace_dir_from_argv(argv: Optional[list] = None,
                        flag: str = "--xprof-trace") -> Optional[str]:
    """The shared CLI spelling: ``--xprof-trace [DIR]`` (bare flag mints
    a /tmp dir), else ``XPROF_TRACE_DIR``, else None."""
    argv = sys.argv if argv is None else argv
    if flag in argv:
        i = argv.index(flag)
        if i + 1 < len(argv) and not argv[i + 1].startswith("-"):
            return argv[i + 1]
        return os.path.join("/tmp", f"h2o3_xprof_{int(time.time())}")
    return os.environ.get("XPROF_TRACE_DIR") or None


def last_trace_dir() -> Optional[str]:
    """Directory of the most recent successful capture (None if the
    last ``profile()`` was a no-op or failed to start)."""
    return _LAST_DIR[0]


class profile:
    """``with profile("name"):`` — jax.profiler capture of the block
    into ``<trace_dir>/<name>``; a checked no-op when no dir resolves
    or the profiler refuses (double-start, missing backend support)."""

    def __init__(self, name: str, trace_dir: Optional[str] = None,
                 log=None):
        self.name = str(name)
        self.trace_dir = trace_dir if trace_dir is not None \
            else trace_dir_from_argv()
        self.dir: Optional[str] = None
        self._log = log or (lambda *a: print(*a, file=sys.stderr,
                                             flush=True))
        self._active = False

    def __enter__(self) -> "profile":
        _LAST_DIR[0] = None
        if not self.trace_dir:
            return self
        self.dir = os.path.join(self.trace_dir, self.name)
        try:
            import jax
            os.makedirs(self.dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0    # the spans, not the frames
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self._active = True
            _LAST_DIR[0] = self.dir
            self._log(f"xprof: tracing '{self.name}' -> {self.dir}")
        except Exception as e:   # profiling must never sink the run
            self._log(f"xprof trace unavailable: {e!r}")
            self.dir = None
        return self

    def __exit__(self, *exc) -> bool:
        if self._active:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception as e:
                self._log(f"xprof stop failed: {e!r}")
                _LAST_DIR[0] = None
                self.dir = None
            self._active = False
        return False
