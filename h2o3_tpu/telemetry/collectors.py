"""Device-aware collectors: the telemetry the JVM-era tools can't see.

- **Compile counter** (production promotion of tests/_compile_counter.py):
  a ``jax.monitoring`` duration listener counts every
  ``/jax/core/compile/backend_compile_duration`` event into
  ``h2o3_xla_compiles_total`` + a duration histogram. JAX wraps that
  event around ``compile_or_get_cached``, so it fires once per jit-cache
  miss that reached the backend, whether the executable was built or
  loaded from the persistent cache: zero over a window is the warm-path
  guarantee the test harness proves, as a metric production can watch.
- **Jit stages**: what the host pays on a jit-cache miss, counted where
  it happens: spans ``jit.trace`` (jaxpr trace), ``jit.lower`` (jaxpr to
  MLIR module), ``jit.load`` (executable read from the persistent cache)
  and ``jit.build`` (backend compile with no cache hit inside it), one
  of each under the span that was open on the calling thread
  (``spans.fold_span``): its seconds are the stage's host time there,
  attr ``n`` the number of events, attr ``top`` the up to five
  ``[fun_name, seconds]`` of most seconds among them: JAX hands the
  program's name over with every one of these events (``fun_name``);
  a ``jit.load`` has none of its own and takes the name of the
  ``backend_compile_duration`` event that closes around it.
  ``h2o3_span_seconds{span="jit.*"}`` sums the seconds for /metrics; no
  series carries a name (unbounded cardinality). None of these events
  fires on a warm dispatch.
- **Compile-cache hit/miss**: the persistent-compile-cache events
  (``/jax/compilation_cache/cache_hits`` / ``cache_misses``).
- **Transfer bytes**: ``record_h2d``/``record_d2h`` counters called from
  the frame layer's transfer choke points (``batch_device_put`` /
  ``Vec.to_numpy`` / spill).
- **Device memory**: a scrape-time view over ``memory_stats()`` (TPU)
  falling back to summing ``jax.live_arrays()`` (CPU backend), plus a
  peak gauge updated at every scrape and h2d record.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

from h2o3_tpu.telemetry.registry import on_reset, registry

_INSTALL_LOCK = threading.Lock()
_INSTALLED = [False]

BACKEND_COMPILE_SUFFIX = "backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# emitted on a persistent-cache hit only, on the compiling thread, inside
# the backend_compile_duration event that then closes around it
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
JIT_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    CACHE_RETRIEVAL_EVENT: "load",
}
_TLS = threading.local()


def _compiles():
    return registry().counter(
        "h2o3_xla_compiles_total",
        help="executables built or loaded from the persistent cache "
             "(one per jit-cache miss that reached the backend)")


def _cache_hits():
    return registry().counter(
        "h2o3_compile_cache_hits_total",
        help="persistent compile cache hits")


def _cache_misses():
    return registry().counter(
        "h2o3_compile_cache_misses_total",
        help="persistent compile cache misses")


def _duration_listener(key: str, dur: float, fun_name=None, **_kw) -> None:
    dur = float(dur)
    start = time.time() - dur  # h2o3-lint: allow[monotonic-durations] wall START anchor reconstructed from a duration JAX reports after the fact
    stage = JIT_STAGE_EVENTS.get(key)
    if stage is None:
        if not key.endswith(BACKEND_COMPILE_SUFFIX):
            return
        _compiles().inc()
        registry().histogram(
            "h2o3_xla_compile_seconds",
            help="XLA backend compile durations").observe(dur)
        loaded, _TLS.loaded = getattr(_TLS, "loaded", None), None
        if loaded is not None:
            # a load: the retrieval's own interval, under this event's name
            stage, (start, dur) = "load", loaded
        else:
            stage = "build"
    elif key == CACHE_RETRIEVAL_EVENT:
        # JAX names no program here; the backend_compile_duration event
        # that closes around it does, and the load is folded then
        _TLS.loaded = (start, dur)
        return
    from h2o3_tpu.telemetry.spans import fold_span
    fold_span(f"jit.{stage}", start, dur,
              label=None if fun_name is None else str(fun_name))


def _event_listener(key: str, **_kw) -> None:
    if key == CACHE_HIT_EVENT:
        _cache_hits().inc()
    elif key == CACHE_MISS_EVENT:
        _cache_misses().inc()


def install() -> bool:
    """Register the jax.monitoring listeners + the device-memory view.
    Idempotent; safe to call from cluster boot, bench, server start and
    tests. Returns True when the listeners are (already) live."""
    with _INSTALL_LOCK:
        if _INSTALLED[0]:
            return True
        try:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _duration_listener)
            jax.monitoring.register_event_listener(_event_listener)
        except Exception:          # jax without monitoring: gate, don't die
            return False
        # touch the counters so a zero-compile process still exports them
        _compiles(), _cache_hits(), _cache_misses()
        registry().add_collector(_device_memory_samples)
        _INSTALLED[0] = True
        return True


def installed() -> bool:
    return _INSTALLED[0]


# ---------------------------------------------------------------- bytes

# transfer counters sit at the frame-layer choke points — hold the
# handles instead of paying the registry creation mutex per transfer.
# Cleared by Registry.reset() on the global registry.
_BYTE_HANDLES: Dict[str, object] = {}
on_reset(_BYTE_HANDLES.clear)

# pipelines a transfer can be attributed to (the label set is closed so
# a typo'd span name can't mint unbounded label cardinality)
_PIPELINES = frozenset(
    {"ingest", "train", "serve", "analytics", "rapids", "frame"})


def _byte_counter(name: str, help_: str, pipeline: Optional[str] = None):
    key = name if pipeline is None else f"{name}|{pipeline}"
    c = _BYTE_HANDLES.get(key)
    if c is None:
        labels = {"pipeline": pipeline} if pipeline is not None else None
        c = registry().counter(name, labels, help=help_)
        _BYTE_HANDLES[key] = c
    return c


def _infer_pipeline() -> Optional[str]:
    """Attribute a transfer to the pipeline whose span is open on this
    thread (ingest.parse / train.* / serve.* roots all thread their
    stage work), so Vec.to_numpy-style chokepoints need no plumbing."""
    from h2o3_tpu.telemetry.spans import current_span
    sp = current_span()
    if sp is None:
        return None
    head = sp.name.split(".", 1)[0]
    return head if head in _PIPELINES else None


def _record_bytes(direction: str, nbytes: int,
                  pipeline: Optional[str],
                  fallback: Optional[str] = None) -> None:
    help_ = f"{direction} transfer bytes"
    _byte_counter(f"h2o3_{direction}_bytes_total", help_).inc(float(nbytes))
    p = pipeline if pipeline in _PIPELINES else _infer_pipeline()
    if p is None and fallback in _PIPELINES:
        # sharded frame-layer transfers issued with NO span open
        # (Frame.resharded, ad-hoc host fetches) used to vanish from
        # the pipeline-labeled counters (ISSUE 8) — the caller's
        # fallback label catches them WITHOUT overriding span inference
        p = fallback
    if p is not None:
        _byte_counter(f"h2o3_{direction}_pipeline_bytes_total",
                      f"{direction} transfer bytes by pipeline",
                      p).inc(float(nbytes))


def record_h2d(nbytes: int, pipeline: Optional[str] = None,
               fallback: Optional[str] = None) -> None:
    """Host→device transfer bytes (batch_device_put / _pad_and_put /
    the streamed chunk uploads). ``pipeline`` attributes the bytes to
    ingest/train/serve/analytics/rapids; when omitted, the open span on
    the calling thread decides, then ``fallback``."""
    if not registry().enabled:
        return
    _record_bytes("h2d", nbytes, pipeline, fallback)


def record_d2h(nbytes: int, pipeline: Optional[str] = None,
               fallback: Optional[str] = None) -> None:
    """Device→host fetch bytes (Vec.to_numpy / spill / device_get)."""
    if not registry().enabled:
        return
    _record_bytes("d2h", nbytes, pipeline, fallback)


def record_d2d(nbytes: int, pipeline: Optional[str] = None) -> None:
    """Device→device move bytes: the stitched sharded-ingest assembly's
    boundary-fragment moves and model-axis replica copies (ISSUE 8 —
    these used to escape the transfer counters entirely, hiding a
    misaligned chunk-home mapping's real cost)."""
    if not registry().enabled:
        return
    _record_bytes("d2d", nbytes, pipeline)


def _tree_nbytes(host) -> int:
    """Byte count of a fetched pytree of numpy arrays/scalars."""
    import numpy as np
    total = 0
    stack = [host]
    while stack:
        x = stack.pop()
        if isinstance(x, dict):
            stack.extend(x.values())
        elif isinstance(x, (list, tuple)):
            stack.extend(x)
        else:
            total += getattr(x, "nbytes", 0) or np.asarray(x).nbytes
    return total


def device_get(x, pipeline: Optional[str] = None):
    """Counted ``jax.device_get`` behind the ``d2h`` fault seam: the
    d2h byte counters see ad-hoc fetches (analytics/rapids, model
    finalize), not just the frame-layer choke points, and chaos specs
    can fail the fetch path. Returns the host pytree unchanged."""
    import jax
    from h2o3_tpu import faults
    if faults.ACTIVE:
        faults.check("d2h", pipeline=pipeline)
    host = jax.device_get(x)
    if registry().enabled:
        record_d2h(_tree_nbytes(host), pipeline=pipeline)
    return host


# ---------------------------------------------------------- device memory

def device_memory_bytes() -> Dict[str, Optional[float]]:
    """Live/peak device memory. TPU backends expose memory_stats();
    the CPU backend doesn't, so fall back to summing live jax arrays
    (an upper-bound view of OUR allocations, good enough to trend)."""
    live = peak = None
    try:
        import jax
        stats = [d.memory_stats() for d in jax.local_devices()]
        stats = [s for s in stats if s]
        if stats:
            live = float(sum(s.get("bytes_in_use", 0) for s in stats))
            peak = float(sum(s.get("peak_bytes_in_use", 0) for s in stats))
        else:
            live = float(sum(getattr(a, "nbytes", 0)
                             for a in jax.live_arrays()))
    except Exception:
        pass
    return {"live": live, "peak": peak}


def sample_device_memory() -> Dict[str, Optional[float]]:
    """Measure device memory now and fold it into the peak gauge —
    called at scrape time and from bench round boundaries."""
    mem = device_memory_bytes()
    reg = registry()
    if reg.enabled and mem["live"] is not None:
        g = reg.gauge("h2o3_device_peak_bytes",
                      help="peak observed live device bytes")
        g.set_max(mem["peak"] if mem["peak"] is not None else mem["live"])
    return mem


def _device_memory_samples() -> List[dict]:
    mem = sample_device_memory()
    out = []
    if mem["live"] is not None:
        out.append({"name": "h2o3_device_live_bytes", "kind": "gauge",
                    "value": mem["live"],
                    "help": "live device memory bytes"})
    return out
