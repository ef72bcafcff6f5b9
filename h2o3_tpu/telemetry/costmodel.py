"""Performance accounting: per-executable FLOP/byte attribution, honest
MFU and roofline placement (ISSUE 11).

The telemetry plane (PR 4/8) says where TIME goes; this module says what
the hardware COULD have done with it. At every jit seam the compile
counter already watches (GBM/DRF ``_compiled_chunk`` dispatch, the
streamed-GBM level kernels, serve bucket executables, the frame rollup
reduction) the lowered program's XLA cost analysis (``flops``, ``bytes
accessed``) is captured ONCE per cached executable and paired with
measured device time at the existing commit seams, yielding:

- ``achieved_flops`` / ``achieved_bytes_per_s`` — executed work over
  measured device-saturated wall time;
- ``arith_intensity`` (flops/byte) and the roofline regime — compute-
  vs memory-bound against the detected ridge point;
- ``MFU`` — achieved flops / peak flops, the number that survives
  hardware changes (ROADMAP: vs_baseline is a nominal constant).

Honesty riders, recorded rather than hidden:

- cost analysis runs on the UNOPTIMIZED lowered HLO: a ``lax.scan``
  body is counted once, so scan-shaped programs pass ``scale=`` (the
  trip count) and the non-scan prologue is overcounted by at most
  1/scale — callers note coverage via ``note=``;
- peaks come from a per-chip lookup table over
  ``jax.devices()[0].device_kind`` (bf16 MXU peak + HBM bandwidth),
  overridable via ``H2O3_PEAK_FLOPS`` / ``H2O3_PEAK_BYTES_PER_S`` for
  hardware the table does not know — without which an unknown TPU kind
  is an error. ``peak_source`` is recorded per field; the CPU backend's
  ``nominal`` source flags the whole point ``informational`` — a
  CPU-virtual MFU is a trend line, not a utilization claim.

``H2O3_TELEMETRY=0`` keeps every producer a checked no-op:
``accumulator()`` returns None and ``executable_cost`` returns without
tracing anything.
"""
from __future__ import annotations

import functools
import os
import threading
from collections import OrderedDict
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

from h2o3_tpu.telemetry.registry import on_reset, registry


class Cost(NamedTuple):
    """One executable's analytic work: flops + HBM bytes accessed."""
    flops: float
    bytes: float


# ------------------------------------------------------------- peaks

# per-chip peaks: (device_kind substring lowercase, peak FLOPS, HBM
# bytes/s). bf16 MXU peak — the precision the histogram/predict kernels
# actually run in; README "Performance accounting" records the sources.
# Ordered most-specific-first: "v5 lite"/"v5e" must match before "v5".
_PEAK_TABLE: Tuple[Tuple[str, float, float], ...] = (
    ("tpu v6 lite", 918e12, 1638e9),    # Trillium / v6e
    ("tpu v6e", 918e12, 1638e9),
    ("tpu v5 lite", 197e12, 819e9),     # v5e
    ("tpu v5e", 197e12, 819e9),
    ("tpu v5p", 459e12, 2765e9),
    ("tpu v5", 459e12, 2765e9),
    ("tpu v4", 275e12, 1228e9),
    ("tpu v3", 123e12, 900e9),
    ("tpu v2", 45e12, 700e9),
)

# the CPU backend (virtual test devices): a nominal single-socket-class
# constant so trend lines still render — flagged informational, never a
# claim. A TPU kind without a table row or override is an error.
NOMINAL_PEAK_FLOPS = 1e12
NOMINAL_PEAK_BYTES_PER_S = 100e9


def _env_float(name: str) -> Optional[float]:
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        from h2o3_tpu.log import warn
        warn("%s=%r is not a number — ignoring the override", name, v)
        return None


def device_peaks() -> Dict[str, object]:
    """Per-chip peak FLOPS and memory bandwidth with provenance:
    ``source`` per field is ``override`` (env), ``table`` (device_kind
    lookup) or ``nominal`` (the CPU backend); ``informational`` is set
    whenever any field fell back to nominal. A TPU whose kind has
    neither a table row nor an override raises — a made-up peak would
    read as a utilization. Read fresh each call (env overrides are
    test/bench knobs)."""
    import jax
    dev = jax.devices()[0]
    kind = str(dev.device_kind)
    t_flops = t_bytes = None
    for sub, fl, by in _PEAK_TABLE:
        if sub in kind.lower():
            t_flops, t_bytes = fl, by
            break
    out: Dict[str, object] = {"device_kind": kind}
    ov_f = _env_float("H2O3_PEAK_FLOPS")
    ov_b = _env_float("H2O3_PEAK_BYTES_PER_S")
    if dev.platform == "tpu" and ((ov_f is None and t_flops is None)
                                  or (ov_b is None and t_bytes is None)):
        raise LookupError(
            f"no peak FLOP/s and bytes/s known for TPU device_kind "
            f"'{kind}': add a _PEAK_TABLE row with its source, or set "
            "H2O3_PEAK_FLOPS and H2O3_PEAK_BYTES_PER_S")
    if ov_f is not None:
        out["flops"], out["flops_source"] = ov_f, "override"
    elif t_flops is not None:
        out["flops"], out["flops_source"] = t_flops, "table"
    else:
        out["flops"], out["flops_source"] = NOMINAL_PEAK_FLOPS, "nominal"
    if ov_b is not None:
        out["bytes_per_s"], out["bytes_source"] = ov_b, "override"
    elif t_bytes is not None:
        out["bytes_per_s"], out["bytes_source"] = t_bytes, "table"
    else:
        out["bytes_per_s"], out["bytes_source"] = (
            NOMINAL_PEAK_BYTES_PER_S, "nominal")
    out["peak_source"] = ("override" if "override" in
                          (out["flops_source"], out["bytes_source"])
                          else out["flops_source"])
    out["informational"] = ("nominal" in (out["flops_source"],
                                          out["bytes_source"]))
    return out


# ----------------------------------------------- executable cost cache

# (seam key) -> Cost | None (None = capture failed; don't retry every
# dispatch). Bounded: keys are per-(mesh, config, bucket) like the jit
# caches they mirror.
_COSTS: "OrderedDict[tuple, Optional[Cost]]" = OrderedDict()
# key -> the scale (scan trip count) the cached Cost was multiplied by,
# so consumers that want PER-ITERATION work (the training scheduler's
# admission working-set hint) can divide it back out
_COST_SCALES: Dict[tuple, float] = {}
_COSTS_LOCK = threading.Lock()
_COSTS_CAP = 512


def _extract_cost(lowered) -> Optional[Cost]:
    ca = lowered.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    return Cost(float(ca.get("flops", 0.0) or 0.0),
                float(ca.get("bytes accessed", 0.0) or 0.0))


@functools.lru_cache(maxsize=8)
def _warn_no_cost(why: str) -> None:
    """Say ONCE per reason that executables go unaccounted — otherwise
    ``model.output['perf']`` and every MFU field are silently absent
    (the TPU runtime answers ``Lowered.cost_analysis()`` with None)."""
    from h2o3_tpu.log import warn
    warn("performance accounting is off: %s — no FLOP/byte cost is "
         "attached to this process's executables", why)


def lowered_cost(lower: Callable[[], object],
                 scale: float = 1.0) -> Optional[Cost]:
    """Uncached capture: ``lower()`` returns a ``jax.stages.Lowered``
    (trace+lower only — NO backend compile, so the zero-recompile
    guards never see this). ``scale`` multiplies the analytic counts
    (scan trip count — the HLO analysis counts a while body once)."""
    if not registry().enabled:
        return None
    try:
        c = _extract_cost(lower())
        why = "the backend returned no cost analysis for lowered HLO"
    except Exception as e:  # noqa: BLE001 — accounting must not fail a train
        c, why = None, f"{type(e).__name__}: {e}"
    if c is None:
        _warn_no_cost(why)
        return None
    return Cost(c.flops * scale, c.bytes * scale)


def executable_cost(key: tuple, lower: Callable[[], object],
                    scale: float = 1.0) -> Optional[Cost]:
    """Cached per-executable cost: one trace+lower per ``key`` for the
    process lifetime — the warm path pays a dict lookup. A key that
    failed to capture stays None (no per-dispatch retries)."""
    if not registry().enabled:
        return None
    with _COSTS_LOCK:
        if key in _COSTS:
            _COSTS.move_to_end(key)
            return _COSTS[key]
    cost = lowered_cost(lower, scale=scale)
    with _COSTS_LOCK:
        _COSTS[key] = cost
        _COST_SCALES[key] = max(float(scale), 1.0)
        while len(_COSTS) > _COSTS_CAP:
            old, _ = _COSTS.popitem(last=False)
            _COST_SCALES.pop(old, None)
    return cost


def traced_cost(key: tuple, fn: Callable, *args, **kwargs
                ) -> Optional[Cost]:
    """``executable_cost`` for a plain traceable function: jit+lower it
    once per key (eager call sites like the streamed level kernels have
    no jitted handle to lower)."""
    scale = kwargs.pop("scale", 1.0)

    def _lower():
        import jax
        return jax.jit(fn).lower(*args, **kwargs)

    return executable_cost(key, _lower, scale=scale)


def cost_cache_size() -> int:
    with _COSTS_LOCK:
        return len(_COSTS)


def per_iteration_bytes_hint(prefix: str) -> Optional[float]:
    """Max PER-ITERATION HBM bytes accessed over cached executables
    whose key leads with ``prefix`` (e.g. ``"gbm.chunk"``): the cached
    Cost was multiplied by its scan trip count at capture, so dividing
    it back out yields what ONE tree/step touches — the training
    scheduler's admission working-set refinement (ISSUE 15). Bytes
    accessed bound the resident working set from above (every resident
    operand is read at least once per step), so the hint is a
    conservative OVER-estimate; None when nothing is cached yet (cold
    process — shape-based fallback applies)."""
    best = None
    with _COSTS_LOCK:
        for key, cost in _COSTS.items():
            if cost is None or not key or key[0] != prefix:
                continue
            per_it = cost.bytes / _COST_SCALES.get(key, 1.0)
            if best is None or per_it > best:
                best = per_it
    return best


def cost_cached(key: tuple) -> bool:
    """Whether ``key`` already holds a captured cost — call sites use
    this to detect a COLD call (first compile + first lower land in the
    same invocation) and keep its skewed wall time out of the measured
    device seconds."""
    with _COSTS_LOCK:
        return key in _COSTS


# ------------------------------------------------------- roofline math

def roofline_point(flops: float, bytes_: float, seconds: float,
                   n_devices: int = 1,
                   peaks: Optional[Dict] = None,
                   note: Optional[str] = None) -> Optional[Dict]:
    """Derive the roofline point for accumulated work over measured
    device time. ``n_devices`` scales the per-chip peaks (the lowered
    program is the GLOBAL module on a sharded mesh — its flops span
    every participating chip)."""
    if seconds <= 0 or (flops <= 0 and bytes_ <= 0):
        return None
    peaks = peaks or device_peaks()
    pk_f = float(peaks["flops"]) * max(int(n_devices), 1)
    pk_b = float(peaks["bytes_per_s"]) * max(int(n_devices), 1)
    ach_f = flops / seconds
    ach_b = bytes_ / seconds
    ai = (flops / bytes_) if bytes_ > 0 else None
    ridge = pk_f / pk_b        # flops/byte at the roofline knee
    regime = ("compute-bound" if ai is not None and ai >= ridge
              else "memory-bound")
    # significant-figure rounding: a tiny-but-real MFU (CPU backend,
    # huge peak override) must not decimal-round to a fake 0.0
    def _sig(x):
        return float(f"{x:.4g}")

    mfu = ach_f / pk_f
    bw_util = ach_b / pk_b
    # attainable ceiling at this intensity: min(peak, AI x bandwidth)
    attain = min(pk_f, ai * pk_b) if ai is not None else pk_f
    pt = {
        "flops_total": float(flops),
        "bytes_total": float(bytes_),
        "device_seconds": round(float(seconds), 6),
        "achieved_flops": round(ach_f, 1),
        "achieved_bytes_per_s": round(ach_b, 1),
        "arith_intensity": _sig(ai) if ai is not None else None,
        "ridge_intensity": _sig(ridge),
        "roofline_regime": regime,
        "mfu": _sig(mfu),
        "bw_utilization": _sig(bw_util),
        "roofline_utilization": _sig(ach_f / attain) if attain else None,
        "n_devices": int(n_devices),
        "peak_flops": pk_f,
        "peak_bytes_per_s": pk_b,
        "peak_source": peaks["peak_source"],
        "device_kind": peaks["device_kind"],
        "informational": bool(peaks["informational"]),
    }
    if note:
        pt["note"] = note
    return pt


# --------------------------------------------------- phase accumulation

# registry handles per phase, cached off the creation mutex (the GBM
# chunk loop touches these per dispatch). Cleared on Registry.reset().
_PHASE_HANDLES: Dict[str, tuple] = {}
on_reset(_PHASE_HANDLES.clear)


def _phase_counters(phase: str):
    h = _PHASE_HANDLES.get(phase)
    if h is None:
        reg = registry()
        lab = {"phase": phase}
        h = (reg.counter("h2o3_achieved_flops_total", lab,
                         help="executed flops by phase (cost_analysis "
                              "x dispatch count)"),
             reg.counter("h2o3_achieved_bytes_total", lab,
                         help="HBM bytes accessed by phase"),
             reg.counter("h2o3_device_seconds_total", lab,
                         help="measured device-saturated seconds by "
                              "phase"))
        _PHASE_HANDLES[phase] = h
    return h


def record(phase: str, cost: Optional[Cost],
           seconds: Optional[float] = None, n: int = 1) -> None:
    """One-shot accounting (the rollup / ingest-assembly seams): fold a
    cost (xN executions) and optionally its measured seconds into the
    phase counters. No-op when telemetry is disabled."""
    if not registry().enabled:
        return
    cf, cb, cs = _phase_counters(phase)
    if cost is not None and n > 0:
        cf.inc(cost.flops * n)
        cb.inc(cost.bytes * n)
    if seconds is not None and seconds > 0:
        cs.inc(float(seconds))


class PerfAccumulator:
    """Per-window (one train / one live deployment) accounting: ``add``
    at each dispatch, ``add_device_seconds`` at the commit seam,
    ``point()`` for the roofline point. Every add also lands in the
    process-wide ``h2o3_achieved_*`` counters, so the cluster snapshot
    plane merges the totals like any other metric."""

    def __init__(self, phase: str, n_devices: int = 1,
                 note: Optional[str] = None):
        self.phase = phase
        self.n_devices = max(int(n_devices), 1)
        self.note = note
        self._mu = threading.Lock()
        self.flops = 0.0
        self.bytes = 0.0
        self.device_s = 0.0
        self.capture_s = 0.0
        self.executions = 0

    def note_capture_seconds(self, seconds: float) -> None:
        """Host time the window spent CAPTURING costs (a cold key's
        trace+lower runs inside the measured loop). NOT subtracted from
        device seconds — in the pipelined loops the lower overlaps
        async device work, so subtracting could OVERSTATE MFU (the
        dishonest direction). Surfaced as ``capture_seconds`` on the
        point instead: a cold window's MFU is a visible lower bound,
        and warm windows (the bench's measured trains) carry ~0 here."""
        if seconds and seconds > 0:
            with self._mu:
                self.capture_s += float(seconds)

    def add(self, cost: Optional[Cost], n: int = 1) -> None:
        if cost is None or n <= 0:
            return
        with self._mu:
            self.flops += cost.flops * n
            self.bytes += cost.bytes * n
            self.executions += n
        record(self.phase, cost, n=n)

    def add_device_seconds(self, seconds: float) -> None:
        if seconds is None or seconds <= 0:
            return
        with self._mu:
            self.device_s += float(seconds)
        record(self.phase, None, seconds=seconds)

    def point(self, update_gauges: bool = True) -> Optional[Dict]:
        with self._mu:
            flops, by, secs, ex, cap = (self.flops, self.bytes,
                                        self.device_s, self.executions,
                                        self.capture_s)
        pt = roofline_point(flops, by, secs, n_devices=self.n_devices,
                            note=self.note)
        if pt is None:
            return None
        pt["executions"] = ex
        if cap > 0:
            # cold-window caveat: this much of device_seconds was spent
            # tracing/lowering for the capture itself (overlapped with
            # async device work to an unknown degree) — the MFU is a
            # lower bound; warm windows report 0 here
            pt["capture_seconds"] = round(cap, 6)
        if update_gauges and registry().enabled:
            reg = registry()
            lab = {"phase": self.phase}
            reg.gauge("h2o3_mfu", lab,
                      help="model flops utilization by phase (latest "
                           "window)").set(pt["mfu"])
            if pt["arith_intensity"] is not None:
                reg.gauge("h2o3_arith_intensity", lab,
                          help="flops per HBM byte by phase (latest "
                               "window)").set(pt["arith_intensity"])
        return pt

    def finish(self) -> Optional[Dict]:
        return self.point(update_gauges=True)


def accumulator(phase: str, n_devices: int = 1,
                note: Optional[str] = None) -> Optional[PerfAccumulator]:
    """A phase accumulator, or None when telemetry is disabled — call
    sites guard with ``if acc is not None`` so the disabled path is one
    attribute load + branch."""
    if not registry().enabled:
        return None
    return PerfAccumulator(phase, n_devices=n_devices, note=note)


# ------------------------------------------------------------- summary

def summary() -> Dict[str, object]:
    """Process-wide accounting view (``GET /3/Telemetry/perf``): the
    detected peaks plus a roofline point per phase derived from the
    cumulative ``h2o3_achieved_*`` counters. Phases without measured
    device seconds report their raw totals with ``mfu: None`` instead
    of inventing a rate. Points here are computed against SINGLE-chip
    peaks (the counters don't carry mesh width); the per-train points
    in ``model.output["perf"]`` scale peaks by the mesh the train ran
    under."""
    peaks = device_peaks()
    out: Dict[str, object] = {"enabled": registry().enabled,
                              "peak": peaks, "phases": {}}
    if not registry().enabled:
        return out
    totals: Dict[str, Dict[str, float]] = {}
    for s in registry().samples():
        name = s.get("name")
        if name not in ("h2o3_achieved_flops_total",
                        "h2o3_achieved_bytes_total",
                        "h2o3_device_seconds_total"):
            continue
        phase = (s.get("labels") or {}).get("phase", "")
        t = totals.setdefault(phase, {"flops": 0.0, "bytes": 0.0,
                                      "seconds": 0.0})
        fld = {"h2o3_achieved_flops_total": "flops",
               "h2o3_achieved_bytes_total": "bytes",
               "h2o3_device_seconds_total": "seconds"}[name]
        t[fld] += float(s.get("value", 0.0) or 0.0)
    phases: Dict[str, Dict] = {}
    for phase, t in sorted(totals.items()):
        pt = roofline_point(t["flops"], t["bytes"], t["seconds"],
                            peaks=peaks)
        if pt is None:
            pt = {"flops_total": t["flops"], "bytes_total": t["bytes"],
                  "device_seconds": t["seconds"], "mfu": None,
                  "roofline_regime": None,
                  "informational": bool(peaks["informational"])}
        phases[phase] = pt
    out["phases"] = phases
    return out
