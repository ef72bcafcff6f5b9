"""From a profiler trace (``.xplane.pb``) to numbers, with JAX alone.

``jax.profiler.ProfileData`` gives planes, their lines, and events with a
start and a duration in nanoseconds since the trace began, on one clock for
host and device planes. What this file takes from it:

* device planes: those named ``/device:TPU:<n>`` (lines ``XLA Modules``,
  ``XLA Ops``, ``Async XLA Ops``, ``TC Overlay``); the line ``XLA Ops`` holds
  one event per executed HLO operation, named by the instruction's whole text
  (``%fusion.16 = f32[280000000]{0:T(1024)} fusion(...)``). A Mosaic kernel is
  one ``custom-call`` event whose text ends in
  ``custom_call_target="tpu_custom_call"``. A ``while`` spans the operations
  of its body on the same line, so busy time is a union of intervals, never a
  sum;
* the host plane ``/host:CPU``: the line that holds the harness's
  ``bench.window`` annotation (``python3``, the interpreter's thread) drives
  the device; its other events (``PjitFunction(...)``, ``np.asarray(jax.Array)``)
  say what the host was doing in a gap. PJRT's own events are on ``main/<n>``.

Everything is clipped to the window, the ``bench.window`` span.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
# "%fusion.16 = f32[...]{...} fusion(...": the instruction's name and opcode
HLO_TEXT = re.compile(r"^%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")
# operations that only hold others on the op line
CONTAINERS = ("while", "conditional", "call")


def short_name(text: str) -> tuple[str, str]:
    """(``fusion.16 fusion``, opcode) of an op event's text; a plain name
    (a host event, a hand-made trace) stands for itself, its opcode the part
    before the first dot."""
    m = HLO_TEXT.match(text)
    if m:
        return f"{m.group(1)} {m.group(2)}", m.group(2)
    return text[:80], text.split(".")[0]


@dataclass
class Event:
    name: str               # short: "fusion.16 fusion"
    start: float            # seconds since the trace began
    dur: float
    label: str = ""         # whole text and string stats: a pattern's haystack
    opcode: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # chip index -> [Event]
    host: list = field(default_factory=list)      # driver thread's events
    window: tuple | None = None                   # (start, end) seconds


def _events(line, with_stats: bool) -> list[Event]:
    out = []
    for e in line.events:
        label = e.name
        if with_stats:
            label += " " + " ".join(str(v) for _, v in e.stats
                                    if isinstance(v, str))
        name, opcode = short_name(e.name)
        out.append(Event(name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         label, opcode))
    return out


def from_profile(data) -> Trace:
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OP_LINE:
                    tr.devices[int(m.group(1))] = _events(line, True)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                evs = _events(line, False)
                win = [e for e in evs if e.name == WINDOW_SPAN]
                if win:
                    tr.host = evs
                    tr.window = (win[0].start, win[0].end)
    return tr


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(e.start, lo), min(e.end, hi)) for e in events
            if e.end > lo and e.start < hi and e.dur > 0]


def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_seconds(events, lo: float, hi: float) -> float:
    return sum(b - a for a, b in union(clip(events, lo, hi)))


def gaps(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The idle intervals of [lo, hi]: what the union of events leaves."""
    out, at = [], lo
    for a, b in union(clip(events, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def pattern_seconds(events, pattern: str, lo: float, hi: float):
    """Summed device time of the operations a pattern names, and how many."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e.label)
           and e.end > lo and e.start < hi]
    return sum(min(e.end, hi) - max(e.start, lo) for e in hit), len(hit)


def spans(tr: Trace, name: str) -> list[Event]:
    """The harness's own annotations of one name inside the window."""
    lo, hi = tr.window
    return [e for e in tr.host if e.name == name
            and e.start >= lo - 1e-9 and e.end <= hi + 1e-9]


def innermost(host, t: float) -> str:
    """What the driving thread was in at time t: its shortest event there."""
    best = None
    for e in host:
        if e.start <= t <= e.end and e.dur > 0:
            if best is None or e.dur < best.dur:
                best = e
    return best.name[:80] if best else "(no host event)"


def top_ops(events, lo: float, hi: float, n: int = 10) -> list[list]:
    """The device operations that took most time, containers left out."""
    total: dict[str, float] = {}
    for e in events:
        if e.end > lo and e.start < hi and e.opcode not in CONTAINERS:
            total[e.name] = total.get(e.name, 0.0) + (
                min(e.end, hi) - max(e.start, lo))
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def idle_by_host(tr: Trace, chip: int = 0, n: int = 10,
                 longest: int = 200) -> list[list]:
    """The idle time of one chip by what the host was doing at the middle
    of each gap, summed by name; only the ``longest`` gaps are looked up."""
    lo, hi = tr.window
    total: dict[str, float] = {}
    found = sorted(gaps(tr.devices[chip], lo, hi), key=lambda g: g[0] - g[1])
    for a, b in found[:longest]:
        what = innermost(tr.host, 0.5 * (a + b))
        total[what] = total.get(what, 0.0) + (b - a)
    if found[longest:]:
        total["(shorter gaps)"] = sum(b - a for a, b in found[longest:])
    return [[k, v] for k, v in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:n]]


def busy_window(tr: Trace, chips: int) -> tuple[float, float]:
    """(seconds in which an operation ran, averaged over the chips used;
    length of the window)."""
    if tr.window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    if not tr.devices:
        raise ValueError("the trace holds no device plane with an "
                         f"'{OP_LINE}' line")
    lo, hi = tr.window
    used = sorted(tr.devices)[:chips]
    busy = sum(busy_seconds(tr.devices[c], lo, hi) for c in used) / len(used)
    return busy, hi - lo


def summary(tr: Trace, chips: int) -> dict:
    """``busy_s`` and ``window_s`` of the result's ``device`` key, and the
    ``breakdown`` (of the first chip used)."""
    busy, window = busy_window(tr, chips)
    lo, hi = tr.window
    chip = sorted(tr.devices)[0]
    return {"busy_s": busy, "window_s": window,
            "breakdown": {"device_ops": top_ops(tr.devices[chip], lo, hi),
                          "idle_gaps": idle_by_host(tr, chip)}}
