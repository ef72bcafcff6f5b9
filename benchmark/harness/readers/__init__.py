"""Per-layer readers, found by file name. Each has ``read(r, **arguments)``
where ``r`` is the run's ``Reading``; it returns the number, or None where it
finds nothing to read, and the harness then leaves the metric out."""
from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class Reading:
    config: dict
    peaks: dict | None                # published peaks of the chip; None in a rehearsal
    chips: int
    step_span: str                    # the annotation around each step
    steps: int = 0                    # steps completed in the window
    elapsed: float = 0.0              # the window, by the host clock
    profiles: list = field(default_factory=list)   # train_profile per train
    counters: dict = field(default_factory=dict)   # deltas over the window
    trace: object = None              # trace_reduce.Trace
    notes: dict = field(default_factory=dict)      # which bound, event counts
