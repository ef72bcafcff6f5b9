"""Share of the traced window in which no operation ran on the device."""
from harness import trace_reduce as tr


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    busy, window = tr.busy_window(r.trace, r.chips)
    return 100.0 * (1.0 - busy / window)
