"""Device-idle seconds inside the host events of one name, per step: the
gaps of the first chip's op line (``trace_reduce.gaps``) that fall inside
the program's span ``span`` on the driving thread, summed over the window
and divided by its steps. None where the trace holds no device plane (a
rehearsal) or no such host event (a program from before the span)."""
from harness import trace_reduce as tr


def read(r, span):
    if r.trace is None or not r.trace.devices:
        return None
    inside = tr.spans(r.trace, span)
    steps = tr.spans(r.trace, r.step_span)
    if not inside or not steps:
        return None
    ops = r.trace.devices[sorted(r.trace.devices)[0]]
    return sum(b - a for s in inside
               for a, b in tr.gaps(ops, s.start, s.end)) / len(steps)
