"""Device-busy seconds inside one step: the union of the operations'
intervals inside each of the window's step spans, averaged over the steps."""
from harness import trace_reduce as tr


def read(r):
    if r.trace is None or not r.trace.devices:
        return None
    done = tr.spans(r.trace, r.step_span)
    if not done:
        return None
    chip = sorted(r.trace.devices)[0]
    return sum(tr.busy_seconds(r.trace.devices[chip], s.start, s.end)
               for s in done) / len(done)
