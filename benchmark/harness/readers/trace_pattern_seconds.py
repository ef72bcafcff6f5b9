"""Device seconds a step in the operations a pattern names: their summed
durations on the first chip's op line inside the window's steps, over the
steps. None where there is no device trace or the pattern finds nothing (a
program from before the kernel's name)."""
from harness import trace_reduce as tr


def read(r, pattern):
    if r.trace is None or not r.trace.devices or not r.steps:
        return None
    done = tr.spans(r.trace, r.step_span)
    if not done:
        return None
    chip = sorted(r.trace.devices)[0]
    seconds, n = tr.pattern_seconds(r.trace.devices[chip], pattern,
                                    done[0].start, done[-1].end)
    r.notes[f"pattern_seconds.events[{pattern[:40]}]"] = n
    return seconds / len(done) if n else None
