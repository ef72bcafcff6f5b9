"""A kernel's share of its roofline: the least time the chip could take for
the counted work of the steps in the traced window, over the summed device
time of the operations whose name or metadata the pattern finds. The count
has one phase per kernel call, so the pattern has to find exactly that many
events a step: where it finds more or fewer (a later kernel that the pattern
also names, a level fused away) the time is not the counted work's, the
reader says so in the run's notes and on standard error and returns nothing.
None too where the pattern finds no operation."""
import sys

from harness import counts, peaks, trace_reduce as tr


def read(r, pattern, count):
    if (r.peaks is None or r.trace is None or not r.trace.devices
            or not r.steps):
        return None
    done = tr.spans(r.trace, r.step_span)
    if not done:
        return None
    lo, hi = done[0].start, done[-1].end
    chip = sorted(r.trace.devices)[0]
    seconds, n = tr.pattern_seconds(r.trace.devices[chip], pattern, lo, hi)
    phases = counts.phases(count, r.config)
    r.notes[f"{count}.events"] = n
    r.notes[f"{count}.events_expected"] = len(phases) * len(done)
    if not n or seconds <= 0:
        return None
    if n != len(phases) * len(done):
        print(f"{count}: the pattern finds {n} events in {len(done)} steps, "
              f"the count has {len(phases)} calls a step: no roofline",
              file=sys.stderr, flush=True)
        return None
    least, bound = peaks.least_seconds(phases, r.peaks)
    r.notes[f"{count}.bound"] = bound
    r.notes[f"{count}.device_s_per_step"] = seconds / len(done)
    return 100.0 * least * len(done) / seconds
