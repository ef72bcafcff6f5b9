"""One telemetry counter of the program as a share (%) of another, over every
label set and the whole process: the set-up step and the window's steps are
the same step, so the share is the window's. None where the program has not
counted the denominator (a program from before the counters)."""
from harness import device


def read(r, numerator, denominator):
    den = device.counter_total(denominator)
    if not den:
        return None
    return 100.0 * device.counter_total(numerator) / den
