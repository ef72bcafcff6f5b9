"""The whole step's share of the chip's peak: the least time the chip could
take for one step's counted work over the step's wall time in the window."""
from harness import counts, peaks


def read(r, count):
    if r.peaks is None or not r.steps or r.elapsed <= 0:
        return None
    least, bound = peaks.least_seconds(counts.phases(count, r.config),
                                       r.peaks)
    r.notes[f"{count}.bound"] = bound
    r.notes[f"{count}.least_s"] = least
    return 100.0 * least * r.steps / r.elapsed
