"""Change of one telemetry counter of the program over the window."""


def read(r, counter):
    return r.counters.get(counter)
