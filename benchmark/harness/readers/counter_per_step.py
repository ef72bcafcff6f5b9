"""One telemetry counter of the program a step, over the whole process: the
counter's total over the label sets that carry ``labels``, times ``scale``,
over the steps the process made. The set-up step and the window's steps are
the same step (as ``counter_ratio`` reads them), so the process made the
window's steps and one more. None where the program has no such series (a
program from before the counter) or no step was completed."""


def read(r, counter, labels=None, scale=1.0):
    from h2o3_tpu import telemetry
    want = dict(labels or {})
    found = [s["value"] for s in telemetry.registry().samples()
             if s["name"] == counter and "value" in s
             and all(s.get("labels", {}).get(k) == v
                     for k, v in want.items())]
    if not found or r.steps <= 0:
        return None
    return scale * sum(found) / (r.steps + 1)
