"""A stage of the program's own ``train_profile`` over the events of one
of its counters, a step: the mean over the window's trains of the profile's
``keys`` (summed), over the counter's total on the label sets that carry
``labels``, divided by the steps the process made (the set-up step and the
window's steps are the same step, as ``counter_per_step`` reads them),
times ``scale``. None where the program has no such series (a program from
before the counter), its profile lacks a key, or no step was completed."""


def read(r, keys, counter, labels=None, scale=1.0):
    from h2o3_tpu import telemetry
    want = dict(labels or {})
    vals = [sum(float(p[k]) for k in keys) for p in r.profiles
            if all(k in p for k in keys)]
    found = [s["value"] for s in telemetry.registry().samples()
             if s["name"] == counter and "value" in s
             and all(s.get("labels", {}).get(k) == v
                     for k, v in want.items())]
    if not vals or not found or r.steps <= 0:
        return None
    per_step = sum(found) / (r.steps + 1)
    return scale * (sum(vals) / len(vals)) / per_step if per_step else None
