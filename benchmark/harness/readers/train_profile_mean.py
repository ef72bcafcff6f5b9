"""Mean over the window's trains of ``model.output["train_profile"]`` keys
(summed where several are given): the program's own host-clock stage split."""


def read(r, keys):
    vals = [sum(float(p[k]) for k in keys) for p in r.profiles
            if all(k in p for k in keys)]
    return sum(vals) / len(vals) if vals else None
