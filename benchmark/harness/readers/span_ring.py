"""Per step, what the program's own finished spans say of one kind of work.

Reads ``h2o3_tpu.telemetry.finished_spans()`` after the window, as
``harness/device.py`` reads the registry. The window's steps are the last
``r.steps`` finished spans named ``root`` that ended without an error:
every step that succeeded left one, after the warm-up's, so the warm-up's
is not among them, and a failed step's root is marked or missing and not
counted in ``r.steps`` either. Under each root, every descendant whose
name matches ``span`` (``fnmatch``: ``score.fetch``, ``jit.*``) adds its
seconds (``what: "seconds"``) or the number of events it stands for
(``what: "count"``: its attribute ``n`` where the program folded several
reports into one span, else 1), and the steps are averaged. None where
the ring holds fewer such roots than steps: a program from before these
spans leaves none, and a ring that has dropped some would read low.
"""
from fnmatch import fnmatchcase


def read(r, root, span, what):
    if what not in ("seconds", "count"):
        raise ValueError(f"what is 'seconds' or 'count', not {what!r}")
    from h2o3_tpu import telemetry
    spans = telemetry.finished_spans()
    roots = [s for s in spans if s.name == root and not s.attrs.get("error")]
    if r.steps <= 0 or len(roots) < r.steps:
        return None
    roots = roots[-r.steps:]
    children: dict[int, list] = {}
    for s in spans:
        children.setdefault(s.parent_id, []).append(s)
    total = 0.0
    for top in roots:
        below = list(children.get(top.span_id, ()))
        while below:
            s = below.pop()
            below.extend(children.get(s.span_id, ()))
            if fnmatchcase(s.name, span):
                total += (s.duration_s if what == "seconds"
                          else s.attrs.get("n", 1))
    return total / len(roots)
