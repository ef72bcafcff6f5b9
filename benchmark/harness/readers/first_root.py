"""What the program's own finished spans say of the FIRST time a process did
something: its boot, its first train (the set-up train), its first predict.

Reads ``h2o3_tpu.telemetry.finished_spans()`` after the window, as
``span_ring`` does, but where that reader takes the window's last ``steps``
roots this one takes, for each pattern of ``roots`` (``fnmatch``: ``train.*``,
``boot.import``), the first finished root span whose name matches, and sums
over the patterns:

``what: "seconds"``    the root's own seconds; with ``spans`` given, the
                       seconds in the descendants whose name matches one of
                       them (``jit.trace``, ``jit.lower``)
``what: "count"``      the events those descendants stand for (attribute
                       ``n`` where the program folded several reports into
                       one span, else 1)
``what: "end_since"``  from the start of the first span named ``since`` to
                       the end of the root, on the spans' wall clock

None where ``h2o3_spans_dropped_total`` is over 0 (a ring that has dropped its
oldest spans has lost the set-up), where a pattern finds no root or the span
``since`` is missing (a program from before these spans), and where the root
it finds ended with an error. A descendant's attribute ``top`` (the program
names the up to five ``[program, seconds]`` of most seconds it folded into the
span) goes to ``r.notes`` under ``"<root> <span>"``: a label's seconds summed
over the stages of the root, the largest first, at most ``NOTED``.
"""
from fnmatch import fnmatchcase

from harness import device

NOTED = 8


def _note(r, root, found) -> None:
    by_span: dict[str, dict[str, float]] = {}
    for s in found:
        for label, seconds in s.attrs.get("top") or ():
            labels = by_span.setdefault(s.name, {})
            labels[label] = labels.get(label, 0.0) + seconds
    for name, labels in by_span.items():
        ranked = sorted(labels.items(), key=lambda kv: -kv[1])[:NOTED]
        r.notes[f"{root.name} {name}"] = [list(kv) for kv in ranked]


def by_parent(ring) -> dict:
    children: dict[int, list] = {}
    for s in ring:
        children.setdefault(s.parent_id, []).append(s)
    return children


def first(children, pattern):
    """The first finished root whose name matches, or None."""
    return next((s for s in children.get(0, ())
                 if fnmatchcase(s.name, pattern)), None)


def under(children, root, patterns) -> list:
    """Every descendant of ``root`` whose name matches a pattern."""
    found, below = [], list(children.get(root.span_id, ()))
    while below:
        s = below.pop()
        below.extend(children.get(s.span_id, ()))
        if any(fnmatchcase(s.name, p) for p in patterns):
            found.append(s)
    return found


def read(r, roots, what, spans=(), since=None):
    if what not in ("seconds", "count", "end_since"):
        raise ValueError(
            f"what is 'seconds', 'count' or 'end_since', not {what!r}")
    if (what == "end_since") != (since is not None) or (
            what == "count" and not spans):
        raise ValueError("'end_since' goes with since, 'count' with spans")
    from h2o3_tpu import telemetry
    if device.counter_total("h2o3_spans_dropped_total") > 0:
        return None
    ring = telemetry.finished_spans()
    children = by_parent(ring)
    total = 0.0
    for pattern in roots:
        root = first(children, pattern)
        if root is None or root.attrs.get("error"):
            return None
        if what == "end_since":
            start = next((s for s in ring if s.name == since), None)
            if start is None:
                return None
            total += root.t_wall + root.duration_s - start.t_wall
        elif not spans:
            total += root.duration_s
        else:
            found = under(children, root, spans)
            _note(r, root, found)
            total += sum(s.duration_s if what == "seconds"
                         else s.attrs.get("n", 1) for s in found)
    return total
