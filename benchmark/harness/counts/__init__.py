"""Least work from shapes: ``harness/counts/<file>.py`` holds a ``BY_NAME``
table of functions ``config -> [phase, ...]``. A metric names the file and
the entry (``"count": "gbm.levels"``), so a later file cannot shadow one."""
from __future__ import annotations

from harness.loader import plugin


def phases(name: str, config: dict) -> list[dict]:
    file, _, entry = name.partition(".")
    table = plugin("counts", file).BY_NAME
    if entry not in table:
        raise KeyError(f"harness/counts/{file}.py has no count {entry!r}; "
                       f"it has {sorted(table)}")
    return table[entry](config)
