"""Finds a cell, its configuration, its traffic and its metrics by file name.

Nothing here lists cells or metrics: ``BENCHMARK.json`` names them and each
name is a file under ``benchmark/``. A later PR adds files and entries and
edits none.

    configs/<config>.json     sizes and parameters as run, source, assumed
    traffic/<traffic>.json    runner and its window parameters, what it reports
    workloads/<cell>.json     the check that decides ``correct`` and its limits
    metrics/<metric>.json     reader and its arguments
    harness/<kind>/<name>.py  runners, readers, generators, counts, checks
"""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)


class BenchmarkError(Exception):
    """The benchmark's own files do not fit together."""


def read_json(*parts: str) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError as e:
        raise BenchmarkError(f"no file {os.path.relpath(path, REPO_DIR)}") from e


def plugin(kind: str, name: str):
    """``harness/<kind>/<name>.py``, imported by name."""
    if not name.replace("_", "").isalnum():
        raise BenchmarkError(f"{kind} name {name!r}")
    try:
        return importlib.import_module(f"harness.{kind}.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"harness.{kind}.{name}":
            raise
        raise BenchmarkError(f"no harness/{kind}/{name}.py") from e


def load_benchmark(path: str | None = None) -> dict:
    path = path or os.path.join(REPO_DIR, "BENCHMARK.json")
    with open(path) as f:
        return json.load(f)


def reports(bench: dict, cell: str, traffic: dict) -> list[str]:
    """The end-to-end metrics a cell reports: what its traffic file says,
    held against the ``workloads`` keys of ``BENCHMARK.json``."""
    declared = {m["name"]: m for m in bench["end_to_end"]}
    out = []
    for name in traffic["reports"]:
        if name not in declared:
            raise BenchmarkError(
                f"traffic reports {name}, which BENCHMARK.json does not name")
        cells = declared[name].get("workloads")
        if cells is not None and cell not in cells:
            raise BenchmarkError(
                f"{cell} reports {name}, but BENCHMARK.json lists {name} "
                f"only for {cells}")
        out.append(name)
    if "setup_s" not in out:
        raise BenchmarkError(f"{cell} does not report setup_s")
    return out


def load_cell(bench: dict, name: str) -> dict:
    """Everything one run needs, gathered by name."""
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchmarkError(
            f"no cell {name!r} in BENCHMARK.json; it has "
            f"{[w['name'] for w in bench['workloads']]}")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = read_json(os.path.join(REPO_DIR, cfg_entry["file"]))
    traffic = read_json("traffic", entry["traffic"] + ".json")
    check = read_json("workloads", name + ".json")
    cell = {"name": name, "chips": entry["chips"], "config": config,
            "traffic": traffic, "check": check}
    cell["reports"] = reports(bench, name, traffic)
    cell["metrics"] = load_metrics(bench, cell)
    return cell


def load_metrics(bench: dict, cell: dict) -> list[dict]:
    """The per-layer metrics read in this cell, each with its reader file.
    A metric that lists a cell which does not report its ``moves`` metric
    is refused here, before anything runs."""
    reported = {w["name"]: read_json("traffic", w["traffic"] + ".json")[
        "reports"] for w in bench["workloads"]}
    out = []
    for m in bench["per_layer"]:
        spec = read_json("metrics", m["name"] + ".json")
        moved = {c for c, names in reported.items() if m["moves"] in names}
        listed = m.get("workloads")
        for c in listed or ():
            if c not in moved:
                raise BenchmarkError(
                    f"metric {m['name']} lists cell {c}, which does not "
                    f"report {m['moves']}")
        if cell["name"] in (listed if listed is not None else moved):
            out.append({**m, **spec})
    if not out:
        raise BenchmarkError(f"{cell['name']} has no per-layer metric")
    return out
