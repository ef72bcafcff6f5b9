"""Runs of the benchmark on the chip that look into set-up: ``chip_runs.py``'s
protocol (one call, one or more checkouts, a process a run, one JSON line a
run) with a run's environment chosen by name and the first train's compile
work kept beside the result line, on a program with or without ISSUE 37.

    chiprun --timeout 3000 -- python3 benchmark/tests/chip_first_model.py \\
        --out chiprun_out/pr37_a.jsonl --side parent=.chip_checkout/parent \\
        parent:h2o_defaults.train:37001:0 change:h2o_defaults.train:37001:0 \\
        change:h2o_defaults.train:37002:1:cold \\
        change:h2o_defaults.train:37003:0:notelemetry

A run is ``side:cell:seed:trace[:flavour]``. Flavours: ``cold`` points
``JAX_COMPILATION_CACHE_DIR`` at a new empty directory (every program is
built); ``notelemetry`` sets ``H2O3_TELEMETRY=0`` (no span, no counter: what
the span tree itself costs). Beside ``profiles`` (``chip_runs.py``) a line
carries ``setup_end_s``, this process's seconds when the runner's set-up
returned (a traced run prints no ``setup_s``), and ``first``: read from the
program's span ring as the process ends with THIS checkout's
``harness/readers/first_root.py`` (a side from before it has none), the roots
``boot.*`` and, of the first root ``train.*``, its seconds and every ``jit.*``
span under it with the name of the span it fell in, its seconds, ``n`` and
``top`` where the program writes one. A traced run's line also carries
``host_spans``: the program's and the harness's spans among the host events of
the traced window, ``{name: [events, seconds]}``.
"""
import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

LOGGED = r"""
import atexit, json, runpy, sys, time
T0 = time.monotonic()
sys.path[:0] = ["benchmark", "."]
READER = sys.argv.pop(1)    # the change's first_root.py, whichever side runs
from harness.runners import score, train, train_enum, train_enum_mesh
step = train.step
def say(tag, value):
    print(tag + " " + json.dumps(value), file=sys.stderr, flush=True)
def logged(state):
    ok = step(state)
    say("PROFILE", state.profiles[-1])
    return ok
train.step = logged
def timed(setup):
    def wrapped(*a, **kw):
        state = setup(*a, **kw)
        say("SETUP_END", time.monotonic() - T0)
        return state
    return wrapped
for runner in (score, train, train_enum, train_enum_mesh):
    runner.setup = timed(runner.setup)
def first():
    import importlib.util
    spec = importlib.util.spec_from_file_location("first_root", READER)
    first_root = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(first_root)
    from h2o3_tpu import telemetry
    spans = telemetry.finished_spans()
    by_id = {s.span_id: s for s in spans}
    children = first_root.by_parent(spans)
    out = {"boot": {s.name: s.duration_s for s in children.get(0, ())
                    if s.name.startswith("boot.")}, "spans": len(spans)}
    top = first_root.first(children, "train.*")
    if top is not None:
        out["train"] = {"name": top.name, "seconds": top.duration_s}
        out["jit"] = [
            {"span": s.name, "under": by_id[s.parent_id].name,
             "seconds": s.duration_s, "n": s.attrs.get("n"),
             "top": s.attrs.get("top")}
            for s in first_root.under(children, top, ["jit.*"])]
    say("FIRST", out)
atexit.register(first)
sys.argv[0] = "benchmark/run.py"
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


# the program's spans among a kept trace's host events: {name: [events, s]}
HOST_SPANS = r"""
import glob, json, sys
from jax.profiler import ProfileData
found = {}
for path in glob.glob(sys.argv[1] + "/*.xplane.pb"):
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("train.", "score.", "boot.", "bench.")):
                    n, s = found.get(e.name, (0, 0.0))
                    found[e.name] = (n + 1, s + e.duration_ns / 1e9)
print(json.dumps(found))
"""


READER = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "harness", "readers", "first_root.py")


def host_spans(trace_dir: str):
    """Read in a process of its own, on the CPU, after the run has let go
    of the chip."""
    p = subprocess.run([sys.executable, "-c", HOST_SPANS, trace_dir],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True)
    try:
        return json.loads(p.stdout.strip().splitlines()[-1])
    except (IndexError, ValueError):
        return None


def environment(flavour: str, scratch: str) -> dict:
    env = dict(os.environ)
    if flavour == "cold":
        env["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
            prefix="cold_cache_", dir=scratch)
    elif flavour == "notelemetry":
        env["H2O3_TELEMETRY"] = "0"
    elif flavour:
        raise ValueError(f"flavour {flavour!r}: cold or notelemetry")
    return env


def tagged(stderr: str, tag: str) -> list:
    return [json.loads(ln[len(tag) + 1:]) for ln in stderr.splitlines()
            if ln.startswith(tag + " ")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--side", action="append", default=[],
                    metavar="NAME=DIR", help="a checkout besides change=.")
    ap.add_argument("--seconds", default="16")
    ap.add_argument("--rehearse", action="store_true",
                    help="try this script on the CPU; no run is correct")
    ap.add_argument("runs", nargs="+",
                    metavar="side:cell:seed:trace[:flavour]")
    args = ap.parse_args(argv)
    sides = {"change": ".", **dict(s.split("=", 1) for s in args.side)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    scratch = tempfile.mkdtemp(prefix="first_model_")
    worst = 0
    try:
        with open(args.out, "a") as out, open(args.out + ".err", "a") as err:
            for run in args.runs:
                side, cell, seed, trace, *flavour = run.split(":")
                flavour = flavour[0] if flavour else ""
                kept = tempfile.mkdtemp(prefix="trace_", dir=scratch)
                t0 = time.monotonic()
                p = subprocess.run(
                    [sys.executable, "-c", LOGGED, READER, "--workload", cell,
                     "--seed", seed, "--seconds", args.seconds, "--trace",
                     trace, *(["--keep-trace", kept] if trace == "1" else []),
                     *(["--rehearse"] if args.rehearse else [])],
                    cwd=sides[side], env=environment(flavour, scratch),
                    capture_output=True, text=True)
                wall = time.monotonic() - t0
                spans = host_spans(kept) if trace == "1" else None
                shutil.rmtree(kept, ignore_errors=True)
                lines = p.stdout.strip().splitlines()
                try:
                    result = json.loads(lines[-1])
                except (IndexError, ValueError):
                    result = None
                first = tagged(p.stderr, "FIRST")
                out.write(json.dumps({
                    "side": side, "cell": cell, "seed": int(seed),
                    "trace": int(trace), "flavour": flavour,
                    "rc": p.returncode, "wall_s": wall,
                    "profiles": tagged(p.stderr, "PROFILE"),
                    "setup_end_s": tagged(p.stderr, "SETUP_END"),
                    "first": first[-1] if first else None,
                    "host_spans": spans,
                    "result": result}) + "\n")
                out.flush()
                err.write(f"== {run} rc={p.returncode} wall={wall:.0f}\n"
                          f"{p.stderr}\n")
                err.flush()
                ok = result is not None and (result["correct"]
                                             or args.rehearse)
                print(f"{run} rc={p.returncode} correct={ok} "
                      f"wall={wall:.0f}s", flush=True)
                worst = max(worst, p.returncode,
                            0 if ok or side != "change" else 1)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return worst


if __name__ == "__main__":
    sys.exit(main())
