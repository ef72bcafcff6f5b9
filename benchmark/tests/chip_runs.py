"""Runs of the benchmark on the chip, one after another in ONE call, from one
or more checkouts, with the stage profile of every train kept.

    chiprun --timeout 3000 -- python3 benchmark/tests/chip_runs.py \\
        --out chiprun_out/runs.jsonl --side parent=.chip_checkout/parent \\
        parent:xgb_hist.train:29701:0 change:xgb_hist.train:29701:0 \\
        change:xgb_hist.train:29702:1

A run is ``side:cell:seed:trace``: ``benchmark/run.py`` as the driver calls
it, a process of its own, from the side's directory (``change`` is this
checkout; a parent is a ``git archive`` of it with this checkout's
``BENCHMARK.json`` and ``benchmark/`` laid over, in a directory ``.gitignore``
lists). A pair shares a seed; alternate the sides. One JSON line a run: the
result line, the seconds it took, and ``profiles``: ``train_profile`` of the
set-up train and of every train of the window (bin, sketch, digitize, pack,
loop, finalize, queue, spec, total), which an untraced result line does not
carry: a ``train_s`` that stands apart is then placed in its stage. What the
runs wrote to standard error goes to ``<out>.err``.
"""
import argparse
import json
import os
import subprocess
import sys
import time

# the train runner's step, wrapped to print the profile it has just stored
LOGGED = r"""
import json, runpy, sys
sys.path[:0] = ["benchmark", "."]
from harness.runners import train
step = train.step
def logged(state):
    ok = step(state)
    print("PROFILE " + json.dumps(state.profiles[-1]), file=sys.stderr,
          flush=True)
    return ok
train.step = logged
sys.argv[0] = "benchmark/run.py"
runpy.run_path("benchmark/run.py", run_name="__main__")
"""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--side", action="append", default=[],
                    metavar="NAME=DIR", help="a checkout besides change=.")
    ap.add_argument("--seconds", default="16")
    ap.add_argument("--rehearse", action="store_true",
                    help="try this script on the CPU; no run is correct")
    ap.add_argument("runs", nargs="+", metavar="side:cell:seed:trace")
    args = ap.parse_args(argv)
    sides = {"change": ".", **dict(s.split("=", 1) for s in args.side)}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    worst = 0
    with open(args.out, "a") as out, open(args.out + ".err", "a") as err:
        for run in args.runs:
            side, cell, seed, trace = run.split(":")
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "-c", LOGGED, "--workload", cell, "--seed",
                 seed, "--seconds", args.seconds, "--trace", trace,
                 *(["--rehearse"] if args.rehearse else [])],
                cwd=sides[side], capture_output=True, text=True)
            wall = time.monotonic() - t0
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1])
            except (IndexError, ValueError):
                result = None
            profiles = [json.loads(ln[8:]) for ln in p.stderr.splitlines()
                        if ln.startswith("PROFILE ")]
            out.write(json.dumps({"side": side, "cell": cell,
                                  "seed": int(seed), "trace": int(trace),
                                  "rc": p.returncode, "wall_s": wall,
                                  "profiles": profiles,
                                  "result": result}) + "\n")
            out.flush()
            err.write(f"== {run} rc={p.returncode} wall={wall:.0f}\n"
                      f"{p.stderr}\n")
            err.flush()
            ok = result is not None and result["correct"]
            print(f"{run} rc={p.returncode} correct={ok} wall={wall:.0f}s",
                  flush=True)
            worst = max(worst, p.returncode, 0 if ok or side != "change" else 1)
    return worst


if __name__ == "__main__":
    sys.exit(main())
