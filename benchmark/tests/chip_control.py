"""Readings for the limits, on the chip at the cell's own size, one process.

    python3 benchmark/tests/chip_control.py --workload h2o_defaults.train \\
        --seeds 201,202,203 --controls fp8,half_batch --control-seeds 3

For every seed: the frame from the seed and the step of the runner's own
set-up (a whole train; for the score cell a train and a predict), then the
cell's check against the plain reference: the sound readings. For the first
``--control-seeds`` seeds also each control: the reference in the program's
place, in the precision below or with a fault planted. One JSON line a
reading, with each followed tree's own numbers where the check gives them.
The benchmark's own runs never come here.
"""
import argparse
import gc
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", default="")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("H2O3_PALLAS_INTERPRET", "1")
        os.environ.setdefault("H2O3_HIST_TILE", "512")
    from harness import device, loader
    cell = loader.load_cell(loader.load_benchmark(), args.workload)
    dev = device.require(cell["chips"], args.rehearse)
    device.setup_compile_cache()
    runner = loader.plugin("runners", cell["traffic"]["runner"])
    check = loader.plugin("checks", cell["check"]["check"])
    controls = [c for c in args.controls.split(",") if c]
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        state = runner.setup(cell, seed, args.rehearse)   # raises on a failed step
        product = runner.product(state)
        runner.release(state)
        del state
        gc.collect()
        t1 = time.monotonic()
        for control in [None] + (controls if i < args.control_seeds else []):
            t2 = time.monotonic()
            detail = {}
            numbers = check.run(cell, product, seed, control=control,
                                per_tree=detail)
            print(json.dumps({"workload": cell["name"], "seed": seed,
                              "control": control, "numbers": numbers,
                              "per_tree": detail, "device": dev["kind"],
                              "program_s": t1 - t0,
                              "check_s": time.monotonic() - t2}), flush=True)
        del product
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
