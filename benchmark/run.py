#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload h2o_defaults.train --seed 7 --seconds 16 --trace 0
    python3 benchmark/run.py --workload h2o_defaults.score --seed 7 --seconds 5 --trace 1 --rehearse

One process; JAX is imported once. Set-up (rows from the seed, the frame, the
first step, which compiles or loads every program) is timed from the start
of this process to the start of the window. The window then runs whole steps
back to back and closes at the first step boundary at or after ``--seconds``.
After it the peak memory is read, the program's state is freed, and the plain
reference decides ``correct``. The last line of standard output is the result.

``--rehearse`` runs the same path on the CPU with the Pallas kernels
interpreted, at the configuration's rehearsal size: for control flow only. It
can never print ``"correct": true``. Without it a run that finds no TPU
prints no result and exits with code 3. ``--keep-trace DIR`` copies a traced
run's ``.xplane.pb`` to DIR before it is removed, for a look by hand
(``benchmark/tests/trace_inventory.py``).
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
TRACE_DIR = os.path.join(REPO_DIR, ".bench_trace")
COUNTERS = ("h2o3_xla_compiles_total", "h2o3_compile_cache_hits_total",
            "h2o3_compile_cache_misses_total", "h2o3_degrade_total",
            "h2o3_retry_total")


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, interpreted kernels, tiny size; never correct")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="copy the traced run's .xplane.pb here")
    return ap.parse_args(argv)


def run_window(runner, state, seconds: float, step_span: str):
    """Whole steps back to back until ``seconds`` have passed; returns
    (elapsed, failed, the time at which each step ended)."""
    from jax.profiler import TraceAnnotation
    failed, ends = 0, []
    with TraceAnnotation("bench.window"):
        t0 = time.monotonic()
        while True:
            try:
                with TraceAnnotation(step_span):
                    ok = runner.step(state)
            except Exception:
                traceback.print_exc()
                ok = False
            failed += not ok
            ends.append(time.monotonic() - t0)
            if ends[-1] >= seconds:
                break
        elapsed = time.monotonic() - t0
    return elapsed, failed, ends


def start_trace(cell_name: str) -> str:
    import jax
    path = os.path.join(TRACE_DIR, cell_name)
    shutil.rmtree(path, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # the harness's annotations, not frames
    opts.host_tracer_level = 2
    jax.profiler.start_trace(path, profiler_options=opts)
    return path


def stop_trace(path: str, keep: str | None = None):
    """The reduced trace; the files go again, a run writes little."""
    import jax
    from harness import trace_reduce
    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(path, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    try:
        return trace_reduce.load(found[-1]) if found else None
    finally:
        if keep:
            os.makedirs(keep, exist_ok=True)
            for f in found:
                shutil.copy(f, keep)
        shutil.rmtree(path, ignore_errors=True)


def per_layer(cell, reading) -> dict:
    from harness.loader import plugin
    out = {}
    for m in cell["metrics"]:
        value = plugin("readers", m["reader"]).read(
            reading, **m.get("arguments", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def decide(cell, product, seed, failed, rehearse):
    """(correct, {name: {value, limit}}) by the cell's check."""
    from harness.loader import plugin
    spec = cell["check"]
    numbers = plugin("checks", spec["check"]).run(cell, product, seed)
    compared, ok = {}, failed == 0
    for name, value in numbers.items():
        limit = spec["limits"].get(name)
        compared[name] = {"value": value, "limit": limit}
        ok = ok and limit is not None and value <= limit
    return bool(ok and not rehearse), compared


def main(argv=None) -> int:
    args = parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("H2O3_PALLAS_INTERPRET", "1")
        os.environ.setdefault("H2O3_HIST_TILE", "512")
    sys.path[:0] = [BENCH_DIR, REPO_DIR]
    from harness import device, loader
    bench = loader.load_benchmark()
    cell = loader.load_cell(bench, args.workload)
    try:
        import h2o3_tpu  # noqa: F401  the system under test
    except ImportError as e:
        log(f"the system under test is not in this checkout: {e}")
        return 4
    try:
        dev = device.require(cell["chips"], args.rehearse)
    except device.NoChip as e:
        log(f"no result: {e}")
        return 3
    device.setup_compile_cache()
    import jax
    from harness.readers import Reading
    traffic = cell["traffic"]
    runner = loader.plugin("runners", traffic["runner"])
    state = runner.setup(cell, args.seed, args.rehearse)
    seconds = args.seconds
    if args.trace:
        trace_path = start_trace(cell["name"])
    before = {n: device.counter_total(n) for n in COUNTERS}
    setup_s = time.monotonic() - T_START
    elapsed, failed, ends = run_window(runner, state, seconds,
                                       traffic["step_span"])
    attempted = len(ends)
    trace = stop_trace(trace_path, args.keep_trace) if args.trace else None
    counters = {n: device.counter_total(n) - before[n] for n in COUNTERS}
    dev["memory_peak_bytes"] = device.memory_peak_bytes(cell["chips"])
    done = attempted - failed
    if args.trace:
        from harness import peaks, trace_reduce
        reading = Reading(config=cell["config"],
                          peaks=None if args.rehearse else peaks.of(dev["kind"]),
                          chips=cell["chips"], step_span=traffic["step_span"],
                          steps=done, elapsed=elapsed,
                          profiles=list(state.profiles), counters=counters,
                          trace=trace)
        metrics = per_layer(cell, reading)
        breakdown = None
        if trace is not None and trace.devices:
            s = trace_reduce.summary(trace, cell["chips"])
            dev.update(busy_s=s["busy_s"], window_s=s["window_s"])
            breakdown = s["breakdown"]
        notes = reading.notes
    else:
        metrics = runner.end_to_end(state, elapsed, done)
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        breakdown, notes = None, {}
    product = runner.product(state)
    info = dict(state.info)
    runner.release(state)
    del state, trace
    gc.collect()
    t_check = time.monotonic()
    correct, compared = decide(cell, product, args.seed, failed,
                               args.rehearse)
    check_s = time.monotonic() - t_check
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dev}
    if breakdown:
        result["breakdown"] = breakdown
    result["run"] = {"workload": cell["name"], "seed": args.seed,
                     "window_s": elapsed, "steps": done, "step_ends_s": ends,
                     "check_s": check_s,
                     "rehearse": args.rehearse, "counters": counters,
                     "notes": notes, "info": info, "jax": jax.__version__}
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    log(f"correct {correct} failed {failed} of {attempted}")
    print(json.dumps(result, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
