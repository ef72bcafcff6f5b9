"""Ingest stage profiler — attribute parse time to its pipeline stages.

Writes a synthetic mixed-type CSV (numeric, enum, time columns with NA
sentinels), runs the REAL end-to-end ``parse()`` (mmap byte-range
fan-out), and reads the stage attribution from the telemetry spans the
pipeline itself records (h2o3_tpu.telemetry): scan (mmap + quote-safe
range discovery), tokenize_encode (native C scan + chunk-local typed
encode, split into tokenize/encode CPU-seconds by the worker stats),
domain_union (enum merge + LUT remap) and device_put (pack + host→device
transfer), plus the h2d transfer-byte counter. The tool keeps NO timers
of its own around pipeline stages — the numbers here are the SAME ones
``GET /metrics`` and ``GET /3/Telemetry`` export, so the tool-reported
and REST-reported splits cannot disagree (ISSUE 4).

Prints ONE JSON line (plus a human per-stage MB/s table on stderr) so a
future ingest regression is attributable to a stage, not just "parse
got slower" — the table is the "where does the next 2x live" artifact
ISSUE 14 asks for. Any byte range that fell back to the Python
tokenizer is listed with its reason; a healthy run shows
``fallback_ranges: 0``.

Args / env knobs: ``--rows N --cols K`` (numeric column count; enum and
time columns ride along via NCOL_ENUM / NCOL_TIME) synthesize the CSV
without a fixture file, so the >=2x claim reproduces anywhere; ``--csv
PATH`` (or CSV env) reuses an existing file; ROWS / NCOL_NUM env still
work for the older driver scripts.
"""
import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _synth_csv(path, rows, ncol_num, ncol_enum, ncol_time):
    rng = np.random.default_rng(11)
    cities = np.array(["ames", "berlin", "cairo", "delhi", "el-paso",
                       "fargo", "galway", "hanoi"])
    header = ([f"n{i}" for i in range(ncol_num)]
              + [f"e{i}" for i in range(ncol_enum)]
              + [f"t{i}" for i in range(ncol_time)])
    log(f"writing {path} ({rows} rows x {len(header)} cols) ...")
    t0 = time.time()
    tmp = path + ".part"
    with open(tmp, "w") as f:
        f.write(",".join(header) + "\n")
        chunk = 200_000
        for s in range(0, rows, chunk):
            e = min(s + chunk, rows)
            cols = []
            for i in range(ncol_num):
                v = np.char.mod("%.6g", rng.normal(size=e - s))
                v[rng.random(e - s) < 0.01] = "NA"
                cols.append(v)
            for i in range(ncol_enum):
                cols.append(cities[rng.integers(0, len(cities), e - s)])
            for i in range(ncol_time):
                days = rng.integers(0, 3650, e - s)
                d = (np.datetime64("2015-01-01") + days).astype(str)
                cols.append(d)
            mat = np.stack(cols, axis=1)
            block = [",".join(row) for row in mat]
            f.write("\n".join(block) + "\n")
    os.replace(tmp, path)
    log(f"csv written in {time.time() - t0:.1f}s")


def _profile_once(path, setup):
    """Run ONE measured parse of ``path`` and return the stage-split
    dict (the JSON-line payload). Factored out so the ``--workers``
    sweep reruns the identical measurement under each pool size."""
    from h2o3_tpu import telemetry
    from h2o3_tpu.ingest.parse import LAST_PROFILE, parse

    # counters are cumulative — diff against the pre-run snapshot
    h2d0 = telemetry.registry().value("h2o3_h2d_bytes_total")
    stages0 = telemetry.stage_seconds("ingest.")

    # optional xprof capture of the parse (shared helper, SNIPPETS [1]
    # shape): --xprof-trace [DIR] / XPROF_TRACE_DIR, else a no-op
    from h2o3_tpu.telemetry.profiling import last_trace_dir, profile
    with profile("ingest_parse", log=log):
        # timed INSIDE the capture: start/stop_trace (trace
        # serialization is hundreds of ms) must not skew the verdict
        t0 = time.perf_counter()
        fr = parse([path], setup)
        wall = time.perf_counter() - t0

    # ONE scrape for every stage read (each samples() pass runs the
    # collector views, incl. an O(live arrays) device-memory walk)
    stages1 = telemetry.stage_seconds(
        "ingest.", samples=telemetry.registry().samples())

    def stage(name):
        tot = stages1.get(name, {})
        pre = stages0.get(name, {})
        # no new span observations (telemetry off) → null, never a fake
        # "0.0s stage" datapoint
        if tot.get("count", 0) == pre.get("count", 0):
            return None
        return round(tot.get("seconds", 0.0) - pre.get("seconds", 0.0), 4)

    nbytes = os.path.getsize(path)
    out = {"rows": fr.nrow, "ncol": fr.ncol,
           "bytes": nbytes,
           "native": LAST_PROFILE.get("native"),
           "chunks": LAST_PROFILE.get("chunks"),
           "streamed": LAST_PROFILE.get("streamed"),
           # range-scoped fallback visibility (ISSUE 14): a healthy run
           # parses every range natively
           "fallback_ranges": LAST_PROFILE.get("fallback_ranges"),
           "fallback_reasons": LAST_PROFILE.get("fallback_reasons"),
           # stage split read from the pipeline's OWN telemetry spans —
           # identical to what GET /metrics exports for the same run
           "scan_s": stage("ingest.scan"),
           "tokenize_encode_s": stage("ingest.tokenize_encode"),
           "domain_union_s": stage("ingest.domain_union"),
           "device_put_s": stage("ingest.device_put"),
           # worker-pool CPU-second split of tokenize_encode (summed
           # across threads, so they exceed the wall split above under
           # fan-out — they answer "which half is the CPU spent in")
           "tokenize_cpu_s": LAST_PROFILE.get("tokenize_cpu_s"),
           "encode_cpu_s": LAST_PROFILE.get("encode_cpu_s"),
           # per-chunk streamed transfer: share of device_put wall time
           # hidden under tokenize (same number the pipeline exports as
           # the h2o3_ingest_h2d_overlap_ratio gauge)
           "h2d_overlap_ratio": LAST_PROFILE.get("h2d_overlap_ratio"),
           "h2d_bytes": round(
               telemetry.registry().value("h2o3_h2d_bytes_total") - h2d0),
           "parse_wall_s": round(wall, 4),
           "parse_rows_per_s": round(fr.nrow / wall, 1),
           "parse_mb_per_s": round(nbytes / 1e6 / wall, 1),
           "xprof_trace_dir": last_trace_dir()}
    return out


def _gil_wait_estimate(out, workers):
    """Estimated thread-seconds the tokenize_encode pool spent NOT
    running Python/C work: ``workers`` threads were nominally live for
    the stage's wall time, and the worker stats say how many CPU-seconds
    they actually burned — the gap is GIL contention + pool idle. A
    nogil-healthy encode keeps this near zero as workers grow; a
    GIL-bound one grows it linearly."""
    te = out.get("tokenize_encode_s")
    cpu = (out.get("tokenize_cpu_s") or 0.0) + (out.get("encode_cpu_s")
                                                or 0.0)
    if te is None or cpu <= 0.0:
        return None
    return round(max(0.0, workers * te - cpu), 4)


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="profile the ingest parse pipeline per stage")
    ap.add_argument("--rows", type=int,
                    default=int(os.environ.get("ROWS", 2_000_000)))
    ap.add_argument("--cols", type=int,
                    default=int(os.environ.get("NCOL_NUM", 6)),
                    help="numeric column count of the synthetic CSV")
    ap.add_argument("--enum-cols", type=int,
                    default=int(os.environ.get("NCOL_ENUM", 2)))
    ap.add_argument("--time-cols", type=int,
                    default=int(os.environ.get("NCOL_TIME", 1)))
    ap.add_argument("--csv", default=os.environ.get("CSV"),
                    help="reuse an existing CSV instead of synthesizing")
    ap.add_argument("--workers", default=os.environ.get("WORKERS"),
                    help="comma list of pool sizes (e.g. 1,4,8,16): "
                         "rerun the parse per size and report the "
                         "scaling + GIL-wait table")
    args = ap.parse_args(argv)

    from h2o3_tpu import telemetry
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    from h2o3_tpu.ingest.parse import parse_setup

    setup_compilation_cache()               # also installs telemetry
    if not telemetry.enabled():
        log("H2O3_TELEMETRY=0: stage attribution unavailable — stage "
            "fields will be null (re-run with telemetry enabled)")
    path = args.csv or os.path.join(
        tempfile.gettempdir(),
        f"h2o3_profile_ingest_{args.rows}x{args.cols}"
        f"_{args.enum_cols}_{args.time_cols}.csv")
    if not os.path.exists(path):
        _synth_csv(path, args.rows, args.cols, args.enum_cols,
                   args.time_cols)
    setup = parse_setup(path)
    nbytes = os.path.getsize(path)

    if args.workers:
        # worker-scaling sweep: same file, same setup, pool size forced
        # per run via the env knob parse() reads. The per-size GIL-wait
        # estimate is the nogil-encode scaling artifact ISSUE 16 asks
        # for: flat ≈0 means the native encode really released the GIL.
        sizes = [int(w) for w in str(args.workers).split(",") if w]
        prev = os.environ.get("H2O3_INGEST_WORKERS")
        sweep = []
        try:
            for w in sizes:
                os.environ["H2O3_INGEST_WORKERS"] = str(w)
                r = _profile_once(path, setup)
                sweep.append({
                    "workers": w,
                    "parse_mb_per_s": r["parse_mb_per_s"],
                    "tokenize_encode_s": r.get("tokenize_encode_s"),
                    "tokenize_cpu_s": r.get("tokenize_cpu_s"),
                    "encode_cpu_s": r.get("encode_cpu_s"),
                    "gil_wait_est_s": _gil_wait_estimate(r, w),
                    "fallback_ranges": r.get("fallback_ranges")})
        finally:
            if prev is None:
                os.environ.pop("H2O3_INGEST_WORKERS", None)
            else:
                os.environ["H2O3_INGEST_WORKERS"] = prev
        log(f"\n  workers   MB/s   tok+enc wall   cpu-s   GIL-wait est")
        for s in sweep:
            te = s["tokenize_encode_s"]
            cpu = (s["tokenize_cpu_s"] or 0) + (s["encode_cpu_s"] or 0)
            gw = s["gil_wait_est_s"]
            log(f"  {s['workers']:>7} {s['parse_mb_per_s']:>6.1f}"
                f"   {te if te is not None else float('nan'):>12.3f}"
                f"   {cpu:>5.2f}"
                f"   {gw if gw is not None else float('nan'):>12.3f}")
        out = {"bytes": nbytes, "csv": path, "worker_sweep": sweep}
        print(json.dumps(out))
        return out

    out = _profile_once(path, setup)
    wall = out["parse_wall_s"]

    # the "where does the next 2x live" table: per-stage seconds and
    # effective MB/s over the file's bytes (wall stages are additive;
    # the cpu-second rows attribute the tokenize_encode wall)
    log(f"\n  stage               seconds   MB/s (of {nbytes / 1e6:.0f} MB)")
    for label, key, kind in (
            ("scan (ranges)", "scan_s", "wall"),
            ("tokenize_encode", "tokenize_encode_s", "wall"),
            ("  tokenize (cpu)", "tokenize_cpu_s", "cpu"),
            ("  encode   (cpu)", "encode_cpu_s", "cpu"),
            ("domain_union", "domain_union_s", "wall"),
            ("device_put", "device_put_s", "wall")):
        v = out.get(key)
        if v is None:
            log(f"  {label:<19} {'-':>7}")
            continue
        rate = nbytes / 1e6 / v if v > 0 else float("inf")
        log(f"  {label:<19} {v:>7.3f}   {rate:,.0f}")
    log(f"  {'TOTAL parse wall':<19} {wall:>7.3f}   "
        f"{out['parse_mb_per_s']:,.1f}")
    if out.get("fallback_ranges"):
        log(f"  fallback ranges: {out['fallback_ranges']} "
            f"({out['fallback_reasons']})")

    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
