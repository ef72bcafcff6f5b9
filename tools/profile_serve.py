"""Serving-path stage profiler: where does a scored row's time go?

Trains a small GBM, deploys it (h2o3_tpu.serve), drives a mixed
single-row + batched load through the micro-batcher, and prints the
stage attribution the batcher records per batch:

  encode  — dict rows → padded float32 matrix (RowCodec / rows_to_matrix)
  queue   — first-enqueue → batch pick-up (the micro-batching tick)
  device  — dispatch + device execution + result fetch
  decode  — host scores → per-row prediction dicts

plus deploy-time warm-compile cost per batch bucket. Stage numbers come
from the telemetry registry (ISSUE 4): ServeStats is a view over the
process-wide metrics the REST endpoints export, and the per-batch
``serve.*`` spans land in the same registry — so this tool, GET
/3/Serve/stats and GET /metrics can never disagree. The warm-path XLA
compile count (production ``h2o3_xla_compiles_total``) is asserted-by-
reporting: it must be 0 after deploy. One JSON line on stdout (same
contract as tools/profile_train.py / profile_ingest.py).

Knobs: H2O3_SERVE_PROF_ROWS (train rows, default 50k),
H2O3_SERVE_PROF_REQUESTS (single-row requests, default 500),
H2O3_SERVE_PROF_BATCH (batched request size, default 512).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import h2o3_tpu as h2o
    from h2o3_tpu import serve, telemetry
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator

    setup_compilation_cache()               # also installs telemetry
    if not telemetry.enabled():
        log("H2O3_TELEMETRY=0: span/compile attribution unavailable — "
            "those fields will be empty (stats still report)")

    rows_n = int(os.environ.get("H2O3_SERVE_PROF_ROWS", 50_000))
    n_req = int(os.environ.get("H2O3_SERVE_PROF_REQUESTS", 500))
    bsz = int(os.environ.get("H2O3_SERVE_PROF_BATCH", 512))
    rng = np.random.default_rng(7)
    F = 12
    X = rng.normal(size=(rows_n, F)).astype(np.float32)
    y = (X[:, 0] - X[:, 1] + 0.3 * rng.normal(size=rows_n) > 0)
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["label"] = np.where(y, "YES", "NO")
    fr = h2o.Frame.from_numpy(cols)

    gbm = H2OGradientBoostingEstimator(ntrees=20, max_depth=5, seed=1)
    t0 = time.time()
    gbm.train(y="label", training_frame=fr)
    log(f"trained in {time.time() - t0:.1f}s")
    model = gbm.model
    model.key = "profile_serve_gbm"

    t0 = time.time()
    dep = serve.deploy(model.key, model=model, max_batch=4096,
                       max_delay_ms=1.0, queue_limit=65536)
    deploy_s = time.time() - t0
    log(f"deployed in {deploy_s:.2f}s; per-bucket warm compile: "
        f"{ {b: round(s, 3) for b, s in dep.scorer.warm_seconds.items()} }")

    names = [f"f{i}" for i in range(F)]
    pool = [{n: float(X[i, j]) for j, n in enumerate(names)}
            for i in range(min(rows_n, 8192))]

    # warm-path compile guard: everything after deploy must compile 0
    # XLA modules — tracked by the PRODUCTION counter, not a test shim
    compiles0 = telemetry.registry().value("h2o3_xla_compiles_total")

    # phase 1: sequential single-row requests (latency path)
    for i in range(n_req):
        dep.predict_rows([pool[i % len(pool)]])
    single = dep.stats.snapshot()

    # phase 2: batched requests (throughput path) — fresh stage counters
    # come from the delta against phase 1's snapshot. Optionally under
    # an xprof capture (shared helper, --xprof-trace / XPROF_TRACE_DIR)
    # for kernel-level attribution of the scoring dispatches
    from h2o3_tpu.telemetry.profiling import last_trace_dir, profile
    n_batches = 32
    with profile("serve_batched", log=log):
        # timed INSIDE the capture: start/stop_trace (trace
        # serialization is hundreds of ms) must not skew the verdict
        t0 = time.time()
        for i in range(n_batches):
            dep.predict_rows(pool[:bsz])
        batch_wall = time.time() - t0
    total = dep.stats.snapshot()

    # phase 3: SAME load through the columnar response path — one
    # vectorized decode per batch instead of per-row dicts (the decode
    # stage delta shows the win; values bit-match the row path)
    t0 = time.time()
    for i in range(n_batches):
        dep.predict_columnar(pool[:bsz])
    col_wall = time.time() - t0
    col_total = dep.stats.snapshot()

    def stage_split(snap, rows):
        ms = snap["stage_ms"]
        tot = sum(ms.values()) or 1.0
        return {s: {"ms_total": round(v, 2),
                    "share": round(v / tot, 4),
                    "us_per_row": round(1e3 * v / max(rows, 1), 2)}
                for s, v in ms.items()}

    batch_stage = {s: total["stage_ms"][s] - single["stage_ms"][s]
                   for s in total["stage_ms"]}
    batch_rows = total["rows"] - single["rows"]
    col_stage = {s: col_total["stage_ms"][s] - total["stage_ms"][s]
                 for s in col_total["stage_ms"]}
    col_rows = col_total["rows"] - total["rows"]
    # per-deployment roofline (ISSUE 11): warm-bucket executable cost x
    # dispatched batches over the measured device stage — printed next
    # to the stage split, captured in the same run as the xprof trace
    perf = dep.perf_snapshot()
    if perf:
        log(f"roofline[serve]: "
            f"{perf['achieved_flops'] / 1e9:.3f} GFLOP/s  "
            f"{perf['achieved_bytes_per_s'] / 1e9:.3f} GB/s  "
            f"AI={perf['arith_intensity']} flop/B "
            f"(ridge {perf['ridge_intensity']})  "
            f"mfu={perf['mfu']}  {perf['roofline_regime']}  "
            f"peaks={perf['peak_source']}"
            + (" [informational]" if perf.get("informational") else ""))

    out = {
        "metric": "serve_stage_profile",
        "deploy_seconds": round(deploy_s, 3),
        "warm_compile_seconds": {
            str(b): round(s, 3)
            for b, s in dep.scorer.warm_seconds.items()},
        "single_row": {
            "requests": n_req,
            "p50_ms": single["p50_ms"], "p99_ms": single["p99_ms"],
            "stages": stage_split(single, single["rows"]),
        },
        "batched": {
            "batch_size": bsz, "batches": n_batches,
            "rows_per_sec": round(batch_rows / max(batch_wall, 1e-9), 1),
            "stages": {s: round(v, 2) for s, v in batch_stage.items()},
            "us_per_row": {s: round(1e3 * v / max(batch_rows, 1), 2)
                           for s, v in batch_stage.items()},
        },
        # columnar response path (?format=columnar / predict_columnar):
        # identical encode/device work, vectorized decode — compare
        # decode us_per_row and rows_per_sec against "batched" above
        "batched_columnar": {
            "batch_size": bsz, "batches": n_batches,
            "rows_per_sec": round(col_rows / max(col_wall, 1e-9), 1),
            "us_per_row": {s: round(1e3 * v / max(col_rows, 1), 2)
                           for s, v in col_stage.items()},
            "decode_speedup": round(
                max(batch_stage.get("decode", 0.0), 1e-9)
                / max(col_stage.get("decode", 1e-9), 1e-9), 2),
        },
        "bucket_fill": total["bucket_fill"],
        "warm_compiles": int(telemetry.registry().value(
            "h2o3_xla_compiles_total") - compiles0),
        # span-level view of the same run (counts prove every batch got
        # stage spans; seconds match the stage_ms sums above)
        "spans": telemetry.stage_seconds("serve."),
        "perf": perf,
        "xprof_trace_dir": last_trace_dir(),
    }
    serve.undeploy(model.key)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
