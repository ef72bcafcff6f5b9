"""Headline benchmark: GBM histogram-tree training throughput, rows/sec/chip.

North star (BASELINE.json): HIGGS-shaped binomial boosting — the reference
runs it through xgboost4j's gpu_hist (C++/CUDA + Rabit); here it's the
fused PACKED binned-code tree kernel on one TPU chip (features binned
once into int8 codes, the gpu_hist global-sketch shape —
ops/hist_adaptive.py binned kernels; ISSUE 12. H2O3_BENCH_HIST=random
recovers the round-5 per-node-adaptive f32 config,
hex/tree/DHistogram.java UniformAdaptive). Throughput = rows × trees /
boost loop seconds (setup excluded, matching how xgboost benchmarks count
ingest separately). AUC is printed alongside: the adaptive kernel at
nbins=62 matches the 254-bin global sketch's AUC on this task (0.8364 vs
0.8366) because per-node range narrowing recovers resolution with depth.

The recorded run is DISK-RESIDENT by default: the HIGGS-shaped CSV is
written once, then ingested through the real two-phase parse path
(native C++ tokenizer fan-out, ingest/parse.py) — the measured frame
came off disk the way the reference's benchmarks ingest theirs. Set
H2O3_BENCH_DISK=0 for the in-memory variant (throughput is the same;
only setup differs — the metric counts the boost loop only, matching
how gpu_hist benchmarks report train time net of ingest).

vs_baseline divides by A100_GPU_HIST_ROWS_PER_SEC = 25e6 — see
BASELINE.md "Denominator" for exactly what that constant stands for,
how it was chosen, and why it cannot be re-measured in this image.

Kernel ceiling (documented for the perf record): the per-level pallas
kernel is MXU-STREAMING-bound — a [3N<=128, K]x[K, F·W] contraction
costs ceil(F·W/512)·K MXU cycles independent of the M=3N dim
(tools/kern_mxu_probe.py: [6,8192]x[8192,896] takes 73% of the
[126,...] time). At W=32 (F·W=896, 2 stripes) that put a ~72M
rows/s/chip structural ceiling on depth-6 training and the round-4
number (68.6M at nbins=30) sat at ~95% of it. The recorded config now
uses W=16 (F·W=448, ONE 512-lane stripe — half the MXU passes) with
the reference's own histogram_type=Random per-tree grid phase
recovering the bin resolution (AUC 0.8360 vs 0.8358 before; table
above). Measured: ~79M rows/s/chip — past the doubled MXU bound's
knee, now co-limited by the one-hot build + routing VPU work. Other
tested escapes — int8 fixed-point contraction (1.33x bare-matmul win,
eaten by Mosaic's lack of i8 select/mul forcing i32 operand builds;
removed in PR 31, git history keeps it), lane-gather range lookups
(Mosaic declines), tile resizing (flat) — are recorded in tools/.

Prints exactly one JSON line on stdout.
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

ROWS = int(os.environ.get("H2O3_BENCH_ROWS", 10_000_000))
TREES = int(os.environ.get("H2O3_BENCH_TREES", 20))
DEPTH = int(os.environ.get("H2O3_BENCH_DEPTH", 6))
# 14 bins (W=16 lanes): F*W=448 fits one 512-lane MXU stripe so each
# level costs HALF the W=32 passes. Round 6 moves the recorded config
# to the PACKED global-quantile sketch (histogram_type=quantiles_global
# + packed_codes auto, ISSUE 12): features bin once into int8 codes and
# the level kernel streams 1 byte/value instead of 4 — the roofline
# lever in the memory-bound regime. Earlier AUC ladder on this task:
# 14-bin random 0.8360 / 30-bin adaptive 0.8358 / 62-bin adaptive
# 0.8364 / 254-bin global 0.8366; the 14-bin quantile sketch places
# bins by mass, not the uniform grid, so it needs no phase jitter.
# H2O3_BENCH_HIST=random recovers the r5 adaptive-kernel config.
NBINS = int(os.environ.get("H2O3_BENCH_NBINS", 14))
HIST_TYPE = os.environ.get("H2O3_BENCH_HIST", "quantiles_global")
# packed_codes param: 'auto' (default — packed wherever compiled pallas
# runs, i.e. TPU), '1' forces the packed representation (CPU smoke
# rounds exercise the scatter reference), '0' forces it off
PACKED = {"1": True, "true": True, "0": False, "false": False}.get(
    os.environ.get("H2O3_BENCH_PACKED", "auto").lower(), "auto")
A100_GPU_HIST_ROWS_PER_SEC = 25e6


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _make_arrays(rows):
    rng = np.random.default_rng(42)
    F = 28  # HIGGS feature count
    X = rng.normal(size=(rows, F)).astype(np.float32)
    logit = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * np.sin(3 * X[:, 4]))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    return X, y, F


def _disk_frame(rows):
    """Disk-resident variant (H2O3_BENCH_DISK=1): materialize the HIGGS-
    shaped dataset as CSV once, then ingest it through the REAL parse
    path (two-phase guess + parallel tokenize, ingest/parse.py) so the
    measured frame came off disk like the reference's benchmarks do.
    Set H2O3_BENCH_CSV to point at an existing CSV (e.g. real HIGGS)."""
    import time as _t
    from h2o3_tpu.ingest.parse import parse, parse_setup
    path = os.environ.get("H2O3_BENCH_CSV") or os.path.join(
        tempfile.gettempdir(), f"h2o3_bench_{rows}.csv")
    if not os.path.exists(path):
        log(f"writing {path} ...")
        X, y, F = _make_arrays(rows)
        t0 = _t.time()
        header = ",".join([f"f{i}" for i in range(F)] + ["label"])
        # write-then-rename: an interrupted write must not leave a
        # truncated file that later runs silently benchmark against
        tmp = path + ".part"
        with open(tmp, "w") as f:
            f.write(header + "\n")
            chunk = 1_000_000
            for s in range(0, rows, chunk):
                e = min(s + chunk, rows)
                block = np.concatenate(
                    [X[s:e], y[s:e, None].astype(np.float32)], axis=1)
                np.savetxt(f, block, delimiter=",", fmt="%.7g")
        os.replace(tmp, path)
        log(f"csv written in {_t.time() - t0:.1f}s")
    t0 = _t.time()
    setup = parse_setup([path])
    t1 = _t.time()
    fr = parse([path], setup)
    t2 = _t.time()
    ingest_s, parse_s = t2 - t0, t2 - t1
    from h2o3_tpu.ingest.parse import LAST_PROFILE
    log(f"ingest: parsed {fr.nrow}x{fr.ncol} from disk in {ingest_s:.1f}s "
        f"({fr.nrow / ingest_s:,.0f} rows/sec, "
        f"{os.path.getsize(path) / 1e6 / parse_s:,.1f} MB/s parse) "
        f"profile={LAST_PROFILE}")
    return fr, ingest_s, parse_s, os.path.getsize(path), path


def _compressed_ingest_round(path, csv_bytes):
    """Multi-member gzip of (a capped prefix of) the bench CSV through
    the member-parallel compressed plane (ingest/compress.py): returns
    UNCOMPRESSED MB/s of the end-to-end compressed import — the number
    perf_gate ratchets as ingest.compressed_mb_per_sec. Cap via
    H2O3_BENCH_COMPRESSED_MB (0 disables the round)."""
    import time as _t
    from h2o3_tpu.ingest.compress import gzip_compress_members
    from h2o3_tpu.ingest.parse import LAST_PROFILE, parse, parse_setup
    cap = int(os.environ.get("H2O3_BENCH_COMPRESSED_MB", 32)) << 20
    if cap <= 0:
        return None
    with open(path, "rb") as f:
        data = f.read(cap)
    if len(data) < csv_bytes:              # cut at a row boundary
        data = data[:data.rfind(b"\n") + 1]
    gz = path + ".member.gz"
    if not os.path.exists(gz):
        with open(gz, "wb") as f:
            f.write(gzip_compress_members(data))
    t0 = _t.time()
    fr = parse([gz], parse_setup([gz]))
    wall = _t.time() - t0
    info = (LAST_PROFILE.get("compressed") or [{}])[0]
    mbps = round(len(data) / 1e6 / wall, 1)
    log(f"compressed ingest: {fr.nrow} rows, members={info.get('members')} "
        f"parallel={info.get('parallel')} "
        f"fallback_ranges={LAST_PROFILE.get('fallback_ranges')} "
        f"{mbps:,.1f} MB/s (uncompressed bytes)")
    return mbps


SERVE_SINGLE_ROWS = int(os.environ.get("H2O3_BENCH_SERVE_ROWS", 300))
SERVE_SECONDS = float(os.environ.get("H2O3_BENCH_SERVE_SECS", 3.0))

# streamed-GBM transfer guard (ISSUE 5): per-tree H2D bytes of the
# memory-pressure path must stay within this factor of the dataset's
# device footprint — the once-per-tree upload contract, asserted per
# round instead of eyeballed. H2O3_BENCH_STREAM_GUARD=0 skips it.
STREAM_GUARD_MAX_RATIO = 1.1


def _streamed_guard_round():
    """Train a small GBM through the FORCED memory-pressure path under a
    budget whose resident window covers the dataset, and check h2d bytes
    per tree against the device footprint (model.output.stream_profile,
    fed by the telemetry byte counters)."""
    import h2o3_tpu as h2o
    from h2o3_tpu import memman
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    rng = np.random.default_rng(11)
    n, F, trees = 40_000, 8, 8
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = X[:, 0] - 0.6 * X[:, 1]
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                            "y", "n")
    x_bytes = n * F * 4
    try:
        # budget below frame+design (forces streaming) but with a
        # resident window that holds the design matrix
        memman.reset(budget=int(2.2 * x_bytes))
        fr = h2o.Frame.from_numpy(cols)
        gbm = H2OGradientBoostingEstimator(
            ntrees=trees, max_depth=4, nbins=16, seed=3,
            score_tree_interval=0, stopping_rounds=0)
        gbm.train(y="resp", training_frame=fr)
        m = gbm.model
        if not m.output.get("streamed"):
            return {"ran": False, "reason": "budget did not force "
                    "streaming (frame layout changed?)"}
        sp = m.output.get("stream_profile") or {}
        per_tree = sp.get("h2d_bytes_per_tree", 0)
        resident = sp.get("h2d_resident_bytes", 0)
        footprint = sp.get("device_footprint_bytes", x_bytes)
        ratio = per_tree / max(footprint, 1)
        # both halves of the contract: steady-state per-tree traffic
        # within budget AND the once-per-train window upload bounded
        # (~X + y/w/margin working vectors), so a 0.0 per-tree ratio
        # can't mask a bloated initial upload
        ok = (ratio <= STREAM_GUARD_MAX_RATIO
              and resident <= 2.0 * footprint)
        return {"ran": True, "trees": sp.get("trees"),
                "chunks": sp.get("chunks"),
                "resident_chunks": sp.get("resident_chunks"),
                "h2d_bytes_per_tree": round(per_tree),
                "h2d_resident_bytes": round(resident),
                "device_footprint_bytes": footprint,
                "ratio": round(ratio, 4),
                "max_ratio": STREAM_GUARD_MAX_RATIO,
                "pass": bool(ok)}
    finally:
        memman.reset()


def _fused_level_round():
    """Multi-level fused dispatch round (ISSUE 17): time the STREAMED
    binned level loop — the path whose per-level host dispatch + sync
    the fused L-level window collapses (the dense chunk body already
    traced its whole loop into one executable, so the headline number
    cannot show this seam). Two legs at identical config, codes and
    bytes/row: H2O3_LEVELS_PER_PASS=1 reproduces the exact pre-fusion
    structure (one dispatch + one host sync per level — what every
    round before r10 ran), the default leg is the fused window. Small
    rows on purpose: the metric guards the dispatch/sync overhead per
    level, which is what dominates when per-level device work is thin
    (the deep-tree tail, fleet-shared chips, preempt-windowed trains).
    Best-of-3 warm loops per leg; the fused leg's level-pass throughput
    is the recorded train.level_loop_rows_per_sec."""
    import h2o3_tpu as h2o
    from h2o3_tpu import memman
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    rng = np.random.default_rng(17)
    n, F, trees, depth = 20_000, 28, 8, 6
    X = rng.normal(size=(n, F)).astype(np.float32)
    logit = X[:, 0] + 0.5 * X[:, 1] * X[:, 2]
    cols = {f"x{i}": X[:, i] for i in range(F)}
    cols["resp"] = np.where(rng.random(n) < 1 / (1 + np.exp(-logit)),
                            "y", "n")
    x_bytes = n * F * 4
    common = dict(ntrees=trees, max_depth=depth, nbins=14, seed=7,
                  distribution="bernoulli", learn_rate=0.1,
                  score_tree_interval=0, stopping_rounds=0,
                  min_rows=1.0, packed_codes=True)

    def leg():
        warm = H2OGradientBoostingEstimator(**common)
        warm.train(y="resp", training_frame=fr)
        best, lpd = None, None
        for _ in range(3):
            m = H2OGradientBoostingEstimator(**common)
            m.train(y="resp", training_frame=fr)
            o = m.model.output
            if not o.get("streamed"):
                return None, None
            t = o["training_loop_seconds"]
            best = t if best is None else min(best, t)
            lpd = o.get("levels_per_dispatch")
        return n * trees * depth / best, lpd

    prev = os.environ.pop("H2O3_LEVELS_PER_PASS", None)
    try:
        # budget below frame+design forces streaming; the resident
        # window still holds the whole code matrix (single chunk), the
        # configuration where windows fuse into one dispatch
        memman.reset(budget=int(2.2 * x_bytes))
        fr = h2o.Frame.from_numpy(cols)
        os.environ["H2O3_LEVELS_PER_PASS"] = "1"
        per_level, _ = leg()
        del os.environ["H2O3_LEVELS_PER_PASS"]
        fused, lpd = leg()
        if per_level is None or fused is None:
            return {"ran": False,
                    "reason": "budget did not force streaming"}
        return {"ran": True, "rows": n, "trees": trees, "depth": depth,
                "levels_per_dispatch": lpd,
                "level_loop_rows_per_sec": round(fused, 1),
                "per_level_rows_per_sec": round(per_level, 1),
                "speedup_vs_per_level": round(fused / per_level, 3)}
    finally:
        if prev is not None:
            os.environ["H2O3_LEVELS_PER_PASS"] = prev
        else:
            os.environ.pop("H2O3_LEVELS_PER_PASS", None)
        memman.reset()


def _serve_round(model, fr, F):
    """Serving benchmark (ISSUE 3): deploy the trained GBM, measure
    single-row request latency (p50/p99 through the full
    encode→queue→device→decode path) and saturated batched throughput
    (8 concurrent clients submitting 512-row requests)."""
    import threading
    from h2o3_tpu import serve
    names = [f"f{i}" for i in range(F)]
    take = 4096
    cols = {n: np.asarray(fr.vec(n).to_numpy())[:take] for n in names}
    rows = [{n: float(cols[n][i]) for n in names} for i in range(take)]

    model.key = model.key or "bench_gbm"
    dep = serve.deploy(model.key, model=model, max_batch=4096,
                       max_delay_ms=1.0, queue_limit=65536)
    try:
        # warm-path sanity + first-use host lazies before timing
        dep.predict_rows(rows[:8])
        # single-row latency: sequential closed-loop client
        for i in range(SERVE_SINGLE_ROWS):
            dep.predict_rows([rows[i % take]])
        p50 = dep.stats.percentile_ms(50)
        p99 = dep.stats.percentile_ms(99)

        # batched throughput: concurrent clients, fixed wall budget
        stop = time.time() + SERVE_SECONDS
        scored = [0] * 8

        def client(ci):
            i = 0
            while time.time() < stop:
                got = dep.predict_rows(rows[(i % 8) * 512:
                                            (i % 8) * 512 + 512])
                scored[ci] += len(got)
                i += 1

        t0 = time.time()
        threads = [threading.Thread(target=client, args=(ci,))
                   for ci in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.time() - t0
        snap = dep.stats.snapshot()
        return {
            "p50_ms": round(p50, 3) if p50 is not None else None,
            "p99_ms": round(p99, 3) if p99 is not None else None,
            "rows_per_sec": round(sum(scored) / max(dt, 1e-9), 1),
            "batch_occupancy": snap["mean_batch_occupancy"],
            "stage_ms": snap["stage_ms"],
            "single_row_requests": SERVE_SINGLE_ROWS,
            # per-deployment roofline point (ISSUE 11): warm-bucket
            # executable cost x dispatched batches over the measured
            # device stage — serve.mfu in the headline JSON
            "perf": dep.perf_snapshot(),
        }
    finally:
        serve.undeploy(model.key)


def _blackbox_round(n=20_000, runs=5):
    """Flight-recorder append cost (ISSUE 19): median enabled-path
    ns/event over ``runs`` batches of ``n`` records into a throwaway
    ring dir (so the measurement never pollutes a shared recovery
    root), plus the events actually recorded. perf_gate bands
    blackbox.ns_per_event against the <=2µs/event budget."""
    import shutil
    import statistics

    from h2o3_tpu import telemetry
    from h2o3_tpu.telemetry import blackbox
    if not telemetry.enabled():
        return {"enabled": False}
    saved = os.environ.get("H2O3_BLACKBOX_DIR")
    tmp = tempfile.mkdtemp(prefix="bench_blackbox_")
    os.environ["H2O3_BLACKBOX_DIR"] = tmp
    blackbox.reset()
    try:
        per_run = []
        for _ in range(runs):
            t0 = time.perf_counter_ns()
            for _i in range(n):
                blackbox.record("placement", member="bench@local",
                                payload="share=0.5 head=1",
                                trace_id="tr-bench")
            per_run.append((time.perf_counter_ns() - t0) / n)
        ns = statistics.median(per_run)
        recorded = blackbox.events_recorded()
        log(f"blackbox: {ns:.0f} ns/event enabled "
            f"({recorded} events recorded)")
        return {"ns_per_event": round(ns, 1),
                "events_recorded": recorded}
    finally:
        blackbox.reset()
        if saved is None:
            os.environ.pop("H2O3_BLACKBOX_DIR", None)
        else:
            os.environ["H2O3_BLACKBOX_DIR"] = saved
        shutil.rmtree(tmp, ignore_errors=True)


def _telemetry_counts():
    """Cumulative telemetry counters (ISSUE 4): diff two calls to
    attribute compiles / cache traffic / transfer bytes to a bench
    phase. Peak device memory is sampled (and folded into the peak
    gauge) at each call so the recorded peak covers the whole round."""
    from h2o3_tpu import telemetry
    mem = telemetry.sample_device_memory()
    reg = telemetry.registry()
    return {
        "compiles": reg.value("h2o3_xla_compiles_total"),
        "cache_hits": reg.value("h2o3_compile_cache_hits_total"),
        "cache_misses": reg.value("h2o3_compile_cache_misses_total"),
        "h2d_bytes": reg.value("h2o3_h2d_bytes_total"),
        "d2h_bytes": reg.value("h2o3_d2h_bytes_total"),
        "peak_device_bytes": mem["peak"] if mem["peak"] is not None
        else reg.value("h2o3_device_peak_bytes"),
    }


def _telemetry_delta(a, b):
    return {k: round(b[k] - a[k]) for k in
            ("compiles", "cache_hits", "cache_misses",
             "h2d_bytes", "d2h_bytes")}


def main():
    import h2o3_tpu as h2o
    from h2o3_tpu import telemetry
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    import jax

    # persistent XLA compile cache: the SECOND process run of this bench
    # skips the cold spec/compile entirely (JAX_COMPILATION_CACHE_DIR, else
    # <checkout>/.jax_cache; time_to_first_model_s below tracks the win).
    # setup_compilation_cache also installs the telemetry collectors, so
    # the compile/cache/transfer counters below see the whole round.
    cache_dir = setup_compilation_cache()
    tel0 = _telemetry_counts()
    log(f"devices: {jax.devices()}  backend: {jax.default_backend()}  "
        f"compile_cache: {cache_dir}")
    ingest_s = parse_s = csv_bytes = None
    ingest_prof = {}
    compressed_mbps = None
    if os.environ.get("H2O3_BENCH_DISK", "1") not in ("0", "false", ""):
        fr, ingest_s, parse_s, csv_bytes, csv_path = _disk_frame(ROWS)
        F = fr.ncol - 1
        # snapshot the plain parse's profile BEFORE the compressed
        # round overwrites LAST_PROFILE
        from h2o3_tpu.ingest.parse import LAST_PROFILE as _LP
        ingest_prof = dict(_LP)
        compressed_mbps = _compressed_ingest_round(csv_path, csv_bytes)
    else:
        X, y, F = _make_arrays(ROWS)
        cols = {f"f{i}": X[:, i] for i in range(F)}
        cols["label"] = y.astype(np.float32)
        fr = h2o.Frame.from_numpy(cols)
    log(f"frame: {ROWS}x{F + 1}")

    common = dict(max_depth=DEPTH, learn_rate=0.1, nbins=NBINS,
                  distribution="bernoulli", seed=7, score_tree_interval=0,
                  stopping_rounds=0, min_rows=1.0,
                  histogram_type=HIST_TYPE, packed_codes=PACKED)
    # warmup: compile the chunked tree scan at the exact shapes/chunk the
    # measured run uses (chunk length is a static scan parameter). Its
    # wall time IS time-to-first-model: ingest/frame excluded, spec +
    # compile + train + metrics included — the cold-start number the
    # persistent compile cache attacks (second process run skips the
    # compile share)
    tel_ingest = _telemetry_counts()
    warm = H2OGradientBoostingEstimator(ntrees=TREES, **common)
    t_cold0 = time.time()
    warm.train(y="label", training_frame=fr)
    time_to_first_model = time.time() - t_cold0
    tel_cold = _telemetry_counts()
    log(f"warmup done in {time_to_first_model:.2f}s; "
        f"warm loop {warm.model.output['training_loop_seconds']:.2f}s "
        f"profile={warm.model.output.get('train_profile')}")

    gbm = H2OGradientBoostingEstimator(ntrees=TREES, **common)
    t0 = time.time()
    gbm.train(y="label", training_frame=fr)
    total = time.time() - t0
    tel_warm = _telemetry_counts()
    warm_h2d_per_tree = ((tel_warm["h2d_bytes"] - tel_cold["h2d_bytes"])
                         / max(TREES, 1))
    loop_s = gbm.model.output["training_loop_seconds"]
    built = gbm.model.ntrees_built
    rows_per_sec = ROWS * built / loop_s
    auc = gbm.model.training_metrics.auc
    log(f"trees={built} loop={loop_s:.2f}s total={total:.2f}s "
        f"rows/sec/chip={rows_per_sec:,.0f} AUC={auc:.4f} "
        f"profile={gbm.model.output.get('train_profile')}")

    # in-CI bf16 numerics guard (driver-run, TPU only): record the bf16
    # vs f32 split-decision parity artifact every round so a kernel
    # numerics regression is CAUGHT, not assumed (BF16_r{N}.json)
    if (jax.default_backend() == "tpu"
            and os.environ.get("H2O3_BENCH_BF16_GUARD", "1") != "0"):
        try:
            rnd = os.environ.get("H2O3_ROUND", "05")
            out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               f"BF16_r{rnd}.json")
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            import bf16_deviation
            # pin the guard's config explicitly — ROWS is a generic env
            # knob shared by the tools/ probes and must not leak in
            bf16_deviation.ROWS = int(
                os.environ.get("H2O3_BF16_GUARD_ROWS", 2_000_000))
            res = bf16_deviation.main()
            with open(out, "w") as f:
                json.dump(res, f, indent=1)
            log(f"bf16 guard: pass={res['pass']} "
                f"auc_delta={res['auc_delta']} -> {out}")
        except Exception as e:  # guard must never sink the headline run
            log(f"bf16 guard FAILED to run: {e!r}")

    serve_out = None
    tel_serve0 = _telemetry_counts()
    if os.environ.get("H2O3_BENCH_SERVE", "1") not in ("0", "false", ""):
        try:
            serve_out = _serve_round(gbm.model, fr, F)
            log(f"serve: p50={serve_out['p50_ms']}ms "
                f"p99={serve_out['p99_ms']}ms "
                f"{serve_out['rows_per_sec']:,.0f} rows/sec "
                f"(occupancy {serve_out['batch_occupancy']})")
        except Exception as e:  # serving must never sink the headline run
            log(f"serve round FAILED to run: {e!r}")

    out = {
        "metric": "gbm_hist_training_throughput",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec/chip",
        "vs_baseline": round(rows_per_sec / A100_GPU_HIST_ROWS_PER_SEC, 4),
        # cold/warm gap tracked per round: cold = first train in this
        # process (spec+compile+train+metrics), warm = the measured
        # second train end-to-end, loop = device boosting loop only
        "time_to_first_model_s": round(time_to_first_model, 2),
        "warm_train_s": round(total, 2),
        "loop_s": round(loop_s, 2),
        # hardware provenance: an off-TPU round is a smoke/trend record
        # — tools/perf_gate.py excludes informational rounds from the
        # hardware-bound ratchet instead of comparing CPU numbers
        # against TPU history
        "backend": jax.default_backend(),
        "informational": jax.default_backend() != "tpu",
    }
    # honest MFU/roofline (ISSUE 11, VERDICT weak #7): computed from the
    # chunk executables' cost_analysis x measured loop device time, not
    # wall-clock guesses; vs_baseline stays for continuity but MFU is
    # the number that survives hardware changes. `informational` is True
    # off-TPU (nominal peaks) — a trend line, not a utilization claim.
    train_perf = (gbm.model.output.get("perf") or {}).get("train") or {}
    out["train.mfu"] = train_perf.get("mfu")
    out["train.roofline_regime"] = train_perf.get("roofline_regime")
    out["train.arith_intensity"] = train_perf.get("arith_intensity")
    out["train.perf_informational"] = train_perf.get("informational")
    # hot-loop representation (ISSUE 12): which bytes the level kernel
    # streamed. hot_loop_bytes_per_row = the feature-operand bytes ONE
    # row costs ONE level pass (representation-level: F x itemsize —
    # the packed lever is a 4x drop here); the _row_tree variant is the
    # cost_analysis-grounded bytes of the whole loop per (row x tree),
    # same name as tools/profile_train.py
    pcinfo = gbm.model.output.get("packed_codes") or {}
    out["train.packed_codes"] = pcinfo
    bpv = pcinfo.get("bytes_per_value", 4) if pcinfo.get("enabled") else 4
    out["train.hot_loop_bytes_per_row"] = F * bpv
    bt = train_perf.get("bytes_total")
    out["train.hot_loop_bytes_per_row_tree"] = (
        round(bt / (ROWS * max(built, 1)), 2) if bt else None)
    # multi-level fused dispatch (ISSUE 17): levels_per_dispatch = how
    # many tree levels one host dispatch grows (the dense chunk body
    # fuses the whole tree; the streamed driver windows by the
    # H2O3_LEVELS_PER_PASS VMEM budget). level_loop_rows_per_sec is
    # recorded by _fused_level_round below — it counts LEVEL PASSES
    # (rows x trees x depth / loop_s) through the STREAMED level loop,
    # the path whose per-level dispatch + host sync the fused window
    # collapses, with an in-round H2O3_LEVELS_PER_PASS=1 leg
    # reproducing the pre-fusion structure at identical codes/bytes
    # per row for the speedup attribution.
    out["train.levels_per_dispatch"] = gbm.model.output.get(
        "levels_per_dispatch")
    if train_perf:
        log(f"train perf: mfu={train_perf.get('mfu')} "
            f"regime={train_perf.get('roofline_regime')} "
            f"ai={train_perf.get('arith_intensity')} flop/B "
            f"peak_source={train_perf.get('peak_source')}"
            + (" (informational: non-table peaks)"
               if train_perf.get("informational") else ""))
    # transfer-minimal pipeline metrics (ISSUE 5): the warm dense train
    # should upload ~nothing per tree (X is device-resident); the
    # streamed guard below asserts the memory-pressure path's
    # once-per-tree contract
    out["train.h2d_bytes_per_tree"] = round(warm_h2d_per_tree)
    if os.environ.get("H2O3_BENCH_STREAM_GUARD", "1") not in ("0", "false",
                                                              ""):
        try:
            guard = _streamed_guard_round()
            out["train.streamed_h2d_guard"] = guard
            log(f"streamed h2d guard: {guard}")
        except Exception as e:  # guard must never sink the headline run
            log(f"streamed h2d guard FAILED to run: {e!r}")
    if os.environ.get("H2O3_BENCH_FUSED_LEVELS", "1") not in ("0", "false",
                                                              ""):
        try:
            fl = _fused_level_round()
            out["train.fused_level_round"] = fl
            if fl.get("ran"):
                out["train.level_loop_rows_per_sec"] = (
                    fl["level_loop_rows_per_sec"])
            log(f"fused level round: {fl}")
        except Exception as e:  # guard must never sink the headline run
            log(f"fused level round FAILED to run: {e!r}")
    # chaos round (ISSUE 6): train+serve under injected faults, guarding
    # the recovery machinery (retry, checkpoint resume, OOM degrade,
    # circuit breaker) the same way transfer budgets are guarded.
    # Runs AFTER the timed rounds so injected faults never skew them.
    # Since ISSUE 9 the round also SIGKILLs a worker process mid-train
    # and asserts boot recovery resumes it bit-identically, emitting
    # resilience.{recovered_after_restart,restart_recovery_s}
    # (H2O3_BENCH_CHAOS_KILL=0 skips that probe).
    if os.environ.get("H2O3_BENCH_CHAOS", "1") not in ("0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_chaos_round
            out["resilience"] = run_chaos_round(rows=2000, log=log)
        except Exception as e:  # must never sink the headline run
            log(f"chaos round FAILED to run: {e!r}")
    # fleet round (ISSUE 13): N serve-replica PROCESSES behind the
    # consistent-hash router, one SIGKILLed mid-traffic — records the
    # multi-replica throughput (vs a single replica at the same client
    # count), the membership shed latency and the rebalance verdict.
    # Informational on CPU (real parallelism but no device contention);
    # the TPU round enforces the >=2.5x speedup + shed-within-one-beat
    # shape. H2O3_BENCH_FLEET=0 skips.
    if os.environ.get("H2O3_BENCH_FLEET", "1") not in ("0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_kill_replica_round
            fl = run_kill_replica_round(log=log)
            # perf_gate's dotted-path lookup resolves
            # fleet.{rows_per_sec,shed_ms} through this nested dict —
            # no flat copies to drift out of sync
            out["fleet"] = fl
            log(f"fleet: {fl.get('replicas')} replicas "
                f"{fl.get('rows_per_sec')} rows/s "
                f"(x{fl.get('speedup')} vs single) "
                f"shed={fl.get('shed_ms')}ms "
                f"rebalance_ok={fl.get('rebalance_ok')}")
        except Exception as e:  # must never sink the headline run
            log(f"fleet round FAILED to run: {e!r}")
    # router-tier round (ISSUE 20): steady-state client affinity —
    # zero-hop dispatch ratio and the affinity path's p50 against the
    # proxy hop over identical request shapes. Emits
    # fleet.{zero_hop_ratio,routed_p50_ms} (ratcheted by
    # tools/perf_gate.py: ratio up, latency down). Shares the fleet
    # kill switch (H2O3_BENCH_FLEET=0 skips).
    if os.environ.get("H2O3_BENCH_FLEET", "1") not in ("0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_router_tier_round
            rt = run_router_tier_round(log=log)
            fl = out.setdefault("fleet", {})
            if isinstance(fl, dict):
                fl["zero_hop_ratio"] = rt.get("zero_hop_ratio")
                fl["routed_p50_ms"] = rt.get("routed_p50_ms")
                fl["proxy_p50_ms"] = rt.get("proxy_p50_ms")
                fl["affinity_ok"] = rt.get("ok")
        except Exception as e:  # must never sink the headline run
            log(f"router-tier round FAILED to run: {e!r}")
    # serving-lane round (ISSUE 20): interactive p99 under a
    # saturating bulk flood vs its solo band — emits
    # serve.interactive_p99_under_bulk_ms (ratcheted by
    # tools/perf_gate.py). H2O3_BENCH_LANES=0 skips.
    if os.environ.get("H2O3_BENCH_LANES", "1") not in ("0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_lane_round
            lr = run_lane_round(log=log)
            out["lanes"] = lr
            out["serve.interactive_p99_under_bulk_ms"] = \
                lr.get("interactive_p99_under_bulk_ms")
            out["serve.interactive_p99_solo_ms"] = \
                lr.get("interactive_p99_solo_ms")
        except Exception as e:  # must never sink the headline run
            log(f"lane round FAILED to run: {e!r}")
    # training-scheduler round (ISSUE 15): budget sized for ONE train,
    # 4 concurrent bulk submissions + 1 interactive preemptor — emits
    # sched.{queue_wait_p50_ms,preempt_resume_ok,oversub_completed}
    # (ratcheted by tools/perf_gate.py). H2O3_BENCH_SCHED=0 skips.
    if os.environ.get("H2O3_BENCH_SCHED", "1") not in ("0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_oversubscribe_round
            sc = run_oversubscribe_round(log=log)
            out["sched"] = sc
            log(f"sched: {sc.get('oversub_completed')}/"
                f"{sc.get('submissions')} completed "
                f"(degraded={sc.get('degraded')}, "
                f"preempted={sc.get('preempted')}, "
                f"resume_ok={sc.get('preempt_resume_ok')}) "
                f"queue_wait_p50={sc.get('queue_wait_p50_ms')}ms")
        except Exception as e:  # must never sink the headline run
            log(f"sched round FAILED to run: {e!r}")
    # fleet-scheduler round (ISSUE 18): two replica processes share a
    # recovery dir; one is SIGKILLed mid-train (evict → requeue on the
    # survivor) and a preempted local train migrates its checkpoint —
    # emits fleetsched.{queue_wait_p50_ms,migrations,resumed_after_evict}
    # (ratcheted by tools/perf_gate.py). H2O3_BENCH_FLEETSCHED=0 skips.
    if os.environ.get("H2O3_BENCH_FLEETSCHED", "1") not in (
            "0", "false", ""):
        try:
            sys.path.insert(0, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "tools"))
            from chaos_sweep import run_kill_replica_training_round
            fs = run_kill_replica_training_round(log=log)
            out["fleetsched"] = fs
            log(f"fleetsched: evict_resume_ok={fs.get('evict_resume_ok')}"
                f" (resumed={fs.get('resumed_after_evict')}) "
                f"migrations={fs.get('migrations')} "
                f"migrate_ok={fs.get('migrate_resume_ok')} "
                f"queue_wait_p50={fs.get('queue_wait_p50_ms')}ms")
        except Exception as e:  # must never sink the headline run
            log(f"fleetsched round FAILED to run: {e!r}")
    # flight-recorder round (ISSUE 19): enabled-path append cost in
    # ns/event + events recorded — emits
    # blackbox.{ns_per_event,events_recorded} (ns_per_event banded by
    # tools/perf_gate.py against the 2µs/event budget).
    # H2O3_BENCH_BLACKBOX=0 skips.
    if os.environ.get("H2O3_BENCH_BLACKBOX", "1") not in ("0", "false",
                                                          ""):
        try:
            out["blackbox"] = _blackbox_round()
        except Exception as e:  # must never sink the headline run
            log(f"blackbox round FAILED to run: {e!r}")
    # per-round telemetry (ISSUE 4): compile count and transfer volume
    # regressions are now tracked in BENCH_*.json, not just wall time.
    # warm_train.compiles is the headline — the zero-recompile contract.
    # With H2O3_TELEMETRY=0 (the overhead-check mode) every counter reads
    # 0 — record that the data is ABSENT, never a fake zero-compile pass.
    if not telemetry.enabled():
        out["telemetry"] = {"enabled": False}
        log("telemetry disabled (H2O3_TELEMETRY=0): no counters recorded")
    else:
        tel_end = _telemetry_counts()
        out["telemetry"] = {
            "total": _telemetry_delta(tel0, tel_end),
            "ingest": _telemetry_delta(tel0, tel_ingest),
            "cold_train": _telemetry_delta(tel_ingest, tel_cold),
            "warm_train": _telemetry_delta(tel_cold, tel_warm),
            # a skipped/failed serve round records NO serve delta — an
            # all-zero entry would read as a passing zero-compile round
            "serve": (_telemetry_delta(tel_serve0, tel_end)
                      if serve_out is not None else None),
            "peak_device_bytes": tel_end["peak_device_bytes"],
        }
        serve_compiles = (out["telemetry"]["serve"] or {}).get("compiles")
        log(f"telemetry: warm_train_compiles="
            f"{out['telemetry']['warm_train']['compiles']} "
            f"serve_compiles={serve_compiles} "
            f"h2d={out['telemetry']['total']['h2d_bytes']:,} "
            f"d2h={out['telemetry']['total']['d2h_bytes']:,} "
            f"peak_dev={out['telemetry']['peak_device_bytes']}")
    if serve_out is not None:
        # online-serving round (h2o3_tpu.serve): single-row latency
        # percentiles through the micro-batcher + saturated batched
        # throughput for the SAME deployed model — the inference half
        # of the training numbers above
        out["serve"] = serve_out
        out["serve.mfu"] = (serve_out.get("perf") or {}).get("mfu")
    if ingest_s is not None:
        # ingest phase reported alongside the headline (the streaming
        # chunk-local parse pipeline, ingest/parse.py): disk CSV →
        # typed sharded Frame, rows/sec of wall-clock parse time
        out["ingest_seconds"] = round(ingest_s, 1)
        out["ingest_rows_per_sec"] = round(fr.nrow / ingest_s, 1)
        # parse throughput in bytes (ISSUE 14): the perf_gate ratchets
        # mb_per_sec UP and fallback_ranges DOWN — a tokenizer
        # regression that silently reroutes ranges through the Python
        # fallback now fails the gate instead of just reading slower
        out["ingest.mb_per_sec"] = round(csv_bytes / 1e6 / parse_s, 1)
        out["ingest.fallback_ranges"] = ingest_prof.get(
            "fallback_ranges", 0)
        # per-chunk streamed H2D: share of device_put wall time hidden
        # under tokenize (ingest/stream.py; None = streaming not taken)
        out["ingest.h2d_overlap_ratio"] = ingest_prof.get(
            "h2d_overlap_ratio")
        # nogil native encode throughput (ISSUE 16): file bytes over
        # worker-pool CPU-seconds spent in the typed column encode
        enc = ingest_prof.get("encode_cpu_s")
        if enc:
            out["ingest.encode_mb_per_sec"] = round(
                csv_bytes / 1e6 / enc, 1)
        if compressed_mbps is not None:
            out["ingest.compressed_mb_per_sec"] = compressed_mbps
    print(json.dumps(out))


if __name__ == "__main__":
    main()
