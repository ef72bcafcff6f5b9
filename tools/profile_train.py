"""Train-path stage profiler — attribute GBM train time to its stages.

Mirrors tools/profile_ingest.py for the training side of the pipeline:
synthesizes a HIGGS-shaped frame (or ingests CSV= / reuses the bench
shape), trains once COLD (spec + compile) and once WARM, and prints ONE
JSON line attributing the warm run to its stages:

  spec_s      frame → dense TrainingSpec (as_matrix, weights, domains)
  bin_s       global-sketch binning / adaptive range setup
  loop_s      the device boosting loop (chunked lax.scan dispatches)
  score_s     host time blocked materializing interval score scalars
  finalize_s  tree device_get + threshold conversion + final metrics
  warm_total_s / warm_over_loop   the headline ratio — ISSUE 2's
              acceptance bar is warm_total <= 2.5x loop at bench shape

plus ``cold_total_s`` (time-to-first-model net of ingest) so compile-
cache regressions are attributable. Stage numbers are read from the
telemetry spans the training driver itself records (h2o3_tpu.telemetry
``train.*`` spans — the same data ``GET /metrics`` and /3/Telemetry
export, so the tool- and REST-reported splits cannot disagree); the
profiler adds no timers of its own around device work, so there is no
double-dispatch skew. The warm run's XLA compile count (the production
``h2o3_xla_compiles_total`` counter) is reported alongside — 0 is the
PR-2 zero-recompile contract.

Env knobs: ROWS (default 2M), NCOL (default 28 features), TREES (20),
DEPTH (6), NBINS (14), HIST (histogram_type, default 'random' like the
bench; set 'quantiles_global' to profile the sketch-binned path),
CSV= (profile a real file through the ingest path instead).

``--xprof-trace [DIR]`` (or XPROF_TRACE_DIR=) wraps the WARM train in a
``jax.profiler.trace`` capture for kernel-level attribution of the
psum/histogram loop — open the dump with xprof/tensorboard
(``python -m xprof.server DIR`` or ``tensorboard --logdir DIR``) to see
per-level fused-histogram kernels and the ICI all-reduce on the
device timeline (the SNIPPETS profiling-harness pattern).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

ROWS = int(os.environ.get("ROWS", 2_000_000))
NCOL = int(os.environ.get("NCOL", 28))
TREES = int(os.environ.get("TREES", 20))
DEPTH = int(os.environ.get("DEPTH", 6))
NBINS = int(os.environ.get("NBINS", 14))
HIST = os.environ.get("HIST", "random")


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _frame():
    import h2o3_tpu as h2o
    csv = os.environ.get("CSV")
    if csv:
        from h2o3_tpu.ingest.parse import parse, parse_setup
        fr = parse([csv], parse_setup([csv]))
        return fr, fr.names[-1]
    rng = np.random.default_rng(42)
    X = rng.normal(size=(ROWS, NCOL)).astype(np.float32)
    logit = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * np.sin(3 * X[:, 4]))
    y = (rng.random(ROWS) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    cols = {f"f{i}": X[:, i] for i in range(NCOL)}
    cols["label"] = y
    return h2o.Frame.from_numpy(cols), "label"


def _train(fr, yname):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    gbm = H2OGradientBoostingEstimator(
        ntrees=TREES, max_depth=DEPTH, nbins=NBINS, learn_rate=0.1,
        distribution="bernoulli", seed=7, min_rows=1.0,
        histogram_type=HIST, score_tree_interval=0, stopping_rounds=0)
    t0 = time.time()
    gbm.train(y=yname, training_frame=fr)
    return gbm.model, time.time() - t0


def _level_split(rows, F, nbins, depth):
    """Standalone per-level timing of the hot kernel, packed binned vs
    f32 adaptive at the profiled shape — attributes the level cost so
    the NEXT 2x is visible per depth, and quantifies the packed-vs-f32
    bytes/row drop at the representation level. Uses the same 'auto'
    dispatch as training (pallas on TPU / interpret escape, scatter on
    CPU); rows are capped off-TPU to keep the probe cheap."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from h2o3_tpu.ops.hist_adaptive import (adaptive_level, binned_level,
                                            pick_W)
    if jax.default_backend() != "tpu":
        rows = min(rows, 1 << 18)
    rng = np.random.default_rng(0)
    W = pick_W(max(nbins, 2))
    dt = np.int8 if W <= 128 else np.int16
    Xh = rng.normal(size=(rows, F)).astype(np.float32)
    X = jnp.asarray(Xh)
    Xt = jnp.asarray(np.ascontiguousarray(Xh.T))
    codes_h = rng.integers(0, max(nbins, 2), size=(rows, F)).astype(dt)
    codes = jnp.asarray(codes_h)
    ct = jnp.asarray(np.ascontiguousarray(codes_h.T))
    ghw = jnp.ones((3, rows), jnp.float32)
    levels = []

    def timeit(fn, *args, reps=3, **kw):
        r = fn(*args, **kw)
        jax.block_until_ready(r)        # warmup/compile
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn(*args, **kw)
            jax.block_until_ready(r)
        return (time.perf_counter() - t0) / reps * 1e3

    for d in range(depth):
        N = 2 ** d
        base = N - 1
        n_prev = N // 2
        np1 = max(n_prev, 1)
        nid = jnp.asarray(
            (base - n_prev + rng.integers(0, max(n_prev, 1), rows))
            .astype(np.int32)) if d else jnp.zeros(rows, jnp.int32)
        tables = (jnp.asarray(rng.integers(0, F, np1).astype(np.float32)),
                  jnp.asarray(rng.integers(1, max(nbins - 1, 2), np1)
                              .astype(np.float32)),
                  jnp.zeros(np1, jnp.float32),
                  jnp.ones(np1, jnp.float32))
        lo = jnp.full((N, F), -3.0, jnp.float32)
        inv = jnp.full((N, F), nbins / 6.0, jnp.float32)
        f32_ms = timeit(partial(adaptive_level, n_prev=n_prev, n_nodes=N,
                                level_base=base, W=W), X, nid, ghw,
                        tables, lo, inv, xt=Xt)
        packed_ms = timeit(partial(binned_level, n_prev=n_prev,
                                   level_base=base, W=W), codes, nid,
                           ghw, tables, ct=ct)
        levels.append({"level": d, "n_nodes": N,
                       "f32_ms": round(f32_ms, 3),
                       "packed_ms": round(packed_ms, 3)})
    return {"rows": rows, "W": W, "levels": levels,
            "bytes_per_row": {"f32": F * 4,
                              "packed": F * int(np.dtype(dt).itemsize)}}


def _fused_pass(rows, F, nbins, depth):
    """Fused-pass view (multi-level streamed windows, ISSUE 17): times a
    full tree grown as windows of L packed binned levels — each window
    ONE jitted dispatch chaining kernel + device split-select, records
    fetched once at the window boundary — at L in {1, 2, 4} (clamped to
    depth). The per-window stage split attributes device loop time vs
    the boundary record fetch, and the per-level delta vs L=1 is the
    dispatch/sync overhead the fusion amortizes (the
    H2O3_LEVELS_PER_PASS lever). Select is a gain-proxy stub shaped
    like _binned_split_level (cumsum + argmax per node), so the window
    executable carries the same level->select->level dependency chain
    as the production window."""
    import jax
    import jax.numpy as jnp
    from functools import partial

    from h2o3_tpu.models.tree import (level_child_sums, levels_per_pass,
                                      sibling_level_hist)
    from h2o3_tpu.ops.hist_adaptive import pick_W
    if jax.default_backend() != "tpu":
        rows = min(rows, 1 << 18)
    W = pick_W(max(nbins, 2))
    dt = np.int8 if W <= 128 else np.int16
    rng = np.random.default_rng(0)
    codes_h = rng.integers(0, max(nbins, 2), size=(rows, F)).astype(dt)
    codes = jnp.asarray(codes_h)
    ct = jnp.asarray(np.ascontiguousarray(codes_h.T))
    ghw = jnp.ones((3, rows), jnp.float32)

    def select_tables(hist, N):
        g, h, _w = hist[0], hist[1], hist[2]          # [N, F, W]
        gl = jnp.cumsum(g, axis=2)
        hl = jnp.cumsum(h, axis=2)
        gt, ht = gl[:, :, -1:], hl[:, :, -1:]
        gain = (gl ** 2 / (hl + 1e-6)
                + (gt - gl) ** 2 / (ht - hl + 1e-6)).reshape(N, -1)
        best = jnp.argmax(gain, axis=1)
        return ((best // W).astype(jnp.float32),
                (best % W).astype(jnp.float32),
                jnp.zeros(N, jnp.float32), jnp.ones(N, jnp.float32))

    def window(codes, ct, nid, ghw, tables, hist, *, d0, Lw):
        recs = []
        for j in range(Lw):
            d = d0 + j
            N = 2 ** d
            nid, sums = level_child_sums(codes, nid, ghw, tables,
                                         N // 2 if d else 0, N - 1, W, ct=ct)
            hist = sibling_level_hist(sums, hist if d else None, tables)
            tables = select_tables(hist, N)
            recs.append(tables[0])
        return nid, tables, hist, recs

    def tree(L):
        nid = jnp.zeros(rows, jnp.int32)
        tables = (jnp.zeros(1, jnp.float32), jnp.ones(1, jnp.float32),
                  jnp.zeros(1, jnp.float32), jnp.zeros(1, jnp.float32))
        loop_s = fetch_s = 0.0
        hist = None
        d = 0
        while d < depth:
            Lw = min(L, depth - d)
            t0 = time.perf_counter()
            nid, tables, hist, recs = wins[(L, d, Lw)](codes, ct, nid, ghw,
                                                       tables, hist)
            jax.block_until_ready(nid)
            t1 = time.perf_counter()
            jax.device_get(recs)           # boundary record fetch
            t2 = time.perf_counter()
            loop_s += t1 - t0
            fetch_s += t2 - t1
            d += Lw
        return loop_s, fetch_s

    out = {"rows": rows, "W": W,
           "auto_levels_per_pass": levels_per_pass(depth, F, W),
           "windows": []}
    base_ms = None
    for L in sorted({1, 2, 4}):
        L = min(L, depth)
        wins = {}
        d = 0
        while d < depth:
            Lw = min(L, depth - d)
            wins[(L, d, Lw)] = jax.jit(partial(window, d0=d, Lw=Lw))
            d += Lw
        tree(L)                            # warm: compile every window
        reps = 3
        loop_s = fetch_s = 0.0
        for _ in range(reps):
            ls, fs = tree(L)
            loop_s += ls
            fetch_s += fs
        loop_ms = loop_s / reps * 1e3
        fetch_ms = fetch_s / reps * 1e3
        per_level = (loop_ms + fetch_ms) / depth
        if L == 1:
            base_ms = per_level
        rec = {"L": L, "windows_per_tree": -(-depth // L),
               "loop_ms": round(loop_ms, 3),
               "boundary_fetch_ms": round(fetch_ms, 3),
               "ms_per_level": round(per_level, 3)}
        if base_ms and L > 1:
            rec["dispatch_overhead_saved"] = round(
                max(0.0, 1 - per_level / base_ms), 3)
        out["windows"].append(rec)
        if L == depth or L >= depth:
            break
    return out


def main():
    import jax
    from h2o3_tpu import telemetry
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    cache = setup_compilation_cache()       # also installs telemetry
    if not telemetry.enabled():
        log("H2O3_TELEMETRY=0: stage/compile attribution unavailable — "
            "those fields will be null/0 (re-run with telemetry enabled)")
    log(f"backend={jax.default_backend()} devices={len(jax.devices())} "
        f"compile_cache={cache}")
    fr, yname = _frame()
    log(f"frame: {fr.nrow}x{fr.ncol} hist={HIST}")

    model, cold_total = _train(fr, yname)
    log(f"cold train {cold_total:.2f}s "
        f"stages={telemetry.stage_seconds('train.')}")
    # stage counters are cumulative: snapshot before the warm run and
    # report the delta — the warm run's own span durations
    stages0 = telemetry.stage_seconds("train.")
    compiles0 = telemetry.registry().value("h2o3_xla_compiles_total")
    h2d0 = telemetry.registry().value("h2o3_h2d_bytes_total")
    # kernel-level attribution of the WARM loop (shared xprof helper,
    # telemetry/profiling.py — the capture holds the per-level histogram
    # kernels and, on a multi-shard mesh, the psum all-reduce on the
    # device timeline); no-op unless --xprof-trace / XPROF_TRACE_DIR
    from h2o3_tpu.telemetry.profiling import last_trace_dir, profile
    with profile("warm_train", log=log):
        model, warm_total = _train(fr, yname)
    trace_dir = last_trace_dir()
    warm_compiles = telemetry.registry().value(
        "h2o3_xla_compiles_total") - compiles0
    warm_h2d = telemetry.registry().value("h2o3_h2d_bytes_total") - h2d0

    # per-phase roofline table (ISSUE 11): the same run that captured
    # the xprof trace carries the chunk executables' cost_analysis —
    # kernel timeline AND FLOP/byte attribution from ONE flag
    perf = model.output.get("perf") or {}
    for pname, pt in (perf.get("phases") or {}).items():
        log(f"roofline[{pname}]: "
            f"{pt['achieved_flops'] / 1e9:.2f} GFLOP/s "
            f"({pt['flops_total'] / 1e9:.2f} GFLOP / "
            f"{pt['device_seconds']:.3f}s)  "
            f"{pt['achieved_bytes_per_s'] / 1e9:.2f} GB/s  "
            f"AI={pt['arith_intensity']} flop/B "
            f"(ridge {pt['ridge_intensity']})  "
            f"mfu={pt['mfu']}  {pt['roofline_regime']}  "
            f"peaks={pt['peak_source']}"
            + (" [informational]" if pt.get("informational") else ""))

    # ONE scrape for every stage read (each samples() pass runs the
    # collector views, incl. an O(live arrays) device-memory walk)
    stages1 = telemetry.stage_seconds(
        "train.", samples=telemetry.registry().samples())

    def stage(name):
        tot = stages1.get(name, {})
        pre = stages0.get(name, {})
        d = tot.get("seconds", 0.0) - pre.get("seconds", 0.0)
        return round(d, 4) if d else None

    loop_s = stage("train.loop") \
        or model.output.get("training_loop_seconds", 0)
    out = {
        "rows": fr.nrow, "ncol": fr.ncol, "trees": model.ntrees_built,
        "depth": DEPTH, "histogram_type": HIST,
        "cold_total_s": round(cold_total, 3),
        "warm_total_s": round(warm_total, 3),
        # stage split from the driver's telemetry spans (same data the
        # REST telemetry endpoints export for this run)
        "spec_s": stage("train.spec"),
        "bin_s": stage("train.bin"),
        "loop_s": round(loop_s, 3),
        "score_s": stage("train.score"),
        "finalize_s": stage("train.finalize"),
        "warm_compiles": int(warm_compiles),
        "warm_over_loop": round(warm_total / max(loop_s, 1e-9), 2),
        "rows_per_sec_warm": round(fr.nrow * model.ntrees_built
                                   / max(loop_s, 1e-9), 1),
        # transfer budget per tree (registry counter delta over the warm
        # train): the dense device-resident path should sit near zero;
        # the streamed path's once-per-tree contract shows up here and
        # in model.output["stream_profile"]
        "h2d_bytes_warm_train": round(warm_h2d),
        "h2d_bytes_per_tree": round(
            warm_h2d / max(model.ntrees_built, 1)),
        "stream_profile": model.output.get("stream_profile"),
        "spmd": model.output.get("spmd"),
        # hot-loop representation (ISSUE 12): what the level kernel
        # streamed — the packed int8/int16 path vs f32, with the
        # cost-analysis-grounded bytes per (row x tree). In the xprof
        # capture above the packed path's kernels are the custom calls
        # named `binned_level_tpu_t` / `binned_level_tpu_stripe` /
        # `binned_route_only_tpu_t` on the device's op line (their
        # pallas_call `name=`, seen on the v5e, PERF.md PR 27).
        "packed_codes": model.output.get("packed_codes"),
        # multi-level fusion (ISSUE 17): how many tree levels each
        # device dispatch covered — max_depth on the dense path (the
        # whole grower traces into one executable), H2O3_LEVELS_PER_PASS
        # on the streamed single-chunk path, 1 per-level otherwise
        "levels_per_dispatch": model.output.get("levels_per_dispatch"),
        "hot_kernel": ((model.output.get("packed_codes") or {})
                       .get("kernel") or "adaptive_level"),
        "hot_loop_bytes_per_row_tree": (
            round(perf.get("train", {}).get("bytes_total", 0)
                  / max(fr.nrow * model.ntrees_built, 1), 2)
            if (perf.get("train") or {}).get("bytes_total") else None),
        # per-phase roofline points (ISSUE 11): cost_analysis-grounded
        # achieved flops/bytes, MFU and regime for the warm train —
        # recorded in the same run as the xprof capture above
        "perf": perf or None,
        "xprof_trace_dir": trace_dir,
    }
    # per-level kernel split (ISSUE 12): standalone binned-vs-f32 level
    # timings at this shape so the roofline table says WHERE the next
    # 2x lives (H2O3_PROFILE_LEVEL_SPLIT=0 skips the probe)
    if os.environ.get("H2O3_PROFILE_LEVEL_SPLIT", "1") not in (
            "0", "false", ""):
        try:
            out["level_split"] = _level_split(fr.nrow, fr.ncol - 1,
                                              NBINS, DEPTH)
            for lv in out["level_split"]["levels"]:
                log(f"level[{lv['level']}] n_nodes={lv['n_nodes']}: "
                    f"f32 {lv['f32_ms']}ms  packed {lv['packed_ms']}ms")
        except Exception as e:  # probe must never sink the profile
            log(f"level-split probe FAILED: {e!r}")
        # fused-pass view (ISSUE 17): per-window stage split at
        # L in {1, 2, 4} — device loop vs boundary fetch, and the
        # dispatch overhead multi-level fusion removes
        try:
            out["fused_pass"] = _fused_pass(fr.nrow, fr.ncol - 1,
                                            NBINS, DEPTH)
            for wv in out["fused_pass"]["windows"]:
                log(f"fused[L={wv['L']}]: {wv['ms_per_level']}ms/level "
                    f"(loop {wv['loop_ms']}ms + fetch "
                    f"{wv['boundary_fetch_ms']}ms / tree)"
                    + (f"  overhead saved "
                       f"{wv['dispatch_overhead_saved']:.0%}"
                       if "dispatch_overhead_saved" in wv else ""))
        except Exception as e:
            log(f"fused-pass probe FAILED: {e!r}")
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
