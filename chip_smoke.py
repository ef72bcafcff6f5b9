"""Chip smoke: the main path once, on the device, through the user's entry points.

    python chip_smoke.py                 # one chip: every phase below
    python chip_smoke.py --chips 4       # four chips: the sharded train only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse --rows 4096 --csv-rows 2048

Phases (one JSON object per line on stdout, the verdict on the LAST line):

  device     a TPU is attached; memman and the cost model resolved it
  ingest     HIGGS-shaped CSV -> h2o.import_file (native tokenizer, no fallback)
  train      10M x 28 GBM, depth 6, 14 bins, packed int8 codes -> Pallas level
             kernel; a second identical train compiles nothing
  reference  Pallas path vs the XLA scatter reference on 200k rows
  predict    model.predict on the training frame vs predict_raw_stacked
  serve      REST deploy + row scoring over HTTP, held to model.predict
  multichip  (--chips 4 only) the same train on n_data=4 vs a one-device mesh

Any phase that raises or whose check fails ends the run: the last line
then reads ``"ok": false`` and the exit code is non-zero. ``--rehearse``
runs every phase on the CPU with the Pallas kernels interpreted (control
flow only); it exits 0 when they pass but still prints ``"ok": false``,
so a rehearsal can never be taken for a chip run. Seconds printed here
are observations of one run, not benchmark results.

One process: JAX is imported once and nothing this script starts needs
the device. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, else ``<checkout>/.jax_cache`` (cluster_boot.setup_compilation_cache).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import urllib.parse
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

FEATURES = 28                      # HIGGS feature count — never cut
ROWS = 10_000_000                  # the repo's headline shape; --rows cuts it
GBM = dict(max_depth=6, nbins=14, learn_rate=0.1, distribution="bernoulli",
           seed=7, min_rows=1.0, score_tree_interval=0, stopping_rounds=0,
           histogram_type="quantiles_global", packed_codes="auto")
TREES = 5
REF_ROWS, REF_TREES = 200_000, 3
P1_TOL, AUC_TOL = 5e-3, 1e-3       # bf16 one-hot rounding (__graft_entry__)
BUCKETS = (1, 8, 64)
SERVE_TOL = 1e-6                   # served vs model.predict, see phase_serve


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


def verdict(phase: str, failures, **fields) -> None:
    """Emit the phase line WITH its diagnostics, then fail if any check
    did not hold — a failed comparison still shows what it compared."""
    emit(phase, ok=not failures, **fields)
    check(not failures, "; ".join(failures))


def make_arrays(rows: int, seed: int):
    """bench.py's HIGGS-shaped generator, seeded from --seed."""
    import numpy as np
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(rows, FEATURES)).astype(np.float32)
    logit = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * np.sin(3 * X[:, 4]))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.int32)
    return X, y


def columns(X, y):
    import numpy as np
    cols = {f"f{i}": X[:, i] for i in range(FEATURES)}
    cols["label"] = y.astype(np.float32)
    return cols


def counter_total(name: str) -> float:
    """Sum of one telemetry counter over all its label sets."""
    from h2o3_tpu import telemetry
    return sum(s["value"] for s in telemetry.registry().samples()
               if s["name"] == name and "value" in s)


def compile_counts() -> dict:
    return {"compiles": counter_total("h2o3_xla_compiles_total"),
            "cache_hits": counter_total("h2o3_compile_cache_hits_total"),
            "cache_misses": counter_total("h2o3_compile_cache_misses_total")}


def delta(a: dict, b: dict) -> dict:
    return {k: int(b[k] - a[k]) for k in a}


def peak_bytes(index: int = 0):
    """One device's peak bytes in use so far (None where unreported)."""
    import jax
    return (jax.devices()[index].memory_stats() or {}).get(
        "peak_bytes_in_use")


def on_platform(arr, platform: str) -> bool:
    return all(d.platform == platform for d in arr.devices())


def split_agreement(a, b) -> float:
    """Share of nodes whose (split feature, bin-derived threshold) agree."""
    import numpy as np
    fa, fb = np.asarray(a._feat), np.asarray(b._feat)
    ta, tb = np.asarray(a._thr), np.asarray(b._thr)
    live = (fa >= 0) | (fb >= 0)
    same = (fa == fb) & ((ta == tb) | (fa < 0))
    return float(same[live].mean()) if live.any() else 1.0


def chunk_hlo(builder) -> str:
    """Compiled HLO of the chunk step the builder last dispatched."""
    return builder.chunk_lowering().compile().as_text()


# ------------------------------------------------------------------ phases


def phase_device(args, device: dict) -> bool:
    """Fill ``device`` as JAX reports it; False when there is no
    accelerator to run on (and this is no rehearsal)."""
    import jax
    import jaxlib
    from importlib import metadata
    dev = jax.devices()[0]
    device.update(platform=dev.platform, kind=dev.device_kind)
    if dev.platform != "tpu" and not args.rehearse:
        emit("device", ok=False, error="no accelerator: jax.devices()[0]."
             f"platform is '{dev.platform}'", **device)
        return False
    check(len(jax.devices()) >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, JAX sees "
          f"{len(jax.devices())}")
    import h2o3_tpu as h2o
    from h2o3_tpu import memman, telemetry
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    cache_dir = setup_compilation_cache()
    h2o.init(n_data=args.chips)
    stats = dev.memory_stats() or {}
    budget = memman.manager().budget
    peaks = telemetry.costmodel.device_peaks()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    if dev.platform == "tpu":
        check(budget == stats.get("bytes_limit"),
              f"memman budget {budget} != bytes_limit "
              f"{stats.get('bytes_limit')}")
        check(peaks["flops_source"] == peaks["bytes_source"] == "table"
              and not peaks["informational"],
              f"cost model has no table row for '{dev.device_kind}': {peaks}")
    emit("device", ok=True, **device, visible_devices=len(jax.devices()),
         bytes_limit=stats.get("bytes_limit"), memman_budget=budget,
         peak_flops=peaks["flops"], peak_bytes_per_s=peaks["bytes_per_s"],
         peak_source=peaks["peak_source"], jax=jax.__version__,
         jaxlib=jaxlib.__version__, libtpu=libtpu, compile_cache=cache_dir,
         rehearse=args.rehearse)
    return True


def phase_ingest(args, platform, workdir):
    import numpy as np
    import h2o3_tpu as h2o
    from h2o3_tpu import native
    from h2o3_tpu.ingest.parse import LAST_PROFILE
    X, y = make_arrays(args.csv_rows, args.seed)
    path = os.path.join(workdir, "higgs_shaped.csv")
    t0 = time.time()
    with open(path, "w") as f:
        f.write(",".join([f"f{i}" for i in range(FEATURES)] + ["label"])
                + "\n")
        np.savetxt(f, np.concatenate([X, y[:, None].astype(np.float32)],
                                     axis=1), delimiter=",", fmt="%.7g")
    write_s = time.time() - t0
    c0 = compile_counts()
    t0 = time.time()
    fr = h2o.import_file(path)
    for v in fr.vecs:
        v.data.block_until_ready()
    ingest_s = time.time() - t0
    check(native.lib() is not None,
          f"native tokenizer did not load: {native.BUILD_ERROR}")
    fallbacks = counter_total("h2o3_ingest_fallback_total")
    check(fallbacks == 0, f"{fallbacks} ingest ranges fell back to Python")
    check((fr.nrow, fr.ncol) == (args.csv_rows, FEATURES + 1),
          f"frame is {fr.nrow}x{fr.ncol}")
    want_types = {**{f"f{i}": "real" for i in range(FEATURES)},
                  "label": "int"}
    check(fr.types == want_types, f"column types {fr.types}")
    host = {"f0": X[:, 0], "f13": X[:, 13], "label": y}
    for name, ref in host.items():
        v, ref = fr.vec(name), ref.astype(np.float64)
        got = (v.mean(), v.sigma(), v.na_count())
        want = (ref.mean(), ref.std(ddof=1), 0)
        # atol: a 1M-row N(0,1) mean sits near 1e-3, below f32 sum noise
        check(np.allclose(got, want, rtol=1e-5, atol=1e-6),
              f"rollups of {name}: {got} != numpy {want}")
        check(on_platform(v.data, platform),
              f"column {name} lives on {v.data.devices()}")
    emit("ingest", ok=True, rows=fr.nrow, cols=fr.ncol,
         csv_bytes=os.path.getsize(path), csv_write_s=round(write_s, 2),
         ingest_s=round(ingest_s, 2), native=True, fallback_ranges=0,
         h2d_overlap_ratio=LAST_PROFILE.get("h2d_overlap_ratio"),
         **delta(c0, compile_counts()))


def train_once(fr, ntrees, **overrides):
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    gbm = H2OGradientBoostingEstimator(ntrees=ntrees, **{**GBM, **overrides})
    c0 = compile_counts()
    t0 = time.time()
    gbm.train(y="label", training_frame=fr)
    return gbm, time.time() - t0, delta(c0, compile_counts())


def check_packed_pallas_train(model, hlo: str) -> dict:
    """What both the one-chip and the four-chip train must show."""
    from h2o3_tpu.ops import hist_adaptive as ha
    out = model.output
    pc = out.get("packed_codes") or {}
    check(pc.get("enabled") is True, f"packed codes not enabled: {pc}")
    check(not out.get("streamed"), "train went through the streamed path")
    for name in ("h2o3_degrade_total", "h2o3_retry_total"):
        fired = counter_total(name)
        check(fired == 0, f"{name} = {fired}")
    kernel = ha.binned_level_kernel(pc["W"], FEATURES)
    n_custom = hlo.count('custom_call_target="tpu_custom_call"')
    if not ha.pallas_interpret():
        check(kernel.startswith("binned_level_tpu") and n_custom > 0,
              f"level kernel '{kernel}', {n_custom} Mosaic custom calls in "
              "the chunk step")
    return {"packed_codes": pc, "level_kernel": kernel,
            "stripe_supported": ha.stripe_supported(),
            "mosaic_custom_calls": n_custom,
            "train_profile": out.get("train_profile"),
            "perf_recorded": bool(out.get("perf"))}


def phase_train(args, X, y):
    import h2o3_tpu as h2o
    t0 = time.time()
    fr = h2o.Frame.from_numpy(columns(X, y))
    frame_s = time.time() - t0
    gbm, cold_s, cold_c = train_once(fr, TREES)
    model = gbm.model
    auc = float(model.training_metrics.auc)
    check(auc > 0.80, f"training AUC {auc}")
    again, warm_s, warm_c = train_once(fr, TREES)
    check(warm_c["compiles"] == 0,
          f"second identical train compiled {warm_c['compiles']} programs")
    facts = check_packed_pallas_train(model, chunk_hlo(gbm))
    emit("train", ok=True, rows=fr.nrow, features=FEATURES, trees=TREES,
         rows_cut_from=ROWS if args.rows < ROWS else None,
         frame_s=round(frame_s, 2), cold_train_s=round(cold_s, 2),
         warm_train_s=round(warm_s, 2), cold=cold_c, warm=warm_c,
         warm_train_profile=again.model.output.get("train_profile"),
         auc=round(auc, 5), peak_bytes_in_use=peak_bytes(), **facts)
    return fr, model


def phase_reference(X, y):
    import numpy as np
    import h2o3_tpu as h2o
    n = min(REF_ROWS, len(y))
    fr = h2o.Frame.from_numpy(columns(X[:n], y[:n]))
    # same f32 semantics on both sides: below 2^18 rows 'auto' runs the
    # kernel's exact f32 contraction, so only a tie can move a split
    pallas, pallas_s, _ = train_once(fr, REF_TREES)
    scatter, scatter_s, _ = train_once(fr, REF_TREES, hist_kernel="scatter")
    p = [np.asarray(g.model.predict(fr).vec("p1").to_numpy())
         for g in (pallas, scatter)]
    dp = float(np.max(np.abs(p[0] - p[1])))
    aucs = [float(g.model.training_metrics.auc) for g in (pallas, scatter)]
    verdict("reference",
            [f"max |dp1| pallas vs scatter = {dp}"] * (dp >= P1_TOL)
            + [f"AUC {aucs}"] * (abs(aucs[0] - aucs[1]) >= AUC_TOL),
            rows=n, trees=REF_TREES, max_abs_dp1=dp,
            auc_pallas=round(aucs[0], 5), auc_scatter=round(aucs[1], 5),
            split_agreement=round(split_agreement(pallas.model,
                                                  scatter.model), 4),
            pallas_train_s=round(pallas_s, 2),
            scatter_train_s=round(scatter_s, 2))


def phase_predict(args, platform, fr, model, X):
    import jax.numpy as jnp
    import numpy as np
    from h2o3_tpu.models.tree import predict_raw_stacked
    t0 = time.time()
    pred = model.predict(fr)
    p1v = pred.vec("p1")
    p1v.data.block_until_ready()
    predict_s = time.time() - t0
    check(pred.nrow == fr.nrow, f"predicted {pred.nrow} of {fr.nrow} rows")
    check(on_platform(p1v.data, platform),
          f"p1 lives on {p1v.data.devices()}")
    p1 = np.asarray(p1v.to_numpy())
    check(np.isfinite(p1).all() and p1.min() >= 0.0 and p1.max() <= 1.0,
          "p1 not finite in [0, 1]")
    idx = np.sort(np.random.default_rng(args.seed).choice(
        fr.nrow, size=min(10_000, fr.nrow), replace=False))
    contribs = predict_raw_stacked(
        jnp.asarray(X[idx]), model._feat, model._thr, model._na_left,
        model._is_split, model._value, model.max_depth)
    margin = np.asarray(model.f0) + np.asarray(contribs).sum(axis=1)
    ref = 1.0 / (1.0 + np.exp(-margin.astype(np.float64)))
    dp = float(np.max(np.abs(p1[idx] - ref)))
    # the margin is the same gathers and sum; the chip's f32 exp and
    # divide are approximations (1.1e-6, ~20 ulp at p=0.5, seen on v5e)
    check(dp < 1e-5, f"predict vs predict_raw_stacked: max |dp1| = {dp}")
    emit("predict", ok=True, rows=pred.nrow, predict_s=round(predict_s, 2),
         sampled=len(idx), max_abs_dp1_vs_reference=dp,
         peak_bytes_in_use=peak_bytes())


def http(port, method, path, payload=None, form=None):
    """One REST call: ``payload`` goes as a JSON body, ``form`` as the
    url-encoded parameters h2o-py sends."""
    body, headers = None, {}
    if payload is not None:
        body = json.dumps(payload).encode()
        headers["Content-Type"] = "application/json"
    elif form is not None:
        body = urllib.parse.urlencode(form).encode()
        headers["Content-Type"] = "application/x-www-form-urlencoded"
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}",
                                 data=body, method=method, headers=headers)
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read().decode())


def phase_serve(platform, model, X):
    import numpy as np
    import h2o3_tpu as h2o
    from h2o3_tpu import dkv, serve
    from h2o3_tpu.api import start_server
    rows_x = X[:BUCKETS[-1]]
    names = [f"f{i}" for i in range(FEATURES)]
    rows = [{n: float(v) for n, v in zip(names, r)} for r in rows_x]
    ref = np.asarray(model.predict(h2o.Frame.from_numpy(
        {n: rows_x[:, i] for i, n in enumerate(names)})).vec("p1").to_numpy())
    dkv.put(model.key, "model", model)
    srv = start_server(port=0)
    try:
        t0 = time.time()
        dep = http(srv.port, "POST", f"/3/Serve/models/{model.key}",
                   form={"buckets": json.dumps(list(BUCKETS)),
                         "max_batch": BUCKETS[-1], "max_delay_ms": 1.0})
        deploy_s = time.time() - t0
        check(dep["compiled_buckets"] == list(BUCKETS),
              f"deployed buckets {dep.get('compiled_buckets')}")
        c0 = compile_counts()
        lat, worst = [], 0.0
        for i in range(20):
            n = BUCKETS[i % len(BUCKETS)]
            lo = i % (len(rows) - n + 1)
            t0 = time.time()
            out = http(srv.port, "POST",
                       f"/3/Predictions/models/{model.key}/rows",
                       {"rows": rows[lo:lo + n]})
            lat.append(time.time() - t0)
            got = np.asarray([p["classProbabilities"]["1"]
                              for p in out["predictions"]], np.float32)
            check(got.shape == (n,), f"request {i}: {got.shape} for {n} rows")
            worst = max(worst, float(np.max(np.abs(got - ref[lo:lo + n]))))
        # the served scorer is one fused program, model.predict the same
        # ops dispatched one by one: bit-equal on the CPU, one f32 ulp
        # (6e-8) apart on the v5e
        check(worst <= SERVE_TOL,
              f"served p1 differs from model.predict by {worst}")
        warm = delta(c0, compile_counts())
        check(warm["compiles"] == 0,
              f"warm serving compiled {warm['compiles']} programs")
        cloud = http(srv.port, "GET", "/3/Cloud")
        devs = cloud["nodes"][0]["tpu_devices"]
        check(devs and (platform != "tpu" or all("TPU" in d.upper()
                                                 for d in devs)),
              f"/3/Cloud devices {devs}")
        gone = http(srv.port, "DELETE", f"/3/Serve/models/{model.key}")
        check(gone.get("undeployed") is True, f"undeploy said {gone}")
    finally:
        srv.stop()
        serve.shutdown_all()
    emit("serve", ok=True, requests=len(lat), buckets=list(BUCKETS),
         deploy_s=round(deploy_s, 2),
         median_request_ms=round(1e3 * sorted(lat)[len(lat) // 2], 2),
         cloud_devices=devs, max_abs_dp1_vs_predict=worst,
         bit_match=worst == 0.0, **warm)


def phase_multichip(args, X, y):
    """The train phase on n_data=4, held against the same train on a
    one-device mesh of this process. Every check is gathered into one
    verdict: a four-chip run is too dear to show one fault at a time."""
    import numpy as np
    import h2o3_tpu as h2o
    failures = []
    cols = columns(X, y)
    fr = h2o.Frame.from_numpy(cols)
    shards = fr.vec("f0").data.addressable_shards
    shard_devs = sorted({str(s.device) for s in shards})
    if (len(shard_devs) != args.chips
            or len({s.data.shape for s in shards}) != 1):
        failures.append(f"frame shards on {shard_devs}, shapes "
                        f"{[s.data.shape for s in shards]}")
    gbm, cold_s, cold_c = train_once(fr, TREES)
    model = gbm.model
    # a stage that runs on the first chip alone shows as a lopsided peak
    peak = [peak_bytes(i) for i in range(args.chips)]
    if all(peak) and max(peak) / min(peak) >= 2.0:
        failures.append(f"device memory peaks are lopsided: {peak}")
    hlo = chunk_hlo(gbm)
    facts = check_packed_pallas_train(model, hlo)
    n_allreduce = hlo.count(" all-reduce(") + hlo.count(" all-reduce-start(")
    if not n_allreduce:
        failures.append("no all-reduce in the compiled chunk step")
    if model.output["spmd"]["n_data"] != args.chips:
        failures.append(f"spmd record {model.output['spmd']}")
    p4 = np.asarray(model.predict(fr).vec("p1").to_numpy())
    auc4 = float(model.training_metrics.auc)
    del fr
    h2o.init(n_data=1)
    fr1 = h2o.Frame.from_numpy(cols)
    gbm1, one_s, _ = train_once(fr1, TREES)
    if gbm1.model.output["spmd"]["n_data"] != 1:
        failures.append(f"comparison ran on {gbm1.model.output['spmd']}")
    p1 = np.asarray(gbm1.model.predict(fr1).vec("p1").to_numpy())
    dp = float(np.max(np.abs(p4 - p1)))
    auc1 = float(gbm1.model.training_metrics.auc)
    if dp >= P1_TOL:
        failures.append(f"max |dp1| four chips vs one = {dp}")
    if abs(auc4 - auc1) >= AUC_TOL or auc4 <= 0.80:
        failures.append(f"AUC {auc4} on four chips, {auc1} on one")
    verdict("multichip", failures, rows=len(y), trees=TREES,
            n_data=args.chips, shard_devices=shard_devs,
            shard_rows=int(shards[0].data.shape[0]),
            peak_bytes_in_use=peak, all_reduces=n_allreduce,
            cold_train_s=round(cold_s, 2),
            one_device_train_s=round(one_s, 2), cold=cold_c,
            auc_four=round(auc4, 5), auc_one=round(auc1, 5),
            max_abs_dp1=dp,
            split_agreement=round(split_agreement(model, gbm1.model), 4),
            collective=model.output["spmd"].get("collective"), **facts)


# ------------------------------------------------------------------- main


def run(args, device: dict) -> bool:
    """Every phase in order. Returns False when no accelerator is found
    (nothing ran); raises when a phase fails."""
    if not phase_device(args, device):
        return False
    platform = device["platform"]
    X, y = make_arrays(args.rows, args.seed)
    if args.chips > 1:
        phase_multichip(args, X, y)
        return True
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        phase_ingest(args, platform, workdir)
    fr, model = phase_train(args, X, y)
    phase_reference(X, y)
    phase_predict(args, platform, fr, model, X)
    phase_serve(platform, model, X)
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=ROWS,
                    help="training rows (the width, depth and bins are fixed)")
    ap.add_argument("--csv-rows", type=int, default=1_000_000,
                    help="rows of the CSV the ingest phase writes and parses")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs ONLY the sharded train and its one-device "
                         "comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU + interpreted kernels; never prints ok:true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("H2O3_PALLAS_INTERPRET", "1")
        os.environ.setdefault("H2O3_HIST_TILE", "512")
        if args.chips > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={args.chips}")
    device = {"platform": None, "kind": None, "count": args.chips}
    try:
        ran = run(args, device)
    except BaseException:
        # the verdict is the last stdout line whatever happened; the
        # failure itself still propagates to the traceback and exit code
        print(json.dumps({"ok": False, "device": device}), flush=True)
        raise
    print(json.dumps({"ok": ran and not args.rehearse, "device": device}),
          flush=True)
    return 0 if ran else 2


if __name__ == "__main__":
    sys.exit(main())
