"""Multi-chip GBM scaling bench — rows/s/chip at n_devices ∈ {1, 4, 8}.

The SPMD default path (ISSUE 7) claims near-linear rows/s scaling across
the mesh; this round ASSERTS it instead of eyeballing: the same
HIGGS-shaped train runs on meshes carved from 1, 4 and 8 devices, each
frame rebuilt under its mesh (Frame.resharded), and the verdict compares
rows/s/chip at 8 devices against the single-device number
(``scaling_efficiency_8 >= 0.7`` is the acceptance bar).

The tool runs over the devices JAX sees and forces none: device counts
the host does not have are skipped. To walk the sharded code path
without a chip, give it virtual CPU devices yourself
(``JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8``)
— they share one host's cores, so aggregate throughput physically cannot
scale, and the verdict is then ``informational``
(basis=cpu-virtual-devices) rather than a fake pass/fail. On a real TPU
mesh the verdict is enforced.

Runs standalone (``python tools/multichip_bench.py``), in its own
process: a chip belongs to one process at a time, so nothing that has
touched JAX may spawn it. Prints ONE JSON line on stdout.

Env knobs: H2O3_MC_ROWS (default 1M TPU / 120k CPU), H2O3_MC_TREES (10),
H2O3_MC_DEPTH (6), H2O3_MC_NBINS (14), H2O3_MC_MIN_EFF (0.7).
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    import jax
    import h2o3_tpu as h2o
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    from h2o3_tpu.models.gbm import H2OGradientBoostingEstimator
    from h2o3_tpu.parallel.mesh import current_mesh, make_mesh, set_mesh

    setup_compilation_cache()
    backend = jax.default_backend()
    n_dev = len(jax.devices())
    rows = int(os.environ.get(
        "H2O3_MC_ROWS", 1_000_000 if backend == "tpu" else 120_000))
    trees = int(os.environ.get("H2O3_MC_TREES", 10))
    depth = int(os.environ.get("H2O3_MC_DEPTH", 6))
    nbins = int(os.environ.get("H2O3_MC_NBINS", 14))
    min_eff = float(os.environ.get("H2O3_MC_MIN_EFF", 0.7))
    log(f"backend={backend} devices={n_dev} rows={rows} trees={trees}")

    rng = np.random.default_rng(42)
    F = 28
    X = rng.normal(size=(rows, F)).astype(np.float32)
    logit = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * X[:, 2] * X[:, 3]
             + 0.3 * np.sin(3 * X[:, 4]))
    y = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    cols = {f"f{i}": X[:, i] for i in range(F)}
    cols["label"] = y
    base_fr = h2o.Frame.from_numpy(cols)

    params = dict(ntrees=trees, max_depth=depth, nbins=nbins,
                  learn_rate=0.1, distribution="bernoulli", seed=7,
                  min_rows=1.0, score_tree_interval=0, stopping_rounds=0,
                  histogram_type="random")
    points = []
    old_mesh = current_mesh()
    try:
        for n in (1, 4, 8):
            if n > n_dev:
                log(f"n_devices={n}: skipped (only {n_dev} devices)")
                continue
            mesh = make_mesh(n_data=n, n_model=1,
                             devices=jax.devices()[:n])
            set_mesh(mesh)
            fr = base_fr.resharded(mesh)
            # warm the executables at this mesh's shapes, then measure
            warm = H2OGradientBoostingEstimator(**params)
            warm.train(y="label", training_frame=fr)
            gbm = H2OGradientBoostingEstimator(**params)
            t0 = time.time()
            gbm.train(y="label", training_frame=fr)
            total = time.time() - t0
            m = gbm.model
            assert m.output["spmd"]["n_data"] == n, m.output["spmd"]
            loop_s = m.output["training_loop_seconds"]
            rps = rows * m.ntrees_built / loop_s
            # collective/straggler attribution (ISSUE 8): when the
            # scaling verdict fails, these say whether the loss is a
            # straggling shard or barrier wait — per device count
            coll = m.output["spmd"].get("collective") or {}
            points.append({
                "n_devices": n, "loop_s": round(loop_s, 3),
                "warm_train_s": round(total, 3),
                "rows_per_sec": round(rps, 1),
                "rows_per_sec_per_chip": round(rps / n, 1),
                "auc": round(float(m.training_metrics.auc), 4),
                "straggler_ratio": coll.get("straggler_ratio"),
                "collective_wait_share": coll.get("collective_wait_share"),
                "collective_wait_ms": coll.get("collective_wait_ms")})
            log(f"n={n}: loop={loop_s:.2f}s rows/s={rps:,.0f} "
                f"({rps / n:,.0f}/chip) AUC={points[-1]['auc']} "
                f"straggler={coll.get('straggler_ratio')} "
                f"wait_share={coll.get('collective_wait_share')}")
    finally:
        set_mesh(old_mesh)

    out = {"metric": "multichip_gbm_scaling", "backend": backend,
           "rows": rows, "trees": trees, "depth": depth, "nbins": nbins,
           "points": points, "min_efficiency": min_eff}
    # headline attribution from the WIDEST measured mesh — so a scaling
    # regression is explainable from the BENCH/MULTICHIP JSON alone
    widest = max((p for p in points
                  if p.get("straggler_ratio") is not None),
                 key=lambda p: p["n_devices"], default=None)
    if widest is not None:
        out["straggler_ratio"] = widest["straggler_ratio"]
        out["collective_wait_share"] = widest["collective_wait_share"]
    per_chip = {p["n_devices"]: p["rows_per_sec_per_chip"] for p in points}
    if 1 in per_chip and 8 in per_chip:
        eff = per_chip[8] / per_chip[1]
        out["scaling_efficiency_8"] = round(eff, 4)
        if backend == "tpu":
            out["verdict"] = "pass" if eff >= min_eff else "fail"
        else:
            # 8 virtual CPU devices share one host's cores: aggregate
            # throughput cannot scale, so an efficiency number here is
            # a code-path check, not a hardware claim — never a fake
            # pass (or fail) against the >=70% bar
            out["verdict"] = "informational"
            out["basis"] = "cpu-virtual-devices"
    else:
        out["verdict"] = "skipped"
        out["basis"] = f"only {n_dev} devices"
    print(json.dumps(out))
    return out


if __name__ == "__main__":
    main()
