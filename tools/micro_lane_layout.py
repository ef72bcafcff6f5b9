"""The readings that chose the level kernel's LANE LAYOUT and ROUTING FORM
for frames with enum columns (PERF.md section 6, PR 33), on the airline
table's shape: 8 features of 12, 31, 7, 100, 22, 300, 300, 100 bins.

Layout: one level of ``binned_level_tpu_t`` with each feature at its own
width on a global lane axis (``ragged``: 896 lanes) against every feature at
the widest one's power of two (``uniform``: 8 x 512 = 4,096 lanes), at the
levels ``--levels`` names (2^d nodes). Routing, at the last split level's
tables (2^(D-1) nodes): the route kernel by SET (the node's set column by
the node one-hot, the row's entry selected on its code), the same kernel by
threshold (what a numeric frame runs), and a per-row gather ``S[node, code]``
in XLA. One JSON line a reading: seconds, the best of ``--reps``.

    chiprun -- python tools/micro_lane_layout.py --out chiprun_out/lanes.jsonl
    JAX_PLATFORMS=cpu H2O3_PALLAS_INTERPRET=1 H2O3_HIST_TILE=512 \\
        python tools/micro_lane_layout.py --rows 4096 --levels 2 --depth 3
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from h2o3_tpu.ops import hist_adaptive as ha
from h2o3_tpu.ops.binning import lane_widths

BINS = (12, 31, 7, 100, 22, 300, 300, 100)


def best_of(fn, args, reps: int):
    out = jax.block_until_ready(fn(*args))          # compiles
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return out, best


def tables_for(rng, n: int, widths, by_set: bool):
    """A level's routing tables: random features, thresholds or sets."""
    W, off = max(widths), np.asarray(ha.lane_offsets(widths))
    feat = rng.integers(0, len(widths), n)
    t = [jnp.asarray(feat, jnp.float32), None, jnp.zeros(n),
         jnp.ones(n, jnp.float32)]
    if by_set:
        t[1] = jnp.asarray(off[feat], jnp.float32)
        t.append(jnp.asarray(rng.random((n, W)) < 0.5, jnp.float32))
    else:
        t[1] = jnp.asarray(rng.integers(1, 7, n), jnp.float32)
    return tuple(t)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=40_001_536)
    ap.add_argument("--levels", default="0,3,6,9")
    ap.add_argument("--uniform-levels", default="6,9")
    ap.add_argument("--depth", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    rows = -(-args.rows // ha.TILE) * ha.TILE
    interpret = ha.pallas_interpret()
    rng = np.random.default_rng(0)
    layouts = {"ragged": lane_widths(BINS), "uniform": (512,) * len(BINS)}
    local = np.stack([rng.integers(0, b, rows, dtype=np.int16)
                      for b in BINS])                        # [F, rows]
    ghw = jnp.asarray(rng.normal(size=(3, rows)).astype(np.float32))
    lines = []

    def emit(**rec):
        rec.update(rows=rows, device=jax.devices()[0].device_kind)
        lines.append(rec)
        print(json.dumps(rec), flush=True)

    for name, widths in layouts.items():
        W = max(widths)
        ct = jnp.asarray(local + np.asarray(ha.lane_offsets(widths),
                                            np.int16)[:, None])
        levels = args.levels if name == "ragged" else args.uniform_levels
        for d in (int(x) for x in levels.split(",") if x):
            N, n_prev = 2 ** d, (2 ** d) // 2
            nid = jnp.asarray(rng.integers(
                max(N // 2 - 1, 0), max(N - 1, 1), rows).astype(np.int32))
            tabs = tables_for(rng, max(n_prev, 1), widths, True)
            fn = jax.jit(lambda c, n, g, *t, N=N, n_prev=n_prev, W=W,
                         widths=widths: ha.binned_level_tpu_t(
                             c, n, g, t, n_prev, N - 1, W,
                             interpret=interpret, widths=widths))
            _, s = best_of(fn, (ct, nid, ghw) + tabs, args.reps)
            emit(what="level", layout=name, lanes=sum(widths), level=d,
                 nodes=N, seconds=s)
        if name != "ragged":
            continue
        # the routing forms, at the leaves' tables
        n_prev = 2 ** (args.depth - 1)
        base = n_prev - 1
        nid = jnp.asarray(rng.integers(base, base + n_prev, rows
                                       ).astype(np.int32))
        by_set = tables_for(rng, n_prev, widths, True)
        by_thr = tables_for(rng, n_prev, widths, False)
        route = jax.jit(lambda c, n, *t: ha.binned_route_only_tpu_t(
            c, n, t, n_prev, base + n_prev, W, interpret=interpret))
        a, s = best_of(route, (ct, nid) + by_set, args.reps)
        emit(what="route", form="kernel_set", nodes=n_prev, seconds=s)
        _, s = best_of(route, (ct, nid) + by_thr, args.reps)
        emit(what="route", form="kernel_threshold", nodes=n_prev, seconds=s)
        rm = jnp.asarray(local.T)                              # [rows, F]

        def gather(rm, nid, feat, can, sets):
            lid = nid - base
            f = feat[lid].astype(jnp.int32)
            code = jnp.take_along_axis(rm, f[:, None], axis=1)[:, 0]
            right = sets[lid, code.astype(jnp.int32)] < 0.5
            return jnp.where(can[lid] > 0.5,
                             2 * nid + 1 + right.astype(jnp.int32), nid)
        b, s = best_of(jax.jit(gather), (rm, nid, by_set[0], by_set[3],
                                         by_set[4]), args.reps)
        emit(what="route", form="xla_gather", nodes=n_prev, seconds=s,
             equal=bool(jnp.array_equal(a, b)))
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            for rec in lines:
                f.write(json.dumps(rec) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
