"""The readings that set ``frame.factor.RANGE_MAX`` and
``frame.factor.DEVICE_MIN_ROWS``: ``Vec.factor()`` of a device-only
float32 column, by the device's range pass (its bounds held out of the
way) and by the host formula, with seconds of each one's first call for a
column length (the range pass compiles its two programs there) and the
best of ``--reps`` more, and whether domain and codes are equal bit for
bit. One JSON line a column: a 0/1 column at each of ``--rows``, then the
integers 0 .. R-1 at the largest row count for each of ``--ranges``.

    python tools/micro_factor.py --out micro_factor.jsonl    # on a TPU host
    JAX_PLATFORMS=cpu python tools/micro_factor.py --rows 4096 --ranges 2,300

``RANGE_MAX`` keeps the range pass far under the host formula at the
largest R it takes; ``DEVICE_MIN_ROWS`` is the least row count read at
which the range pass repays its first call, compile included, within ten
factors of one column: (first - best) / (host best - best) <= 10.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from h2o3_tpu.frame import factor
from h2o3_tpu.frame.vec import T_REAL, Vec
from h2o3_tpu.parallel.mesh import data_sharding, padded_len


def device_vec(values: np.ndarray) -> Vec:
    """A device-only column, padded with NaN, as the benchmark's label."""
    n = len(values)
    padded = np.full(padded_len(n), np.nan, np.float32)
    padded[:n] = values
    return Vec(jax.device_put(padded, data_sharding()), n, T_REAL)


def timed(make, reps: int):
    """(the Vec made, first seconds, best seconds of ``reps`` more)."""
    def once():
        t0 = time.perf_counter()
        out = make()
        jax.block_until_ready(out.data)
        return out, time.perf_counter() - t0
    out, first = once()
    best = min(once()[1] for _ in range(reps))
    return out, first, best


def reading(rows: int, r: int, reps: int, rng) -> dict:
    vec = device_vec(rng.integers(0, r, rows).astype(np.float32))
    host, host_first, host_best = timed(vec._factor_host, reps)
    made, first, best = timed(lambda: vec.factor()[0], reps)
    return {"rows": rows, "R": r, "path": vec.factor()[1],
            "first_s": round(first, 6), "best_s": round(best, 6),
            "host_first_s": round(host_first, 6),
            "host_best_s": round(host_best, 6),
            "bit_equal": made.domain == host.domain and bool(np.array_equal(
                np.asarray(made.data), np.asarray(host.data))),
            "device": jax.devices()[0].device_kind}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="4096,65536,262144,1048576,2097152,"
                                      "4194304,8388608,10000000")
    ap.add_argument("--ranges", default="16,64,256,1024,4096")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(40)
    out = open(args.out, "a") if args.out else sys.stdout
    factor.RANGE_MAX, factor.DEVICE_MIN_ROWS = 1 << 30, 0
    device_vec(np.zeros(8, np.float32)).factor()   # the backend's own start
    rows = [int(n) for n in args.rows.split(",")]
    cells = [(n, 2) for n in rows] + [
        (max(rows), int(r)) for r in args.ranges.split(",")]
    for n, r in cells:
        print(json.dumps(reading(n, r, args.reps, rng)), file=out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
