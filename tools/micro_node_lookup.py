"""The reading that sets ``tree.NODE_SELECT_MAX``: the margin update
``margin + lr * node_lookup(value, nid)`` inside a ``lax.scan`` body, as
the boost chunk has it, with the table's size M and the form (select or
gather) varied. One JSON line per (M, form): ms a lookup (the best of
``--reps`` scans of ``--steps`` trees each, over the steps) and whether the
select's result equals the gather's bit for bit.

    chiprun -- python tools/micro_node_lookup.py --out chiprun_out/micro.jsonl
    JAX_PLATFORMS=cpu python tools/micro_node_lookup.py --rows 4096 --sizes 63,127

The bound is the largest M at which select is at least 2x faster.
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

from h2o3_tpu.models import tree


def timed(form: str, reps: int, margin, tables, nid, lr):
    """(result, seconds of the first call, best seconds of ``reps`` more)
    of the scan with every table looked up in ``form``."""
    def run(margin, tables, nid, lr):
        def one_tree(m, table):
            return m + lr * tree.node_lookup(table, nid), None
        return jax.lax.scan(one_tree, margin, tables)[0]
    fn = jax.jit(run)
    # the rule is read when the first call traces: held to the form there
    rule = tree.NODE_SELECT_MAX
    tree.NODE_SELECT_MAX = tables.shape[1] if form == "select" else 0
    try:
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(margin, tables, nid, lr))
        first_s = time.perf_counter() - t0
    finally:
        tree.NODE_SELECT_MAX = rule
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(margin, tables, nid, lr))
        best = min(best, time.perf_counter() - t0)
    return out, first_s, best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=10_002_432)
    ap.add_argument("--sizes", default="63,127,255,511,1023,2047,4095,8191,"
                                       "16383")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    rng = np.random.default_rng(30)
    margin = jnp.zeros(args.rows, jnp.float32)
    lr = jnp.float32(0.3)
    dev = jax.devices()[0]
    lines = []
    for M in (int(m) for m in args.sizes.split(",")):
        tables = jnp.asarray(
            rng.standard_normal((args.steps, M)).astype(np.float32))
        nid = jnp.asarray(rng.integers(0, M, args.rows).astype(np.int32))
        got = {}
        for form in ("select", "gather"):
            got[form], first_s, best = timed(form, args.reps, margin, tables,
                                             nid, lr)
            lines.append({"M": M, "form": form, "rows": args.rows,
                          "ms_a_lookup": 1e3 * best / args.steps,
                          "first_call_s": first_s,
                          "device": f"{dev.platform}:{dev.device_kind}"})
        same = bool((np.asarray(got["select"]).view(np.int32)
                     == np.asarray(got["gather"]).view(np.int32)).all())
        for ln in lines[-2:]:
            ln["select_equals_gather"] = same
            print(json.dumps(ln), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(json.dumps(ln) + "\n" for ln in lines)
    return 0 if all(ln["select_equals_gather"] for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
