"""The reading that sets ``tree.SCORER_PREDICATE_MAX`` and
``tree.SCORER_ROWS_PER_NODE``: the scorer ``tree.predict_raw_stacked``
under one ``jax.jit``, with the tree's size M, the row count and the form
of the descent (predicate or gather) varied. One JSON line per (rows, M,
form): ms a tree (the best of ``--reps`` calls over the stack's trees), the
seconds to trace and lower and to compile, and whether the two forms'
[rows, T] contributions are equal bit for bit.

    chiprun -- python tools/micro_scorer.py --out chiprun_out/micro_scorer.jsonl
    JAX_PLATFORMS=cpu python tools/micro_scorer.py --rows 4096,64 --sizes 63,511

Rows: the score cell's 500k, a 10M-row batch, the serving bucket of 64;
all sizes at all three take 37 minutes of one v5e at 8 trees and 3 reps (PR
32), most of it the gather form at 10M rows, hence 2 trees there.
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

F = 28


def random_stack(rng, trees: int, depth: int, stop: float = 0.1):
    """Stacked tables of ``trees`` random complete heaps: a node splits
    only under a splitting parent, a tenth of the inner nodes stop early,
    ``feat`` is -1 where nothing splits."""
    M, inner = 2 ** (depth + 1) - 1, 2 ** depth - 1
    is_split = np.zeros((trees, M), bool)
    is_split[:, :inner] = rng.random((trees, inner)) > stop
    for m in range(1, inner):
        is_split[:, m] &= is_split[:, (m - 1) // 2]
    feat = np.where(is_split, rng.integers(0, F, (trees, M)), -1)
    return (feat.astype(np.int32),
            rng.standard_normal((trees, M)).astype(np.float32),
            rng.random((trees, M)) > 0.5, is_split,
            rng.standard_normal((trees, M)).astype(np.float32))


def timed(form: str, depth: int, reps: int, args):
    """(contributions, trace + lower seconds, compile seconds, best
    seconds of ``reps`` calls) of the scorer held to ``form``."""
    def fn(*a):
        return tree.predict_raw_stacked(*a, depth)
    # the rule is read while the call traces: held to the form there
    rule = tree.scorer_node_form
    tree.scorer_node_form = lambda *_: form
    try:
        t0 = time.perf_counter()
        lowered = jax.jit(fn).lower(*args)
        lower_s = time.perf_counter() - t0
    finally:
        tree.scorer_node_form = rule
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0
    out = jax.block_until_ready(compiled(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        best = min(best, time.perf_counter() - t0)
    return out, lower_s, compile_s, best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default="500000,10002432,64")
    ap.add_argument("--sizes", default="63,127,255,511,1023,2047,4095,8191,"
                                       "131071")
    ap.add_argument("--trees", type=int, default=8,
                    help="trees a stack; 2 where rows x nodes pass 2^32")
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    rng = np.random.default_rng(32)
    dev = jax.devices()[0]
    lines = []
    for rows in (int(r) for r in args.rows.split(",")):
        kx, kn = jax.random.split(jax.random.PRNGKey(rows))
        X = jnp.where(jax.random.uniform(kn, (rows, F)) < 0.05, jnp.nan,
                      jax.random.normal(kx, (rows, F), jnp.float32))
        for M in (int(m) for m in args.sizes.split(",")):
            depth = (M + 1).bit_length() - 2
            trees = args.trees if rows * M < 2 ** 32 else 2
            stack = [jnp.asarray(a) for a in random_stack(rng, trees, depth)]
            got = {}
            for form in ("predicate", "gather"):
                got[form], lower_s, compile_s, best = timed(
                    form, depth, args.reps, [X, *stack])
                lines.append({"rows": rows, "M": M, "depth": depth,
                              "trees": trees, "form": form,
                              "ms_a_tree": 1e3 * best / trees,
                              "lower_s": lower_s, "compile_s": compile_s,
                              "device": f"{dev.platform}:{dev.device_kind}"})
            same = bool(jnp.array_equal(
                *(jax.lax.bitcast_convert_type(got[f], jnp.int32)
                  for f in got)))
            del got
            for ln in lines[-2:]:
                ln["forms_equal"] = same
                print(json.dumps(ln), flush=True)
                if args.out:        # line by line: a cut run keeps its part
                    with open(args.out, "a") as f:
                        f.write(json.dumps(ln) + "\n")
    return 0 if all(ln["forms_equal"] for ln in lines) else 1


if __name__ == "__main__":
    sys.exit(main())
