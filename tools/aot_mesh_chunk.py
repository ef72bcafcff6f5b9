"""Compile GBM's sharded chunk step for a DESCRIBED v5e 2x2 host at the
whole airline table's shapes, without a chip (on-chip-measurement guide,
section 2): what XLA:TPU or Mosaic would refuse on four chips (memory, a
kernel that cannot be partitioned) fails here at no chip time, and the
compiled text shows which collectives the compiler put in.

    JAX_PLATFORMS=cpu python tools/aot_mesh_chunk.py [--rows 123534969]

Nothing runs: no result, no time. The packed kernels are asked for by name
(``hist_kernel="pallas"``) because ``jax.default_backend()`` is the CPU
here."""
import argparse
import os
import re
import sys
from dataclasses import replace

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P  # noqa: E402

from h2o3_tpu.models import gbm, tree  # noqa: E402
from h2o3_tpu.ops import hist_adaptive as ha  # noqa: E402
from h2o3_tpu.ops.binning import lane_widths  # noqa: E402

BINS = (12, 31, 7, 100, 22, 300, 300, 100)      # the airline table's bins


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=123_534_969)
    ap.add_argument("--trees", type=int, default=10)
    ap.add_argument("--depth", type=int, default=10)
    args = ap.parse_args()
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    nd = 4
    padded = -(-args.rows // (8 * nd)) * 8 * nd
    per_t = -(-(padded // nd) // ha.TILE) * ha.TILE
    params = dict(gbm.H2OGradientBoostingEstimator().params,
                  max_depth=args.depth, nbins=100, min_rows=10.0,
                  hist_kernel="pallas")
    widths = lane_widths(BINS)
    cfg = replace(tree.tree_config(params, args.depth, 300, 8),
                  set_feats=(True, True, True, False, True, True, True,
                             False),
                  bin_counts=BINS, lane_widths=widths)
    na_bin = tuple(w - 1 for w in widths)
    bucket = tree.chunk_bucket(args.trees)
    step = gbm._compiled_chunk(mesh, cfg, 1, "bernoulli", 1.5, 0.5, None,
                               na_bin, bucket, False, True, False, True,
                               False, False, True)

    def arr(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    rows, rep, f32 = P("data"), P(), jnp.float32
    operands = (
        arr((padded, 8), jnp.int16, rows),                   # codes_rm
        arr((8, per_t * nd), jnp.int16, P(None, "data")),    # codes_t
        arr((padded,), f32, rows), arr((padded,), f32, rows),  # margin, y
        arr((padded,), f32, rows),                           # w
        arr((8 * nd, 8), jnp.int16, rows), arr((8 * nd,), f32, rows),
        arr((2,), jnp.uint32, rep), arr((), f32, rep), arr((), f32, rep),
        arr((8,), f32, rep), arr((8,), f32, rep), arr((8,), f32, rep),
        arr((8,), jnp.int32, rep), arr((1, 8), jnp.bool_, rep),
        arr((), jnp.int32, rep), arr((), jnp.int32, rep),
        arr((), f32, rep), arr((), f32, rep), arr((), f32, rep))
    print(f"rows {args.rows} padded {padded} a shard {padded // nd} "
          f"(t {per_t}) trees {bucket}")
    report("chunk", step.lower(*operands).compile())

    # finalize's binomial metrics over the row-sharded margin: the curve
    # sketch's TPU branch (asked for by name: the backend here is the
    # CPU), the log-loss and the MSE
    from h2o3_tpu.models import metrics

    def finalize(margin, y, w):
        p1 = 1.0 / (1.0 + jnp.exp(-margin))
        yf = y.astype(f32)
        b = (jax.lax.bitcast_convert_type(p1, jnp.uint32)
             >> (32 - metrics._AUC_BIN_BITS)).astype(jnp.int32)
        return (metrics._bucket_sums_by_product(b, w * yf, w * (1.0 - yf)),
                metrics._logloss_kernel(p1, yf, w),
                metrics._regression_kernel(p1, yf, w))
    report("finalize metrics", jax.jit(finalize).lower(
        arr((padded,), f32, rows), arr((padded,), jnp.int32, rows),
        arr((padded,), f32, rows)).compile())
    return 0


def report(name: str, compiled) -> None:
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    print(f"== {name}")
    print("memory a device:", mem)
    found = {}
    for m in re.finditer(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\S+) "
                         r"(all-reduce|all-gather|collective-permute|"
                         r"reduce-scatter|all-to-all)(?:-start)?\(", text,
                         re.M):
        found.setdefault(m.group(3), []).append(m.group(2))
    for op, shapes in found.items():
        print(op, len(shapes), sorted(set(shapes))[:12])
    print("kernels:", len(re.findall("tpu_custom_call", text)))


if __name__ == "__main__":
    sys.exit(main())
