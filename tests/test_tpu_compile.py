"""The main path's kernels, compiled for a v5e that is described, not
attached (on-chip-measurement guide, section 2): what Mosaic or XLA:TPU
would refuse on the chip fails here, at no chip time. Nothing runs, so
this says nothing about results or speed.

The ONLY file that describes the chip. The topology is described inside
a module-scoped fixture, never at import: one process at a time may load
libtpu, and under xdist every worker imports every test file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from h2o3_tpu.models.tree import node_lookup, predict_raw_stacked
from h2o3_tpu.ops import hist_adaptive as ha
from h2o3_tpu.ops import hist_pallas
from h2o3_tpu.ops.binning import lane_widths, stripe_pair_codes

F = 28                    # HIGGS width, the bench and chip_smoke shape
BENCH_ROWS = 10_002_432   # the benchmark's 10M rows, padded to the tile
SCORE_ROWS = 500_000      # the score cell's held-out frame
W = 16                    # nbins=14 -> 16 lanes per feature
ROWS = 8 * ha.TILE
ROOT = (0, 1, 0)          # (n_prev, n_nodes, level_base)
LEVEL5 = (16, 32, 31)     # a packed level holds 3 * n_prev accumulator rows


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _tables(n_prev):
    n = max(n_prev, 1)
    return tuple(((n,), jnp.float32) for _ in range(4))


def _level_operands(code_dtype, n_prev):
    return [((F, ROWS), code_dtype), ((ROWS,), jnp.int32),
            ((3, ROWS), jnp.float32), *_tables(n_prev)]


def _binned_t(level, width=W, code_dtype=jnp.int8):
    """``width`` 256 with int16 codes is XGBoost-hist's shape: 254 bins
    and the NA lane."""
    n_prev, _, base = level

    def fn(ct, nid, ghw, *tables):
        return ha.binned_level_tpu_t(ct, nid, ghw, tables, n_prev, base,
                                     width, tile=ha.TILE)
    return fn, _level_operands(code_dtype, n_prev)


def _binned_stripe(level, mxu_dtype=jnp.bfloat16):
    n_prev, _, base = level

    def fn(ct, nid, ghw, *tables):
        # the operand and F exactly as binned_level hands them over
        return ha.binned_level_tpu_stripe(
            stripe_pair_codes(ct, W), nid, ghw, tables, n_prev, base, W,
            tile=ha.TILE, F=ct.shape[0], mxu_dtype=mxu_dtype)
    return fn, _level_operands(jnp.int8, n_prev)


def _binned_route(width=W, code_dtype=jnp.int8):
    def fn(ct, nid, *tables):
        return ha.binned_route_only_tpu_t(ct, nid, tables, 32, 63, width,
                                          tile=ha.TILE)
    return fn, [((F, ROWS), code_dtype), ((ROWS,), jnp.int32), *_tables(32)]


# the airline table's lane layout (GBM-perf, benchmark cell
# airline_gbm.train): 8 features of 12, 31, 7, 100, 22, 300, 300, 100 bins,
# each at its own width on one global lane axis, int16 global-lane codes,
# routing by set (a fifth table [n_prev, 304])
AIRLINE_BINS = (12, 31, 7, 100, 22, 300, 300, 100)
LEVEL9 = (256, 512, 511)      # the last split level of depth 10


def _airline_operands(n_prev, ghw=True):
    n = max(n_prev, 1)
    widths = lane_widths(AIRLINE_BINS)
    ops = [((len(widths), ROWS), jnp.int16), ((ROWS,), jnp.int32)]
    if ghw:
        ops.append(((3, ROWS), jnp.float32))
    return widths, ops + [*_tables(n_prev), ((n, max(widths)), jnp.float32)]


def _binned_t_ragged(level):
    n_prev, _, base = level
    widths, operands = _airline_operands(n_prev)

    def fn(ct, nid, ghw, *tables):
        return ha.binned_level_tpu_t(ct, nid, ghw, tables, n_prev, base,
                                     max(widths), tile=ha.TILE,
                                     widths=widths)
    return fn, operands


def _binned_route_sets():
    widths, operands = _airline_operands(512, ghw=False)

    def fn(ct, nid, *tables):
        return ha.binned_route_only_tpu_t(ct, nid, tables, 512, 1023,
                                          max(widths), tile=ha.TILE)
    return fn, operands


def _adaptive_t(level):
    n_prev, n_nodes, base = level

    def fn(xt, nid, ghw, lo, inv, *tables):
        return ha.adaptive_level_tpu_t(xt, nid, ghw, tables, lo, inv,
                                       n_prev, n_nodes, base, W,
                                       tile=ha.TILE)
    ops = _level_operands(jnp.float32, n_prev)
    ranges = [((n_nodes, F), jnp.float32)] * 2
    return fn, ops[:3] + ranges + ops[3:]


def _hist_pallas3():
    rows = 8 * hist_pallas.TILE

    def fn(codes_t, seg, ghw):
        return hist_pallas.hist_pallas3(codes_t, seg, ghw, 32, W)
    return fn, [((32, rows), jnp.int32), ((rows,), jnp.int32),
                ((3, rows), jnp.float32)]


def _scorer(rows, trees, depth):
    nodes = 2 ** (depth + 1) - 1

    def fn(X, feat, thr, na_left, is_split, value):
        return predict_raw_stacked(X, feat, thr, na_left, is_split, value,
                                   depth)
    tm = (trees, nodes)
    return fn, [((rows, F), jnp.float32), (tm, jnp.int32), (tm, jnp.float32),
                (tm, jnp.bool_), (tm, jnp.bool_), (tm, jnp.float32)]


def _serve_scorer():
    """The deployed GBM's scorer at the 64-row serving bucket."""
    return _scorer(64, trees=5, depth=6)


CASES = {
    "binned_level_tpu_t-root": lambda: _binned_t(ROOT),
    "binned_level_tpu_t-level5": lambda: _binned_t(LEVEL5),
    "binned_level_tpu_stripe-root": lambda: _binned_stripe(ROOT),
    "binned_level_tpu_stripe-level5": lambda: _binned_stripe(LEVEL5),
    # what histogram_precision='auto' runs below 2^18 rows
    "binned_level_tpu_stripe-level5-f32":
        lambda: _binned_stripe(LEVEL5, jnp.float32),
    "binned_route_only_tpu_t": _binned_route,
    "binned_level_tpu_t-w256-level5": lambda: _binned_t(LEVEL5, 256, jnp.int16),
    "binned_route_only_tpu_t-w256": lambda: _binned_route(256, jnp.int16),
    "binned_level_tpu_t-ragged896-root": lambda: _binned_t_ragged(ROOT),
    "binned_level_tpu_t-ragged896-level9": lambda: _binned_t_ragged(LEVEL9),
    "binned_route_only_tpu_t-sets": _binned_route_sets,
    "adaptive_level_tpu_t-level5": lambda: _adaptive_t(LEVEL5),
    "hist_pallas3": _hist_pallas3,
    "predict_raw_stacked-bucket64": _serve_scorer,
}
PALLAS = {k for k in CASES if not k.startswith("predict_raw_stacked")}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, operands = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*shapes).compile()
    n_mosaic = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert (n_mosaic > 0) == (case in PALLAS), (case, n_mosaic)


def _pallas_eqn(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return eqn
        for sub in eqn.params.values():
            found = _pallas_eqn(sub.jaxpr) if hasattr(sub, "jaxpr") else None
            if found is not None:
                return found
    return None


@pytest.mark.parametrize("case,acc", [
    # the airline cell's last split level: 256 parents, 896 ragged lanes
    ("binned_level_tpu_t-ragged896-level9", (768, 896)),
    # XGBoost-hist's: 16 parents, 28 features of 256 lanes
    ("binned_level_tpu_t-w256-level5", (48, 7168))])
def test_a_level_accumulates_one_child_a_parent_on_v5e(case, acc, one_chip,
                                                       no_persistent_cache):
    """The deepest level of the two long train cells, compiled: scratch
    and output hold one child of every previous-level node, half the rows
    of a level built node by node, and the declared flops are half too."""
    fn, operands = CASES[case]()
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in operands]
    eqn = _pallas_eqn(jax.make_jaxpr(fn)(*shapes).jaxpr)
    assert eqn.outvars[1].aval.shape == acc
    assert [a.shape for a in eqn.params["grid_mapping"].scratch_avals] == [acc]
    n_nodes = 2 * acc[0] // 3
    assert eqn.params["cost_estimate"].flops == (
        2 * 3 * n_nodes * acc[1] * ROWS) // 2
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert f"f32[{acc[0]},{acc[1]}]" in text


@pytest.mark.parametrize("nodes", [127, 63])
def test_margin_update_selects_the_leaf_value_on_v5e(nodes, one_chip,
                                                     no_persistent_cache):
    """The margin update at the benchmark's rows, depth 6 and depth 5:
    the leaf's value is selected in a fusion, with no gather over the rows
    (XLA:TPU keeps one from 65 entries) and no [rows, nodes] temporary."""
    def fn(margin, lr, value, nid):
        return margin + lr * node_lookup(value, nid)
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in [((BENCH_ROWS,), jnp.float32),
                                   ((), jnp.float32),
                                   ((nodes,), jnp.float32),
                                   ((BENCH_ROWS,), jnp.int32)]]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "gather(" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * BENCH_ROWS * 4


@pytest.mark.parametrize("depth", [5, 6])
def test_the_scorer_gathers_nothing_over_the_rows_on_v5e(depth, one_chip,
                                                         no_persistent_cache):
    """The score cell's program ([500000, 28], 50 trees, depth 5) and a
    default XGBoost model's (depth 6): the descent reads whole columns
    and selects, so the optimised HLO holds no gather, and no array of
    rows x nodes: the only [rows, n] are the matrix, one column of it and
    the result."""
    rows, trees, nodes = SCORE_ROWS, 50, 2 ** (depth + 1) - 1
    fn, operands = _scorer(rows, trees, depth)
    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
              for shape, dtype in operands]
    compiled = jax.jit(fn).lower(*shapes).compile()
    text = compiled.as_text()
    assert "gather(" not in text
    beside_rows = {int(a if b == str(rows) else b) for a, b in re.findall(
        r"\[(\d+),(\d+)\]", text) if str(rows) in (a, b)}
    assert beside_rows <= {1, F, trees}, beside_rows
    assert compiled.memory_analysis().temp_size_in_bytes < rows * nodes


@pytest.mark.parametrize("rows,features", [(BENCH_ROWS, F), (40_001_536, 8)],
                         ids=["defaults_10m_x_28", "airline_40m_x_8"])
def test_the_one_device_extremes_sort_nothing_and_keep_no_row_sized_array(
        rows, features, one_chip, no_persistent_cache):
    """The sketch's first pass at the train cells' shapes (ISSUE 36): the
    finite count, min and max of every column in reductions over ``X``, no
    sort in the program and no masked or sorted ``[rows, F]`` copy beside
    the matrix (the parent's sketch held two)."""
    from h2o3_tpu.ops import binning
    compiled = binning._device_extremes.lower(
        jax.ShapeDtypeStruct((rows, features), jnp.float32,
                             sharding=one_chip),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert not re.search(r"\bsort\(", compiled.as_text())
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes <= 3 * features * 4 + 4096
    assert mem.temp_size_in_bytes < rows * 4        # under one column of X


WHOLE_TABLE = 123_534_976      # the airline table's rows, padded over 4 shards


@pytest.fixture(scope="module")
def host_mesh(topo):
    """The four chips of the described host as the platform's mesh."""
    import numpy as np
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))


@pytest.mark.parametrize("what", ["extremes", "digitize"])
def test_the_mesh_bin_stage_keeps_every_row_on_its_chip_on_v5e(
        what, host_mesh, no_persistent_cache):
    """The whole airline table row-sharded over a v5e 2x2 (ISSUE 35): the
    sketch's per-shard extremes reduce F numbers over the data axis and the
    digitise is elementwise: no all-gather, and a chip holds its quarter of
    the matrix and of the codes, not the table."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from h2o3_tpu.ops import binning
    rows = NamedSharding(host_mesh, P("data"))
    whole = NamedSharding(host_mesh, P())
    X = jax.ShapeDtypeStruct((WHOLE_TABLE, 8), jnp.float32, sharding=rows)
    if what == "extremes":
        compiled = binning._mesh_extremes(host_mesh).lower(
            X, jax.ShapeDtypeStruct((), jnp.int32, sharding=whole)).compile()
    else:
        compiled = binning._digitize.lower(
            X, jax.ShapeDtypeStruct((8, 299), jnp.float32, sharding=whole),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=whole),
            dtype=jnp.int32).compile()
    text = compiled.as_text()
    for op in ("all-gather", "all-to-all", "collective-permute"):
        assert op not in text, op
    assert ("all-reduce" in text) == (what == "extremes")
    mem = compiled.memory_analysis()
    quarter = WHOLE_TABLE // 4 * 8 * 4
    assert mem.argument_size_in_bytes <= quarter + 4096 * 8
    assert (mem.temp_size_in_bytes + mem.output_size_in_bytes
            <= 2 * quarter + (1 << 20))
