"""The reader of a process's FIRST boot, train and predict (ISSUE 37):
``first_root`` against a ring built by hand, the new metrics' files against the
loader's rule, and the traced rehearsal line of each cell, which carries every
one of them."""
import json
import os
import subprocess
import sys

import pytest

from harness import loader
from harness.readers import Reading, first_root

SETUP = {"boot_s", "first_model_s", "first_train_s",
         "first_train_trace_lower_s", "first_train_load_s",
         "first_train_build_s", "first_train_programs"}
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]
TRAIN_CELLS = [c for c in CELLS if c != "h2o_defaults.score"]
NEW = {c: SETUP | ({"first_predict_s"} if c == "h2o_defaults.score"
                   else {"init_s"}) for c in CELLS}
T0 = 1_000.0


def reading():
    return Reading(config={}, peaks=None, chips=1, step_span="bench.train",
                   steps=2)


def train(telemetry, start, seconds, jit=(), **attrs):
    """A root ``train.gbm`` with its ``jit.*`` spans two levels down."""
    root = telemetry.record_span("train.gbm", start, seconds, **attrs)
    loop = telemetry.record_span("train.loop", start, seconds / 2,
                                 parent=root)
    for name, s, n, top in jit:
        telemetry.record_span(name, start, s, parent=loop, n=n,
                              **({"top": top} if top else {}))
    return root


@pytest.fixture
def telemetry():
    from h2o3_tpu import telemetry
    was = telemetry.enabled()
    telemetry.set_enabled(True)
    telemetry.clear_spans()
    dropped = telemetry.registry().counter("h2o3_spans_dropped_total")
    before = dropped.value
    yield telemetry
    dropped.inc(before - dropped.value)
    telemetry.clear_spans()
    telemetry.set_enabled(was)


@pytest.fixture
def ring(telemetry):
    """Import, init, the set-up train (it builds and loads), two window
    trains (they do neither), a first and a second predict."""
    telemetry.record_span("boot.import", T0, 2.0)
    telemetry.record_span("boot.init", T0 + 2.5, 0.5)
    first = train(telemetry, T0 + 10.0, 20.0, jit=[
        ("jit.trace", 1.0, 40, [["chunk", 0.8], ["digitize", 0.2]]),
        ("jit.lower", 2.0, 9, [["jit(chunk)", 1.5]]),
        ("jit.load", 0.5, 7, [["jit(digitize)", 0.5]]),
        ("jit.build", 8.0, 2, [["jit(chunk)", 7.0], ["jit(sketch)", 1.0]])])
    sketch = telemetry.record_span("train.bin.sketch", T0 + 11.0, 1.0,
                                   parent=first)
    telemetry.record_span("jit.build", T0 + 11.0, 0.25, parent=sketch, n=1,
                          top=[["jit(sketch)", 0.25]])
    train(telemetry, T0 + 40.0, 3.0)
    train(telemetry, T0 + 43.0, 3.0, jit=[("jit.trace", 9.0, 1, None)])
    telemetry.record_span("score.predict", T0 + 50.0, 0.75)
    telemetry.record_span("score.predict", T0 + 51.0, 0.25)
    return telemetry


def test_first_root_reads_the_first_root_not_the_last(ring):
    r = reading()

    def read(what, roots=("train.*",), **more):
        return first_root.read(r, list(roots), what, **more)

    assert read("seconds") == 20.0
    assert read("seconds", roots=["score.predict"]) == 0.75
    assert read("seconds", roots=["boot.import", "boot.init"]) == 2.5
    # from the import's first line to the end of the set-up train
    assert read("end_since", since="boot.import") == pytest.approx(30.0)
    # every descendant of the root, two and three levels down; not the
    # window's trains
    assert read("seconds", spans=["jit.trace", "jit.lower"]) == 3.0
    assert read("seconds", spans=["jit.load"]) == 0.5
    assert read("seconds", spans=["jit.build"]) == 8.25
    assert read("count", spans=["jit.load", "jit.build"]) == 7 + 2 + 1
    assert read("seconds", spans=["no.such.span"]) == 0
    # what the program named, a label summed over the stages, largest first
    assert r.notes["train.gbm jit.build"] == [["jit(chunk)", 7.0],
                                              ["jit(sketch)", 1.25]]
    assert r.notes["train.gbm jit.trace"] == [["chunk", 0.8],
                                              ["digitize", 0.2]]
    assert len(r.notes) == 4 and all(
        len(v) <= first_root.NOTED for v in r.notes.values())


def test_first_root_reads_nothing_where_the_first_is_lost_or_missing(ring):
    r = reading()
    # a program from before the spans
    assert first_root.read(r, ["no.such.root"], "seconds") is None
    assert first_root.read(r, ["boot.import", "boot.no"], "seconds") is None
    assert first_root.read(r, ["train.*"], "end_since",
                           since="no.such.span") is None
    # a child is no root
    assert first_root.read(r, ["train.bin.sketch"], "seconds") is None
    # a ring that dropped its oldest spans has lost the set-up
    ring.registry().counter("h2o3_spans_dropped_total").inc()
    assert first_root.read(r, ["train.*"], "seconds") is None
    assert first_root.read(r, ["boot.import"], "seconds") is None


def test_first_root_does_not_pass_over_a_first_root_that_raised(telemetry):
    train(telemetry, T0, 5.0, error=True)
    train(telemetry, T0 + 5.0, 3.0)
    assert first_root.read(reading(), ["train.*"], "seconds") is None


@pytest.mark.parametrize("bad", [
    dict(what="mean"), dict(what="end_since"), dict(what="count"),
    dict(what="seconds", since="boot.import")])
def test_first_root_refuses_arguments_that_do_not_go_together(bad):
    with pytest.raises(ValueError):
        first_root.read(reading(), ["train.*"], **bad)


def test_every_new_metric_loads_for_the_cells_it_lists_and_no_other():
    bench = loader.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for cell in CELLS:
        loaded = {m["name"]: m for m in loader.load_cell(bench, cell)[
            "metrics"]}
        assert NEW[cell] <= set(loaded), cell
        others = set().union(*NEW.values()) - NEW[cell]
        assert not others & set(loaded), cell
    for name in set().union(*NEW.values()):
        m = declared[name]
        assert (m["moves"], m["better"]) == (
            "train_s" if name == "init_s" else "setup_s", "lower")
        assert m["unit"] == ("count" if name == "first_train_programs"
                             else "s")
        assert m["workloads"] == (
            TRAIN_CELLS if name == "init_s" else
            ["h2o_defaults.score"] if name == "first_predict_s" else CELLS)
        spec = loader.read_json("metrics", name + ".json")
        assert spec["reader"] == ("train_profile_mean" if name == "init_s"
                                  else "first_root")


def test_a_metric_is_refused_for_a_cell_that_does_not_report_what_it_moves():
    bench = loader.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == "init_s")
    entry["workloads"] = entry["workloads"] + ["h2o_defaults.score"]
    with pytest.raises(loader.BenchmarkError, match="init_s lists cell"):
        loader.load_cell(bench, "h2o_defaults.train")


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_carries_the_first_models_metrics(cell):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    if cell.endswith("_4chip"):
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = subprocess.run(
        [sys.executable, os.path.join(loader.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 37), "--seconds", "1",
         "--trace", "1", "--rehearse"], cwd=loader.REPO_DIR, env=env,
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    metrics = line["metrics"]
    assert NEW[cell] <= set(metrics), sorted(metrics)
    value = {n: metrics[n]["value"] for n in NEW[cell]}
    for name in NEW[cell] - {"first_train_build_s"}:
        assert value[name] > 0, name
    assert value["first_train_build_s"] >= 0
    # one run's numbers hold together
    assert value["boot_s"] + value["first_train_s"] <= value["first_model_s"]
    assert (value["first_train_trace_lower_s"] + value["first_train_load_s"]
            + value["first_train_build_s"]) <= value["first_train_s"]
    # the programs by name, the chunk among them
    lowered = [k for k in line["run"]["notes"] if k.endswith(" jit.lower")]
    assert lowered, line["run"]["notes"]
    assert any("chunk" in name for name, _ in line["run"]["notes"][lowered[0]])
