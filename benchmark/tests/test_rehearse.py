"""``run.py --rehearse`` walks every cell end to end on the CPU and prints a
last line with the contract's keys; it can never say ``correct: true``. A
run that finds no TPU prints no result."""
import json
import os
import subprocess
import sys

import pytest

from harness import loader

RUN = os.path.join(loader.BENCH_DIR, "run.py")
CELLS = [w["name"] for w in loader.load_benchmark()["workloads"]]


def run(*args):
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, RUN, *args], cwd=loader.REPO_DIR,
                          env=env, capture_output=True, text=True,
                          timeout=600)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_line(cell, trace):
    p = run("--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
            "--trace", str(trace), "--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] >= 1
    assert line["device"]["platform"] == "cpu"
    bench = loader.load_benchmark()
    names = {m["name"] for m in bench["per_layer" if trace else "end_to_end"]}
    assert set(line["metrics"]) <= names and line["metrics"]
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    if not trace:
        assert "setup_s" in line["metrics"]
    for c in line["compared"].values():
        assert set(c) == {"value", "limit"}
    # each number compared beside its limit, as the last lines of stderr
    tail = p.stderr.strip().splitlines()[-(len(line["compared"]) + 1):]
    assert all(t.startswith("compared ") for t in tail[:-1])


def test_without_a_tpu_there_is_no_result():
    p = run("--workload", CELLS[0], "--seed", "1", "--seconds", "1",
            "--trace", "0")
    assert p.returncode == 3
    assert p.stdout.strip() == ""
