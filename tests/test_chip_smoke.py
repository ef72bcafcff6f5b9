"""chip_smoke.py off the chip: it must refuse to pass, and its rehearsal
must walk every phase. Run as a subprocess, the way the driver runs it."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PHASES = ["device", "ingest", "train", "reference", "predict", "serve"]


def _run(tmp_path, *args):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "H2O3_MAX_BUILD_THREADS")}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py"),
                        *args], env=env, capture_output=True, text=True,
                       timeout=600)
    lines = [json.loads(ln) for ln in r.stdout.splitlines() if ln.strip()]
    return r, lines


def test_without_a_chip_it_fails_at_the_device_check(tmp_path):
    r, lines = _run(tmp_path)
    assert r.returncode != 0, r.stderr[-2000:]
    assert lines[-1] == {"ok": False, "device": {
        "platform": "cpu", "kind": "cpu", "count": 1}}
    assert [ln["phase"] for ln in lines[:-1]] == ["device"]


def test_rehearsal_walks_every_phase_and_still_says_not_ok(tmp_path):
    r, lines = _run(tmp_path, "--rehearse", "--rows", "4096",
                    "--csv-rows", "2048")
    assert r.returncode == 0, r.stderr[-4000:]
    assert [ln.get("phase") for ln in lines[:-1]] == PHASES
    assert all(ln["ok"] for ln in lines[:-1])
    assert lines[-1]["ok"] is False
    assert lines[-1]["device"]["platform"] == "cpu"
    # the cache went where the environment placed it
    assert lines[0]["compile_cache"] == str(tmp_path / "xla")
    train = lines[2]
    assert train["warm"]["compiles"] == 0
    assert train["packed_codes"]["enabled"] is True
