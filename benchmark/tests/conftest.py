"""CPU checks of the harness, run by hand: ``python -m pytest benchmark/tests -q``
(not part of the repository's tier-1 suite, which collects ``tests/`` only)."""
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
for p in (REPO_DIR, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the kernels interpreted at a tile the test size fills, as --rehearse sets
os.environ.setdefault("H2O3_PALLAS_INTERPRET", "1")
os.environ.setdefault("H2O3_HIST_TILE", "512")
