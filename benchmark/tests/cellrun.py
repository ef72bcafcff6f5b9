"""Drives the rest of a run without the harness's look for a chip: set-up,
one step, the product, the release and the decision, at the rehearsal size,
against the cell's own limits."""
import gc

import run as bench_run
from harness import loader

SEED = 6      # a seed on which the sound run holds every limit at 4,096 rows


def cell_of(name: str) -> dict:
    return loader.load_cell(loader.load_benchmark(), name)


def one_step(name: str, seed: int = SEED):
    """(cell, product, step_ok) of one step of the cell's runner."""
    cell = cell_of(name)
    runner = loader.plugin("runners", cell["traffic"]["runner"])
    state = runner.setup(cell, seed, True)
    ok = runner.step(state)
    product = runner.product(state)
    runner.release(state)
    del state
    gc.collect()
    return cell, product, ok


def decide(cell, product, ok: bool, seed: int = SEED):
    return bench_run.decide(cell, product, seed, failed=0 if ok else 1,
                            rehearse=False)
