"""Device-memory admission estimates for the training scheduler.

Reference: water/MemoryManager.java's allocation gate blocks a request
until heap is available; H2O's FJ ladder then keeps the node from
accepting more concurrent work than it can hold. Here the gate moves
BEFORE dispatch: a train's device footprint is estimated from what the
platform already knows and the scheduler only releases the entry when
the memman budget holds it — an oversubscribed submission WAITS in the
queue with a reason instead of allocating, OOMing, or silently
degrading a peer.

Estimate provenance (recorded on the entry and on /3/Scheduler):

- ``costmodel+shape`` — a cached executable exists for the algo's chunk
  seam (telemetry/costmodel.py): its per-iteration HBM bytes-accessed
  bound the resident working set from above. The hint is clamped to
  [1x, 4x] of the shape estimate so a stale cache entry from a much
  larger train cannot starve admission (the idle-admit rule below keeps
  even a wild over-estimate live-locked-free).
- ``shape`` — conservative fallback: the dense design matrix at the
  spec's padded row count times a per-algo working-set factor (margins,
  histograms, optimizer state), plus the y/w/margin vectors; all of it
  row-sharded, so the estimate is ONE device's share of it
  (``memman.per_shard``), against one device's admission budget.
- ``stream-window`` — the frame will not fit dense (the same
  ``fits_device`` test build_training_spec applies), so the train takes
  the host-chunked streaming path and admits at its budget-sized
  resident WINDOW, not the full matrix.

Double-count honesty: the estimate includes the training frame's own
resident bytes, and two entries over the same frame each count it —
conservative by design (shared-frame accounting would need per-Vec
refcounts across preemption). The scheduler's idle-admit rule (an entry
always admits when nothing else runs) guarantees progress regardless of
over-estimation.
"""
from __future__ import annotations

import os
from typing import NamedTuple, Optional

# rough working-set multipliers over the dense [rows, F] f32 design:
# trees hold X + per-row margin/residual + level histograms (small);
# GLM expands categoricals and keeps gram/optimizer state; DL keeps
# activations per layer. Deliberately coarse — the costmodel hint
# refines them once a real executable has been lowered.
ALGO_WORKING_FACTOR = {
    "gbm": 1.7, "xgboost": 1.7, "drf": 1.7, "isolationforest": 1.7,
    "glm": 2.5, "gam": 2.5, "deeplearning": 3.0, "kmeans": 2.0,
    "pca": 2.5,
}
DEFAULT_WORKING_FACTOR = 2.0

# the streamed paths size their resident window off the budget and
# double-buffer overflow chunks; admit at this budget fraction plus the
# always-resident y/w/margin vectors
STREAM_WINDOW_FRACTION = float(
    os.environ.get("H2O3_SCHED_STREAM_FRACTION", "0.5") or 0.5)

# algo -> the costmodel executable-cache key prefix of its chunk seam
_COSTMODEL_PREFIX = {"gbm": "gbm.chunk", "xgboost": "gbm.chunk",
                     "drf": "drf.chunk"}


class Estimate(NamedTuple):
    bytes: int
    streamed: bool
    source: str


def admission_headroom(reserved_bytes: int) -> int:
    """Admission budget minus the scheduler's reserved ledger; -1 means
    an unlimited backend. This single number is what heartbeats gossip
    into the fleet member table — a remote placement decision admits
    against it exactly as the local gate would."""
    from h2o3_tpu import memman
    mm = memman.manager()
    if mm.unlimited:
        return -1
    return max(mm.admission_budget() - int(reserved_bytes), 0)


def _response_classes(frame, y: Optional[str]) -> int:
    try:
        from h2o3_tpu.frame.vec import T_ENUM
        if y and y in frame and frame.vec(y).type == T_ENUM:
            return max(int(frame.vec(y).cardinality), 1)
    except Exception:   # noqa: BLE001 — estimation must never fail a train
        pass
    return 1


def estimate_submission(builder, frame, y=None, x=None,
                        validation_frame=None) -> Estimate:
    """Device-footprint estimate for one ModelBuilder submission,
    computed from frame shape + params only (the spec — and its device
    allocations — do not exist yet; admission is the point)."""
    from h2o3_tpu import memman
    from h2o3_tpu.frame.vec import T_STR

    try:
        names = list(x) if x else [n for n in frame.names if n != y]
        ignored = set(builder.params.get("ignored_columns") or ())
        for aux in ("weights_column", "offset_column", "fold_column"):
            c = builder.params.get(aux)
            if c:
                ignored.add(c)
        names = [n for n in names
                 if n not in ignored and frame.vec(n).type != T_STR]
        F = max(len(names), 1)
        nrow = int(frame.nrow)
    except Exception:   # noqa: BLE001 — degenerate frame: admit small
        F, nrow = 1, 0
    padded = nrow + 256          # mirrors build_training_spec's estimate
    K = _response_classes(frame, y)
    x_bytes = padded * F * 4
    # y/w + a margin per class (trees/GLM keep one; DL activations ride
    # the working factor instead)
    aux_bytes = padded * 4 * (2 + K)
    valid_bytes = 0
    if validation_frame is not None:
        try:
            valid_bytes = (int(validation_frame.nrow) + 256) * F * 4
        except Exception:   # noqa: BLE001
            pass

    mm = memman.manager()
    # the streamed/dense PREDICTION must mirror build_training_spec's
    # gate exactly — TRAINING bytes only. Folding validation bytes in
    # here once mis-classified dense trains as streamed, reserving the
    # small window while the real footprint ran dense and letting a
    # second train admit into memory that was already spoken for.
    if not mm.fits_device(x_bytes + mm.stats()["device_resident_bytes"]):
        # streamed-mode admission: the design stays on host and only the
        # resident window + working vectors occupy HBM
        win = int(mm.budget * STREAM_WINDOW_FRACTION) + mm.per_shard(
            aux_bytes)
        return Estimate(win, True, "stream-window")

    factor = ALGO_WORKING_FACTOR.get(
        getattr(builder, "algo", ""), DEFAULT_WORKING_FACTOR)
    # validation matrix is resident but carries no histogram/optimizer
    # working set — added outside the factor
    # one device's share (memman.per_shard): the design, the validation
    # matrix and the vectors are all row-sharded over the data axis, and
    # the admission budget they are held against is one device's
    base = mm.per_shard(int(x_bytes * factor) + valid_bytes + aux_bytes)
    prefix = _COSTMODEL_PREFIX.get(getattr(builder, "algo", ""))
    if prefix:
        from h2o3_tpu.telemetry import costmodel
        hint = costmodel.per_iteration_bytes_hint(prefix)
        if hint:
            # the hint is bytes accessed per TREE; a tree pass streams
            # the design once per LEVEL, so dividing by depth
            # approximates the resident working set rather than the
            # traffic. Clamped to [1x, 4x] shape so a cached cost from
            # a much larger train cannot dominate admission.
            depth = max(int(builder.params.get("max_depth", 6) or 6), 1)
            working = hint / depth
            return Estimate(int(min(max(working, base), 4.0 * base)),
                            False, "costmodel+shape")
    return Estimate(base, False, "shape")
