"""Device-memory manager: budget, watermarks, LRU spill-to-host.

Reference: water/Cleaner.java:4 (the background sweeper that swaps
least-recently-used Values to disk when the heap crosses a watermark)
+ water/MemoryManager.java (allocation gate that blocks/frees until
memory is available) + the /3/Cloud free_mem report.

TPU re-design: HBM is the scarce tier and host RAM is the spill target
(the reference spills heap→disk; a v5e host has ~16x the chip's HBM, so
host RAM plays the disk role and disk would be the third tier).
Spillable device blocks (Frame Vec payloads) register here; an
allocation request over the HIGH watermark evicts least-recently-used
blocks to host numpy until under the LOW watermark. Algorithms consult
``fits_device(bytes)`` to pick dense vs streaming execution — frames
beyond the budget stream through training in host-chunked blocks
instead of failing allocation (SURVEY §7.1.7's Criteo-scale config).

The budget is ONE device's memory: the backend's own report, or
H2O3_DEVICE_BUDGET_BYTES. Everything held against it is row-sharded over
the mesh's ``data`` axis (a frame's Vec payloads, a design matrix), so
each device holds ``bytes / data shards`` of it: ``per_shard`` is the one
place that division is made, and the allocation gate, ``fits_device``
and the scheduler's admission estimates all read it. A table that fits
four chips and not one stays dense on a four-shard mesh. (The tests force
a tiny budget with ``reset(budget=...)`` to exercise eviction +
streaming; such a budget is held against WHOLE arrays unless the test
says ``per_shard=True``.)
"""
from __future__ import annotations

import os
import threading
import time
import weakref
from typing import Any, Callable, Dict, Optional

_LOCK = threading.RLock()
_SEQ = 0

HIGH_WATERMARK = 0.90      # evict when a request would cross this
LOW_WATERMARK = 0.70       # ...down to this (Cleaner's DESIRED analog)


def _default_budget() -> int:
    env = os.environ.get("H2O3_DEVICE_BUDGET_BYTES")
    if env:
        return int(env)
    import jax
    d = jax.devices()[0]
    stats = d.memory_stats()
    if stats and "bytes_limit" in stats:
        return int(stats["bytes_limit"])
    if d.platform == "tpu":
        # an unlimited budget on a 16 GB chip turns every admission and
        # dense-vs-streamed decision into a guess — fail where it shows
        raise RuntimeError(
            f"{d.device_kind}: memory_stats() reports no bytes_limit "
            f"({stats!r}); set H2O3_DEVICE_BUDGET_BYTES to the device's "
            "memory to run without it")
    return 1 << 62             # effectively unlimited (CPU backend)


class _Block:
    """One registered spillable device payload."""

    __slots__ = ("nbytes", "spill", "last_use", "seq", "__weakref__")

    def __init__(self, nbytes: int, spill: Callable[[], None]):
        self.nbytes = nbytes
        self.spill = spill
        self.last_use = time.monotonic()
        self.seq = 0


class MemoryManager:
    def __init__(self, budget: Optional[int] = None,
                 shards: Optional[int] = None):
        self.budget = budget if budget is not None else _default_budget()
        # the data shards a row-sharded array is split over when it is
        # held against one device's budget; None: the current mesh's
        # (read at each question, the mesh may be set after the manager)
        self.shards = shards
        # residency is the sum over LIVE blocks: the WeakSet drops
        # garbage-collected payloads automatically, so no counter to
        # keep consistent across gc/spill/free paths
        self._blocks: "weakref.WeakSet[_Block]" = weakref.WeakSet()
        self.spill_count = 0
        self.spilled_bytes = 0

    @property
    def _resident(self) -> int:
        return sum(b.nbytes for b in self._blocks)

    # -- registry ------------------------------------------------------

    def register(self, nbytes: int, spill: Callable[[], None]) -> _Block:
        """Track a device-resident payload; ``spill`` must move it to
        host and drop the device reference."""
        with _LOCK:
            b = _Block(int(nbytes), spill)
            self._blocks.add(b)
            return b

    def touch(self, block: _Block) -> None:
        block.last_use = time.monotonic()

    def released(self, block: _Block) -> None:
        """The payload left the device (spilled or freed)."""
        with _LOCK:
            self._blocks.discard(block)

    def per_shard(self, nbytes: int) -> int:
        """Bytes ONE device holds of ``nbytes`` of row-sharded arrays:
        the whole over the data shards (nothing to divide where the
        budget is unlimited)."""
        if self.unlimited:
            return int(nbytes)
        shards = self.shards
        if shards is None:
            from h2o3_tpu.parallel.mesh import n_data_shards
            shards = n_data_shards()
        return -(-int(nbytes) // max(int(shards), 1))

    # -- allocation gate (MemoryManager.java malloc-with-wait analog) --

    def request(self, nbytes: int) -> None:
        """Make room for an ``nbytes`` row-sharded device allocation:
        evict LRU spillable blocks while a device's projected share
        crosses the high watermark (down to the low one)."""
        with _LOCK:
            if self.fits_device(self._resident + nbytes):
                return
            target = max(self.budget * LOW_WATERMARK
                         - self.per_shard(nbytes), 0)
            for b in sorted(self._blocks, key=lambda b: b.last_use):
                if self.per_shard(self._resident) <= target:
                    break
                try:
                    b.spill()
                finally:
                    self.spill_count += 1
                    self.spilled_bytes += b.nbytes
                    self.released(b)

    def fits_device(self, nbytes: int) -> bool:
        """Whether row-sharded arrays of this size in all are within
        budget, a device's share (:meth:`per_shard`) against its own
        limit — algorithms switch to host-chunked streaming when not."""
        return self.per_shard(nbytes) <= self.budget * HIGH_WATERMARK

    @property
    def unlimited(self) -> bool:
        """True on backends that report no real device limit (CPU) —
        the training scheduler's admission gate is a no-op there."""
        return self.budget >= (1 << 61)

    def admission_budget(self) -> int:
        """Bytes the training scheduler (h2o3_tpu.sched) may promise to
        concurrently RUNNING trains: the same high-watermark ceiling the
        allocation gate evicts toward, so admitted work and LRU spill
        agree on what 'full' means."""
        return int(self.budget * HIGH_WATERMARK)

    # -- reporting (/3/Cloud free_mem) ---------------------------------

    def stats(self) -> Dict[str, Any]:
        with _LOCK:
            return {
                "device_budget_bytes": self.budget
                if self.budget < (1 << 61) else -1,
                "device_resident_bytes": self._resident,
                "registered_blocks": len(self._blocks),
                "spill_count": self.spill_count,
                "spilled_bytes": self.spilled_bytes,
                "high_watermark": HIGH_WATERMARK,
                "low_watermark": LOW_WATERMARK,
            }


_MANAGER: Optional[MemoryManager] = None


def manager() -> MemoryManager:
    global _MANAGER
    with _LOCK:
        if _MANAGER is None:
            _MANAGER = MemoryManager()
        return _MANAGER


def reset(budget: Optional[int] = None,
          per_shard: bool = False) -> MemoryManager:
    """Tests: reinstall with an explicit budget, held against whole
    arrays (one shard) unless ``per_shard`` asks for the mesh's rule."""
    global _MANAGER
    with _LOCK:
        _MANAGER = MemoryManager(
            budget, shards=None if budget is None or per_shard else 1)
        return _MANAGER
