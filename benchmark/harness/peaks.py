"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.
A kind that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture: 197
TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip. (The same numbers as the
package's ``telemetry/costmodel.py`` table, copied so that no later PR can
move the yardstick.)
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add a row with its source to harness/peaks.py") from None


def least_seconds(phases: list[dict], peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for phases that follow one
    another: each the larger of operations over peak FLOP/s and bytes over
    peak bytes/s. Also says which bound holds for most of that time."""
    by = {"bandwidth": 0.0, "compute": 0.0}
    for p in phases:
        tb = p["bytes"] / peaks["bytes_per_s"]
        tf = p["flops"] / peaks["flops_per_s"]
        by["bandwidth" if tb >= tf else "compute"] += max(tb, tf)
    return by["bandwidth"] + by["compute"], max(by, key=by.get)
