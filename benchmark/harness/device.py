"""The chip a run is on: found or the run fails, named in every result."""
from __future__ import annotations


class NoChip(Exception):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


def require(chips: int, rehearse: bool) -> dict:
    """The ``device`` key of the result line, as JAX reports it."""
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu" and not rehearse:
        raise NoChip(f"jax.devices()[0].platform is {platform!r}, not 'tpu'")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": platform, "kind": devs[0].device_kind, "count": chips}


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips used (None where the
    backend reports none, as the CPU does)."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def setup_compile_cache() -> str:
    """The program's own rule for the cache directory
    (``JAX_COMPILATION_CACHE_DIR`` else ``<checkout>/.jax_cache``), with
    JAX's one-second floor on what is worth caching taken away: every run
    is a new process, and what is not cached compiles in each of them."""
    import jax
    from h2o3_tpu.cluster_boot import setup_compilation_cache
    path = setup_compilation_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def counter_total(name: str) -> float:
    """Sum of one telemetry counter of the program over its label sets."""
    from h2o3_tpu import telemetry
    return sum(s["value"] for s in telemetry.registry().samples()
               if s["name"] == name and "value" in s)
