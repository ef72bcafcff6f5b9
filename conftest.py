"""Root conftest: force an 8-device virtual CPU mesh for the test suite,
and gate the heavy tier behind ``-m`` markers so the default run stays
under the 5-minute bar (VERDICT r4 task 8): tests marked ``slow``
(multi-minute AutoML/sharded-parity/client-explain runs) are skipped
unless ``--runslow`` (or ``-m slow``) is given — the driver's full pass
runs them separately.

Mirrors the reference's "fake multi-node" strategy (4 JVMs on loopback,
see SURVEY.md §4.1 / multiNodeUtils.sh) with JAX's
--xla_force_host_platform_device_count: the tests hold JAX to the CPU so
sharding semantics are exercised on 8 virtual devices.

Set H2O3_TPU_TEST_PLATFORM=tpu to run the suite on the real chip instead.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

if os.environ.get("H2O3_TPU_TEST_PLATFORM", "cpu") == "cpu":
    # the 8-participant collective rendezvous can stall >40s on a
    # loaded 1-core host (all participants share one thread pool);
    # XLA's default 40s terminate timeout then abort()s the whole
    # process ("only 7 of them arrived on time") — observed
    # intermittently on the wide sharded tests. The stall resolves;
    # give it room instead of dying (jaxlib 0.9.0 knows both flags).
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120"
        " --xla_cpu_collective_call_terminate_timeout_seconds=900")
    import jax

    jax.config.update("jax_platforms", "cpu")
    # persistent compilation cache: the suite's wall time is dominated by
    # re-compiling the same sharded train steps (a cold full run spends
    # ~80% of its time in XLA); cached executables make repeat runs and
    # re-runs of single files start warm (water/MRTask has no compile
    # step to cache — this cost is TPU-stack-specific, so the fix is too)
    cache_dir = os.environ.get("H2O3_TEST_JAX_CACHE",
                               "/tmp/h2o3_jax_cache")
    # key the cache by host-CPU fingerprint: XLA:CPU AOT results encode
    # machine features (prefer-no-scatter etc.), and loading an entry
    # compiled on a different host warns "could lead to SIGILL" — which
    # manifested as intermittent worker abort()s when this repo's cache
    # outlived a driver-host change
    try:
        import hashlib
        with open("/proc/cpuinfo") as _f:
            flags = next((ln for ln in _f if ln.startswith("flags")), "")
        cache_dir += "_" + hashlib.sha1(flags.encode()).hexdigest()[:8]
    except OSError:
        pass
    # per-xdist-worker cache dir: concurrent processes racing on the
    # same cache files have produced aborted workers ("node down")
    worker = os.environ.get("PYTEST_XDIST_WORKER")
    if worker:
        cache_dir = f"{cache_dir}_{worker}"
    # single-writer lock: two concurrent pytest INVOCATIONS sharing the
    # dir have produced torn cache entries that abort() every later run
    # at deserialize time (observed as SIGABRT inside a jnp.where
    # compile, reproducible until the dir was wiped). The second
    # concurrent run gets a private cold dir instead.
    try:
        import atexit
        import fcntl
        os.makedirs(cache_dir, exist_ok=True)
        _cache_lock_fd = open(os.path.join(cache_dir, ".writer_lock"),
                              "w")
        try:
            fcntl.flock(_cache_lock_fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
        except OSError:
            import shutil
            cache_dir = f"{cache_dir}_p{os.getpid()}"
            atexit.register(shutil.rmtree, cache_dir,
                            ignore_errors=True)
    except OSError:
        pass
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    # concurrent XLA dispatch from CV/grid build threads can abort() the
    # oversubscribed CPU backend under xdist ("gw node down"); pin build
    # pools to one thread for the suite — the dedicated concurrency
    # tests (tests/test_parallel_build.py) raise the cap back.
    os.environ.setdefault("H2O3_MAX_BUILD_THREADS", "1")


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="run tests marked slow (the heavy tier)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-minute tests (AutoML plans, sharded "
        "parity, client explain) — skipped unless --runslow")


def pytest_collection_modifyitems(config, items):
    import pytest as _pytest
    if config.getoption("--runslow") or \
            "slow" in (config.getoption("markexpr", "") or ""):
        return
    # an explicitly named test (node id with '::') means the developer
    # asked for exactly that test — don't skip-trap them into a
    # misleading '1 skipped'
    if any("::" in a for a in config.args):
        return
    skip = _pytest.mark.skip(reason="slow tier: pass --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)
