"""Kubernetes pod entrypoint for multi-host clusters.

Reference: h2o-k8s/ (assisted clustering: H2OKubernetesEmbeddedConfig
resolves peers from a headless-service DNS lookup and waits for cloud
stabilization) + h2o-helm/. TPU re-design: no peer discovery protocol —
``jax.distributed.initialize`` IS cloud formation, and the coordinator
address is a deterministic StatefulSet DNS name (pod ordinal 0), so the
"lookup + stabilize" machinery collapses into env-var resolution. Every
pod runs this module; process 0 additionally serves REST (the node
answering the web port, water/H2O.java boot).

Env contract (set by h2o-k8s/manifests or the h2o-helm chart):
  H2O3_COORDINATOR_ADDRESS  host:port of pod 0 (headless-service DNS)
  H2O3_NUM_PROCESSES        replica count
  H2O3_PROCESS_ID           this pod's ordinal; derived from the
                            StatefulSet hostname suffix when unset
  H2O3_REST_PORT            REST port on the coordinator (default 54321)
  H2O3_MESH_MODEL           'model' mesh axis size (default 1)
  JAX_COMPILATION_CACHE_DIR JAX's own variable: where the persistent XLA
                            compilation cache lives. Unset, the cache is
                            <checkout>/.jax_cache. Mount a PVC there so a
                            pod restart's time-to-first-model skips the
                            cold train-step compile.
  H2O3_RECOVERY_DIR         durable restart-recovery root (mount a PVC).
                            When set, boot scans it for trains the
                            PREVIOUS process left interrupted (crash /
                            kill -9 / pod eviction), re-registers them
                            as RECOVERING jobs and resumes them from
                            their in-training checkpoints under the new
                            process's mesh — plus age-based GC of
                            orphaned checkpoint artifacts. Unset =
                            checked no-op (h2o3_tpu/recovery.py).

Run: ``python -m h2o3_tpu.cluster_boot``
"""
from __future__ import annotations

import os
import re
from dataclasses import dataclass
from typing import Mapping, Optional


# the one default cache location: inside the checkout (git-ignored), so
# a copy of the tree at the same path finds what an earlier run compiled
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")


def setup_compilation_cache() -> str:
    """Wire JAX's persistent compilation cache so the cold train-step
    spec/compile amortises across process restarts (the reference JVM
    has no compile step; this cost is TPU-stack-specific and so is the
    fix). Returns the cache dir in use.

    The directory is placed from OUTSIDE: with ``JAX_COMPILATION_CACHE_DIR``
    in the environment JAX has already read it into
    ``jax.config.jax_compilation_cache_dir`` and nothing is set here; a
    directory some caller configured before (the test conftest's
    per-worker cache) is kept as well. Otherwise the cache is the fixed
    ``<checkout>/.jax_cache`` — never a temp name, pid or time, because
    the path has to be the same for the next process to hit.

    Safe to call before OR after the first jax use in the process —
    compiles after the call hit the cache."""
    # boot is the earliest common chokepoint every entrypoint passes
    # through (k8s pod, bench, tools, chip_smoke) — install the telemetry
    # listeners here so the production compile counter sees the FIRST
    # compile
    from h2o3_tpu import telemetry
    telemetry.install()
    import jax
    if jax.config.jax_compilation_cache_dir:
        return jax.config.jax_compilation_cache_dir
    os.makedirs(DEFAULT_COMPILE_CACHE_DIR, exist_ok=True)
    # JAX's own 1-second floor on what is worth caching stays: it keeps
    # the trivial eager-op executables out of the directory
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


@dataclass
class BootConfig:
    coordinator_address: str
    num_processes: int
    process_id: int
    rest_port: int
    n_model: int


def resolve_boot_config(env: Optional[Mapping[str, str]] = None,
                        hostname: Optional[str] = None) -> BootConfig:
    """Pure env → config resolution (unit-testable without a cluster).

    The pod ordinal falls back to the trailing ``-<n>`` of the
    StatefulSet hostname (``h2o3-2`` → 2) the way the reference's
    assisted clustering derives identity from pod metadata."""
    env = dict(env if env is not None else os.environ)
    addr = env.get("H2O3_COORDINATOR_ADDRESS")
    if not addr:
        raise ValueError("H2O3_COORDINATOR_ADDRESS is required "
                         "(pod-0 headless-service DNS, host:port)")
    n = int(env.get("H2O3_NUM_PROCESSES", "1"))
    pid_s = env.get("H2O3_PROCESS_ID")
    if pid_s is None or pid_s == "":
        host = hostname if hostname is not None else os.uname().nodename
        m = re.search(r"-(\d+)$", host)
        if not m:
            raise ValueError(
                f"H2O3_PROCESS_ID unset and hostname '{host}' has no "
                f"StatefulSet ordinal suffix")
        pid = int(m.group(1))
    else:
        pid = int(pid_s)
    if not (0 <= pid < n):
        raise ValueError(f"process_id {pid} outside [0, {n})")
    return BootConfig(
        coordinator_address=addr, num_processes=n, process_id=pid,
        rest_port=int(env.get("H2O3_REST_PORT", "54321")),
        n_model=int(env.get("H2O3_MESH_MODEL", "1")))


def run_boot_recovery(wait: bool = False) -> Optional[dict]:
    """Boot-time restart recovery (h2o3_tpu/recovery.py): rediscover
    trains a killed predecessor process left interrupted and resume
    them from their in-training checkpoints. Checked no-op when
    ``H2O3_RECOVERY_DIR`` is unset — the recovery module is not even
    imported. NEVER raises: a broken recovery dir must not wedge
    process startup (the scan itself already isolates per-manifest
    failures; this guard covers the rest)."""
    if not (os.environ.get("H2O3_RECOVERY_DIR") or "").strip():
        return None
    try:
        from h2o3_tpu import recovery
        return recovery.recover_at_boot(wait=wait)
    except Exception as e:   # noqa: BLE001 — boot must proceed
        from h2o3_tpu.log import warn
        warn("boot recovery failed (%s) — continuing boot without it", e)
        return None


def main() -> None:
    import h2o3_tpu as h2o
    setup_compilation_cache()
    cfg = resolve_boot_config()
    h2o.init(distributed=True,
             coordinator_address=cfg.coordinator_address,
             num_processes=cfg.num_processes,
             process_id=cfg.process_id,
             n_model=cfg.n_model,
             port=cfg.rest_port)
    import jax
    if cfg.process_id == 0:
        # the coordinator drives training, so it owns recovery; resumes
        # run in the background — the REST/readiness port must come up
        # immediately, recovered models appear on /3/Models as they land
        run_boot_recovery(wait=False)
    if cfg.process_id != 0:
        # workers answer the web port too — but only with a minimal
        # health responder so the /3/Cloud readiness probe passes on
        # every pod (the reference's every-node-answers-the-web-port
        # behavior; full REST stays coordinator-only by design)
        _serve_worker_health(cfg)
    print(f"h2o3_tpu pod {cfg.process_id}/{cfg.num_processes} up: "
          f"{len(jax.devices())} global devices"
          + (f", REST :{cfg.rest_port}" if cfg.process_id == 0 else ""),
          flush=True)
    # workers park forever; the coordinator's REST server owns the
    # process lifetime (SIGTERM from k8s ends the pod)
    import threading
    threading.Event().wait()


def _serve_worker_health(cfg: BootConfig) -> None:
    import json
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _Health(BaseHTTPRequestHandler):
        def do_GET(self):  # noqa: N802 - http.server contract
            body = json.dumps({
                "role": "worker", "process_id": cfg.process_id,
                "coordinator": cfg.coordinator_address}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    httpd = ThreadingHTTPServer(("", cfg.rest_port), _Health)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()


if __name__ == "__main__":
    main()
