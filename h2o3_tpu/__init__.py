"""h2o3_tpu — a TPU-native distributed ML platform with H2O-3's capabilities.

The reference (usefulalgorithm/h2o-3) is a JVM cluster holding a distributed
K/V store of columnar frame chunks, computed over with MRTask map/reduce
(see /root/repo/SURVEY.md). This package is the TPU-first re-design:

- the JVM cloud / Paxos / RPC / DKV collapse into single-controller JAX over a
  ``jax.sharding.Mesh`` (axes ``('data', 'model')``);
- Frame/Vec/Chunk become columnar containers over row-sharded ``jax.Array``s;
- MRTask's binary-tree map/reduce becomes ``shard_map`` + XLA collectives
  (``psum``/``all_gather``/``reduce_scatter``) over ICI;
- the native XGBoost ``gpu_hist`` path becomes a JAX/pallas histogram tree
  builder whose per-node grad/hess histograms all-reduce over ICI: on a mesh
  with more than one data shard every chip bins and builds the histograms of
  its own rows, a level is one ``psum`` of the built half, and a table's
  uniform and identity bin edges come from per-shard extremes reduced over
  the ``data`` axis, so no row-sized array leaves its chip (quantile edges on
  an accelerator mesh still go through a host copy: ops/binning.py);
- device memory is budgeted per device: a row-sharded array is held against
  one chip's limit by its per-shard bytes (memman.per_shard).

Public surface mirrors the h2o python client (reference h2o-py/h2o/h2o.py).
"""
import time as _time

_T_IMPORT = (_time.time(), _time.perf_counter())    # span boot.import, below

from h2o3_tpu.frame.frame import Frame
from h2o3_tpu.frame.vec import Vec
from h2o3_tpu.ingest.parse import import_file, parse_setup, upload_numpy
from h2o3_tpu.parallel.mesh import current_mesh, set_mesh, make_mesh
from h2o3_tpu.mojo import import_mojo
from h2o3_tpu.mojo import export_mojo as download_mojo
from h2o3_tpu.persist import export_file, load_model, save_model

__version__ = "0.2.0"

__all__ = [
    "Frame",
    "Vec",
    "import_file",
    "parse_setup",
    "upload_numpy",
    "current_mesh",
    "set_mesh",
    "make_mesh",
    "init",
    "save_model",
    "load_model",
    "export_file",
    "download_mojo",
    "import_mojo",
]


def init(n_data=None, n_model=1, distributed=False,
         coordinator_address=None, num_processes=None, process_id=None,
         port=None):
    """Initialise the runtime: build the global device mesh.

    Replaces the reference's cluster boot (water/H2O.java:2328 main →
    Paxos cloud formation): there is no membership protocol — the mesh is
    the cloud. Multi-chip SPMD is the default whenever more than one
    device is visible (``H2O3_SPMD=0`` collapses the default mesh to a
    single device — the escape hatch).

    ``distributed=True`` is the multi-host path (SURVEY §7.3): every host
    runs the SAME program, ``jax.distributed.initialize`` forms the
    process group (the cloud-formation step), the mesh spans all hosts'
    devices, and the REST server belongs on process 0 only
    (``is_coordinator()``). Worker loss is fatal — the reference's own
    locked-cloud failure model (water/Paxos.java:145), recovery is
    restart + checkpoint reload.
    """
    from h2o3_tpu.telemetry.spans import span
    with span("boot.init"):     # root: one a process
        if distributed:
            import jax
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes, process_id=process_id)
        mesh = make_mesh(n_data=n_data, n_model=n_model)
        set_mesh(mesh)
    if distributed and port and is_coordinator():
        from h2o3_tpu.api import start_server
        start_server(port=port)
    return current_mesh()


def is_coordinator() -> bool:
    """True on the REST-serving process (host 0) — the reference's
    'node answering the web port' role (water/H2O.java boot)."""
    import jax
    return jax.process_index() == 0


def _record_import_span() -> None:
    """Root span ``boot.import``: this package's own import, first line
    to last, written when it is over (``record_span``)."""
    from h2o3_tpu.telemetry.spans import record_span
    record_span("boot.import", _T_IMPORT[0],
                _time.perf_counter() - _T_IMPORT[1])


_record_import_span()
