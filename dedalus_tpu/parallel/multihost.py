"""
Multi-host (multi-process) execution support
(reference: the MPI world — mpi4py COMM_WORLD throughout,
dedalus/core/distributor.py:109-113; here one jax.distributed world whose
global device set backs the solver's Mesh, with collectives riding
ICI/DCN and process-0-guarded host IO).

Launch recipe (one process per host, e.g. a v4-32's 4 hosts):

    import dedalus_tpu.parallel.multihost as mh
    mh.initialize()                      # env-driven on TPU pods
    mesh = mh.device_mesh()              # spans ALL processes' devices
    dist = d3.Distributor(coords, mesh=mesh)
    ...
    distribute_solver(solver)            # shards over the global mesh

On TPU pods `jax.distributed.initialize()` reads the cluster environment
automatically. For CPU rehearsal (tests) pass coordinator/process counts
explicitly.
"""

import numpy as np
import jax

__all__ = ["initialize", "device_mesh", "is_primary", "barrier",
           "process_allgather"]

_initialized = False


# NOTE: TPU_WORKER_HOSTNAMES is deliberately absent — single-host TPU
# machines set it for libtpu init without implying a multi-host world.
_CLUSTER_ENV_HINTS = ("JAX_COORDINATOR_ADDRESS", "COORDINATOR_ADDRESS",
                      "MEGASCALE_COORDINATOR_ADDRESS",
                      "SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE")


def _cluster_expected(coordinator_address, num_processes):
    import os
    if coordinator_address is not None or num_processes not in (None, 1):
        return True
    return any(os.environ.get(k) for k in _CLUSTER_ENV_HINTS)


def initialize(coordinator_address=None, num_processes=None, process_id=None,
               **kw):
    """Join (or start) the jax.distributed world. Idempotent. A failure is
    swallowed ONLY when nothing suggested a cluster (no arguments, no
    cluster environment) — silently degrading a real pod launch to
    standalone would let every host think it is process 0 and diverge."""
    global _initialized
    if _initialized:
        return
    client = getattr(jax.distributed, "global_state", None)
    if client is not None and getattr(client, "client", None) is not None:
        # user code already called jax.distributed.initialize() directly
        _initialized = True
        return
    # CPU rehearsal worlds (the 2-process tests, laptop dry runs): the
    # default XLA:CPU client has no cross-process collectives ("Multiprocess
    # computations aren't implemented on the CPU backend"); jaxlib's gloo
    # implementation provides them. Must be set before the backend spins
    # up — initialize() is that point; harmless for TPU/GPU worlds (the
    # flag only affects CPU client construction) and best-effort across
    # jax versions that lack the option.
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:
        pass
    try:
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id, **kw)
        _initialized = True
    except Exception:
        if _cluster_expected(coordinator_address, num_processes):
            raise
        # single-process, no cluster env: run standalone


def device_mesh(shape=None, axis_names=None):
    """A Mesh over the GLOBAL device set (all processes). `shape` defaults
    to one flat axis; multi-axis shapes reshape the device list in
    process-major order so intra-host links carry the fastest axis."""
    devices = np.array(jax.devices())
    if shape is None:
        shape = (devices.size,)
    axis_names = tuple(axis_names or
                       ("x", "y", "z", "w")[:len(shape)])
    from jax.sharding import Mesh
    return Mesh(devices.reshape(shape), axis_names)


def is_primary():
    """Whether this process should perform shared-filesystem output
    (reference: rank-0 guarded IO, dedalus/tools/parallel.py:10 Sync)."""
    return jax.process_index() == 0


def barrier(name="dedalus_tpu_barrier"):
    """Cross-process synchronization point (e.g. before process-0 mkdir)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)


def process_allgather(x):
    """Gather a (possibly sharded) array to a full local copy on every
    process (reference: allgather_data, core/field.py:731)."""
    if jax.process_count() == 1:
        return np.asarray(x)
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.process_allgather(x, tiled=True))


def broadcast_from_primary(values):
    """Broadcast a flat numeric array from process 0 to all processes
    (reference: rank-0 state scattered through COMM_WORLD; used for
    append-mode output bookkeeping so only the primary scans the shared
    filesystem)."""
    values = np.asarray(values)
    if jax.process_count() == 1:
        return values
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.broadcast_one_to_all(values))
