"""Sharding and multi-process helpers shared by the serving, launch and
test code, written against the installed JAX (0.9).

  * :func:`auto_axis_types` -- ``AxisType.Auto`` for every mesh axis
    (``jax.make_mesh`` otherwise makes explicit-sharding axes);
  * :func:`shard_map` -- ``jax.shard_map`` with the replication check
    off, the way every call site here uses it;
  * the ``jax.distributed`` helpers below.
"""

from __future__ import annotations

import os

import jax

__all__ = ["auto_axis_types", "shard_map",
           "distributed_initialize", "is_distributed",
           "make_global_mesh", "broadcast_one_to_all", "process_allgather",
           "replicate_global",
           "DIST_COORDINATOR_ENV", "DIST_NUM_PROCESSES_ENV",
           "DIST_PROCESS_ID_ENV"]


def auto_axis_types(n_axes: int) -> tuple:
    """``(AxisType.Auto,) * n_axes`` for ``jax.make_mesh``."""
    return (jax.sharding.AxisType.Auto,) * n_axes


def shard_map(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with ``check_vma=check`` (off by default)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


# ===================================================== multi-process ==
# The multi-process pieces a multi-host serving mesh needs: distributed
# start-up with CPU collectives, the global (host, model) mesh, and
# moving pytrees between processes.

DIST_COORDINATOR_ENV = "REPRO_DIST_COORDINATOR"
DIST_NUM_PROCESSES_ENV = "REPRO_DIST_NUM_PROCESSES"
DIST_PROCESS_ID_ENV = "REPRO_DIST_PROCESS_ID"

_dist_initialized = False


def distributed_initialize(coordinator_address: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> bool:
    """Idempotent ``jax.distributed.initialize`` with CPU collectives.

    Arguments default to ``$REPRO_DIST_COORDINATOR`` /
    ``$REPRO_DIST_NUM_PROCESSES`` / ``$REPRO_DIST_PROCESS_ID``, so a
    launcher wrapper can configure a whole fleet through the
    environment.  Returns True when a multi-process runtime is (now)
    active, False for the single-process case (``num_processes`` <= 1 or
    unset) — callers can branch on it without re-reading the env.

    MUST run before any jax computation: on the CPU backend the
    cross-process collective implementation (gloo) has to be selected
    before the backend initializes, or every collective fails with
    "Multiprocess computations aren't implemented on the CPU backend".
    """
    global _dist_initialized
    if coordinator_address is None:
        coordinator_address = os.environ.get(DIST_COORDINATOR_ENV)
    if num_processes is None:
        num_processes = int(os.environ.get(DIST_NUM_PROCESSES_ENV, "1"))
    if process_id is None:
        process_id = int(os.environ.get(DIST_PROCESS_ID_ENV, "0"))
    if num_processes <= 1 or coordinator_address is None:
        return _dist_initialized
    if _dist_initialized:
        return True
    # cross-process collectives on the CPU backend; others ignore it
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _dist_initialized = True
    return True


def is_distributed() -> bool:
    """True iff :func:`distributed_initialize` activated a fleet."""
    return _dist_initialized


def make_global_mesh(axis_names: tuple[str, str] = ("host", "model")
                     ) -> jax.sharding.Mesh:
    """Global (host, model) mesh over every process's devices.

    Rows are processes (devices sorted by ``(process_index, id)``), so
    the host axis is exactly the process grid and anything sharded over
    ``(host, model)`` lands contiguous shard blocks on each host — the
    layout the hierarchical top-k merge's offset math assumes.
    """
    import numpy as np
    devs = sorted(jax.devices(), key=lambda d: (d.process_index, d.id))
    n_proc = jax.process_count()
    grid = np.asarray(devs).reshape(n_proc, len(devs) // n_proc)
    return jax.sharding.Mesh(grid, axis_names)


def broadcast_one_to_all(x):
    """Process 0's pytree on every process (identity single-process)."""
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils
    return multihost_utils.broadcast_one_to_all(x)


def process_allgather(x):
    """Stack each process's pytree along a new leading axis (identity
    reshape single-process)."""
    if jax.process_count() == 1:
        import jax.numpy as jnp
        return jax.tree.map(lambda l: jnp.asarray(l)[None], x)
    from jax.experimental import multihost_utils
    return multihost_utils.process_allgather(x)


def replicate_global(tree, mesh) -> object:
    """Promote every LOCAL leaf of a pytree to a mesh-replicated global
    array, assuming each process already holds the same mirrored value
    (so no cross-process copy happens — each process just stamps its
    local copy onto its own devices).  Leaves that already span
    non-addressable devices pass through untouched; a multi-process jit
    can then take the tree as arguments next to (host, model)-sharded
    operands."""
    from jax.sharding import NamedSharding, PartitionSpec
    sharding = NamedSharding(mesh, PartitionSpec())

    def leaf(v):
        if isinstance(v, jax.Array) and not v.is_fully_addressable:
            return v
        import numpy as np
        v = np.asarray(v)
        return jax.make_array_from_process_local_data(sharding, v, v.shape)

    return jax.tree.map(leaf, tree)
