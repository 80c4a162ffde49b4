"""Production meshes.

Single pod: 16 x 16 = 256 chips, axes (data, model).
Multi-pod:  2 x 16 x 16 = 512 chips, axes (pod, data, model) — 'pod' is a
pure data-parallel axis with slow (DCI) links; the gradient compressor
(repro.optim.compression) targets exactly that axis.

A FUNCTION, not a module constant: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before any jax import).
"""

from __future__ import annotations

import jax

from repro.utils import compat


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(
        shape, axes, axis_types=compat.auto_axis_types(len(axes)))


def make_debug_mesh(shape=(2, 2), axes=("data", "model")) -> jax.sharding.Mesh:
    """Small mesh for CI tests (XLA_FLAGS host-device-count >= prod(shape))."""
    return jax.make_mesh(
        shape, axes, axis_types=compat.auto_axis_types(len(axes)))


def make_serving_mesh(host_axis: str = "host", model_axis: str = "model"
                      ) -> jax.sharding.Mesh:
    """The multi-host SERVING mesh: (host, model) over every process's
    devices — rows are processes, so vocab shards land host-contiguous
    (what the hierarchical top-k merge assumes).  Requires
    ``compat.distributed_initialize`` (or single-process, where the host
    axis is 1 and the hierarchical merge reduces to the flat one)."""
    return compat.make_global_mesh((host_axis, model_axis))


# v5e hardware constants for the roofline (per chip / per link)
PEAK_FLOPS_BF16 = 197e12        # FLOP/s
HBM_BW = 819e9                  # B/s
ICI_BW = 50e9                   # B/s per link (~per direction)
