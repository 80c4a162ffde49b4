"""Sharding helpers: apply constraints only when a mesh is active, so the
same model code runs in single-device tests and under the production mesh."""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


__all__ = ["maybe_shard", "named_sharding", "specs_to_shardings"]


def _active_mesh_axes() -> tuple[str, ...] | None:
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return None
    return tuple(mesh.axis_names)


def mesh_axis_size(name: str) -> int | None:
    """Size of a mesh axis at trace time, or None outside a mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or name not in mesh.axis_names:
        return None
    return mesh.shape[name]


def maybe_shard(x: jax.Array, spec: P) -> jax.Array:
    """with_sharding_constraint if a mesh with the spec's axes is active;
    identity otherwise (CPU unit tests, single-device smoke runs)."""
    axes = _active_mesh_axes()
    if axes is None:
        return x
    used = {a for part in spec if part is not None
            for a in ((part,) if isinstance(part, str) else tuple(part))}
    if not used.issubset(set(axes)):
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, spec)


def specs_to_shardings(mesh: Mesh, specs: Any) -> Any:
    """Map a pytree of PartitionSpecs to NamedShardings on ``mesh``,
    dropping axis names the mesh doesn't have (e.g. 'pod' on single-pod)."""
    names = set(mesh.axis_names)

    def fix(spec: P) -> NamedSharding:
        parts = []
        for part in spec:
            if part is None:
                parts.append(None)
            elif isinstance(part, str):
                parts.append(part if part in names else None)
            else:  # tuple of axes
                kept = tuple(a for a in part if a in names)
                parts.append(kept if kept else None)
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(fix, specs,
                        is_leaf=lambda s: isinstance(s, P))
