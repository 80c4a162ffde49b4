"""Public op: write a decode step's KV rows into the layer-stacked caches.

``kv_write(k_cache, v_cache, k_rows, v_rows, layer, pos) -> (k_cache,
v_cache, k_slab, v_slab)``: caches ``[L, B, S, KV, H]``, rows ``[B, KV,
H]``; row ``r`` goes to ``[layer, r, pos[r]]`` and a row at ``pos[r] ==
S`` writes nothing.  The slabs are layer ``layer``'s ``[B, S, KV, H]``
holding the step, for decode attention to read.  The write takes the
form that updates the cache in place, from the cache's shape, the mesh
and the registry's impl (``ref`` | ``pallas`` | ``pallas_interpret``),
and records it as ``("decode.kv_write", form)``:

* ``rows``: one scatter of the rows (``ref.py``), where the head axis
  fills whole 128-lane tiles and the TPU lays the cache out head-minor.
  Its slabs are the rows selected into the layer's slabs as they were
  before the write, the expression attention read when each step rewrote
  the whole cache: XLA fuses the select into attention, which so runs
  the code it ran then and gives its bits (read after the scatter, the
  same values took other attention code on a v5e and other low bits);
* ``lanes``: the Pallas kernel (``kernel.py``), where a narrower head
  leaves the sequence axis on the lanes and a one-position update would
  relayout the whole cache;
* ``layer_select``: the rows selected into the layer's whole slab
  (``ref.step_slabs``) and the slab written back, for a narrower head
  under the ``ref`` impl, and wherever a mesh axis splits the cache:
  GSPMD cannot partition the kernel's custom call, and partitions the
  select elementwise.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.kv_write.kernel import (LANE, kv_write_lanes_pallas,
                                           lane_window)
from repro.kernels.kv_write.ref import kv_write_ref, step_slabs
from repro.kernels.registry import kernel_op
from repro.utils.sharding import mesh_axis_size

kv_write_op = kernel_op("kv_write")
kv_write_op.register_impl("ref", kv_write_ref)

# VMEM for one grid step's windows (both caches): small next to the
# default scoped limit, large enough that a decode step's rows are all in
# flight at once
WINDOW_BYTES = 4 * 2 ** 20


def rows_per_step(b: int, row_bytes: int) -> int:
    """The most batch rows whose windows fit ``WINDOW_BYTES`` and that
    divide ``b`` (at least one)."""
    return max([r for r in range(1, b + 1)
                if b % r == 0 and r * row_bytes <= WINDOW_BYTES] or [1])


def _pallas_impl(k_cache, v_cache, k_rows, v_rows, layer, pos, *,
                 interpret: bool):
    _, b, s, n_kv, h = k_cache.shape
    row_bytes = (2 * n_kv * h * lane_window(s)
                 * jnp.dtype(k_cache.dtype).itemsize)
    return kv_write_lanes_pallas(k_cache, v_cache, k_rows, v_rows, layer,
                                 pos, rows=rows_per_step(b, row_bytes),
                                 interpret=interpret)


kv_write_op.register_impl(
    "pallas", functools.partial(_pallas_impl, interpret=False))
kv_write_op.register_impl(
    "pallas_interpret", functools.partial(_pallas_impl, interpret=True))


def kv_write_form(head_dim: int, impl: str) -> str:
    """``rows`` | ``lanes`` | ``layer_select`` (see the module text) for a
    cache of ``head_dim`` under the mesh being traced and ``impl``."""
    if any((mesh_axis_size(a) or 1) > 1 for a in ("data", "model")):
        return "layer_select"
    if head_dim % LANE == 0:
        return "rows"
    return "layer_select" if impl == "ref" else "lanes"


def kv_write(k_cache: jax.Array, v_cache: jax.Array, k_rows: jax.Array,
             v_rows: jax.Array, layer: jax.Array | int, pos: jax.Array, *,
             impl: str | None = None
             ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Write ``k_rows``/``v_rows`` [B, KV, H] into layer ``layer`` of the
    caches [L, B, S, KV, H] at ``pos`` (int32 [B]) -> (k_cache, v_cache,
    k_slab, v_slab); see the module text.

    impl: ``ref`` | ``pallas`` | ``pallas_interpret`` | None (registry
    auto-selection — see ``repro.kernels.registry``); only the ``lanes``
    form dispatches the op.
    """
    args = (k_cache, v_cache, k_rows.astype(k_cache.dtype),
            v_rows.astype(v_cache.dtype),
            jnp.asarray(layer, jnp.int32).reshape(1),
            jnp.asarray(pos, jnp.int32))
    impl = registry.resolve_impl("kv_write", impl)
    form = kv_write_form(k_cache.shape[-1], impl)
    registry.record("decode.kv_write", form)
    if form == "lanes":
        k_cache, v_cache = kv_write_op(*args, impl=impl)
        return (k_cache, v_cache,
                jax.lax.dynamic_index_in_dim(k_cache, args[4][0], 0, False),
                jax.lax.dynamic_index_in_dim(v_cache, args[4][0], 0, False))
    k_slab, v_slab = step_slabs(*args)
    if form == "rows":
        return (*kv_write_ref(*args), k_slab, v_slab)
    return (jax.lax.dynamic_update_index_in_dim(k_cache, k_slab, args[4][0],
                                                0),
            jax.lax.dynamic_update_index_in_dim(v_cache, v_slab, args[4][0],
                                                0),
            k_slab, v_slab)
