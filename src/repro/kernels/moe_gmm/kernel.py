"""Pallas TPU kernel: grouped matrix product over the experts a layer holds.

``lhs`` rows are sorted by group (expert); group ``g`` owns the rows
``[offsets[g], offsets[g + 1])`` and multiplies them by ``rhs[layer, g]``:
the weights of every layer stay one stacked array in HBM, and the layer
index, like the group metadata, is scalar-prefetched into SMEM, so no
layer's slice is copied out before the call.

The grid walks ``(n tile, active tile, k tile)``.  An active tile is one
(m tile, group) pair that holds at least one row of the group: the
metadata (``make_group_metadata``, as the megablox kernel computes it,
empty groups squeezed out) lists them in row order, and the grid's
middle extent is their traced count, so a group with no row is never
visited and its weights are never read.  A group's ``[tk, tn]`` weight
blocks stream through VMEM once per m tile it touches; the fp32
accumulator lives in scratch, and on the last k tile only the rows of
the visited group are stored, so an m tile shared by several groups is
revisited consecutively and each visit fills its own rows.  Rows past
the last group are never stored: ``ops.py`` zeroes them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


def _kernel(offsets_ref, gids_ref, mids_ref, layer_ref, lhs_ref, rhs_ref,
            out_ref, acc_ref, *, tm: int, tn: int, n_k: int):
    del layer_ref                       # read by the weight block's map
    grid_id = pl.program_id(1)
    k_i = pl.program_id(2)

    @pl.when(k_i == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(lhs_ref[...], rhs_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_i == n_k - 1)
    def _store():
        g = gids_ref[grid_id]
        row = (jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
               + mids_ref[grid_id] * tm)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        out_ref[...] = jnp.where(mine, acc_ref[...], out_ref[...])


@functools.partial(jax.jit, static_argnames=("tiling", "vmem_limit_bytes",
                                             "interpret"))
def moe_gmm_pallas(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   layer: jax.Array, *, tiling: tuple[int, int, int],
                   vmem_limit_bytes: int | None = None,
                   interpret: bool = False) -> jax.Array:
    """``[M, K] x [L, G, K, N][layer] -> fp32 [M, N]`` over group-sorted
    rows.

    Args:
      lhs: ``[M, K]`` rows sorted by group, M a multiple of ``tm``.
      rhs: ``[L, G, K, N]`` one weight matrix per layer and group.
      group_sizes: int32 ``[G]``, summing to at most M.
      layer: int32 ``[1]``, the layer whose matrices the groups use.
      tiling: ``(tm, tk, tn)``; tk divides K and tn divides N.
      vmem_limit_bytes: scoped VMEM the kernel asks Mosaic for.

    Rows past ``sum(group_sizes)``, and rows of m tiles no group
    touches, are left as the output buffer held them.
    """
    m, k = lhs.shape
    _, g, k2, n = rhs.shape
    tm, tk, tn = tiling
    assert k == k2 and m % tm == 0 and k % tk == 0 and n % tn == 0, \
        (lhs.shape, rhs.shape, tiling)
    meta, n_active = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm,
        start_group=jnp.zeros((), jnp.int32), num_nonzero_groups=g,
        visit_empty_groups=False)
    n_k = k // tk

    def lhs_map(n_i, t, k_i, offsets, gids, mids, layer):
        return mids[t], k_i

    def rhs_map(n_i, t, k_i, offsets, gids, mids, layer):
        return layer[0], gids[t], k_i, n_i

    def out_map(n_i, t, k_i, offsets, gids, mids, layer):
        return mids[t], n_i

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, tn=tn, n_k=n_k),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, tk), lhs_map),
                      pl.BlockSpec((None, None, tk, tn), rhs_map)],
            out_specs=pl.BlockSpec((tm, tn), out_map),
            # an all-empty call still runs one (masked) tile: the grid
            # never has an empty axis
            grid=(n // tn, jnp.maximum(n_active, 1), n_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
        name="moe_gmm_pallas",
    )(*meta, layer, lhs, rhs)
