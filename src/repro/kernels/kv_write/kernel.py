"""Pallas TPU kernel: write one new KV row per batch row into caches laid
out with the sequence axis on the lanes.

At a head narrower than the 128 lanes (qwen2-0.5b: 2 KV heads of 64) the
TPU stores a ``[L, B, S, KV, H]`` cache sequence-minor, so one
position's ``[KV, H]`` row is one lane of ``KV * H`` sublanes, and XLA
can update it in place only by relayouting the whole cache.  The kernel
takes the cache as its ``[L, B, KV, H, S]`` transpose, which in that
layout is a bitcast, and for each row reads the ``[KV, H, W]`` window of
lanes that holds its position (``W`` = 128, or all of ``S`` where 128
does not divide it), selects the new row into its lane, and writes the
window back: both caches stay in HBM, aliased to the outputs, and the
rows of a grid step are all in flight at once.  A row at ``pos == S``
matches no lane and writes its window back unchanged.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def lane_window(s: int) -> int:
    """Positions a row's window spans: 128, or all of ``s`` where 128
    does not divide it."""
    return LANE if s % LANE == 0 else s


def _kernel(pos_ref, layer_ref, nk_ref, nv_ref, ck_hbm, cv_hbm, ok_hbm,
            ov_hbm, buf, sem, *, rows: int, s: int, w: int):
    del ck_hbm, cv_hbm                   # aliased to ok_hbm, ov_hbm
    layer = layer_ref[0]
    first = pl.program_id(0) * rows
    pos = [pos_ref[first + r] for r in range(rows)]
    start = [pl.multiple_of(jnp.minimum(p, s - 1) // w * w, w) for p in pos]

    def copies(to_vmem):
        cps = []
        for j, cache in enumerate((ok_hbm, ov_hbm)):
            for r in range(rows):
                win = cache.at[layer, first + r, :, :, pl.ds(start[r], w)]
                src, dst = (win, buf.at[j, r]) if to_vmem else (buf.at[j, r],
                                                               win)
                cps.append(pltpu.make_async_copy(src, dst, sem.at[j, r]))
        for cp in cps:
            cp.start()
        for cp in cps:
            cp.wait()

    copies(True)
    lane = jax.lax.broadcasted_iota(jnp.int32, buf.shape[2:], 2)
    for j, new in enumerate((nk_ref, nv_ref)):
        for r in range(rows):
            buf[j, r] = jnp.where(lane == pos[r] - start[r], new[r],
                                  buf[j, r])
    copies(False)


@functools.partial(jax.jit, static_argnames=("rows", "interpret"))
def kv_write_lanes_pallas(k_cache: jax.Array, v_cache: jax.Array,
                          k_rows: jax.Array, v_rows: jax.Array,
                          layer: jax.Array, pos: jax.Array, *, rows: int,
                          interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """k/v caches [L, B, S, KV, H], rows [B, KV, H], layer int32 [1], pos
    int32 [B]; ``rows`` batch rows a grid step (it divides B)."""
    n_l, b, s, n_kv, h = k_cache.shape
    w = lane_window(s)

    def lanes(cache):                    # [L, B, KV, H, S]
        return jnp.transpose(cache, (0, 1, 3, 4, 2))

    def column(new):                     # [B, KV, H, 1]
        return new[..., None]

    row_spec = pl.BlockSpec((rows, n_kv, h, 1), lambda i, *_: (i, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    shape = jax.ShapeDtypeStruct((n_l, b, n_kv, h, s), k_cache.dtype)
    k_out, v_out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, s=s, w=w),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b // rows,),
            in_specs=[row_spec, row_spec, hbm, hbm],
            out_specs=[hbm, hbm],
            scratch_shapes=[pltpu.VMEM((2, rows, n_kv, h, w), k_cache.dtype),
                            pltpu.SemaphoreType.DMA((2, rows))]),
        out_shape=[shape, shape],
        input_output_aliases={4: 0, 5: 1},
        name="kv_write_lanes_pallas",
        interpret=interpret,
    )(pos, layer, column(k_rows), column(v_rows), lanes(k_cache),
      lanes(v_cache))

    def back(cache):
        return jnp.transpose(cache, (0, 1, 4, 2, 3))

    return back(k_out), back(v_out)
