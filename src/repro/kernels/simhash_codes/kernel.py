"""Pallas TPU kernel: fused ``X @ Theta -> sign -> bit-pack``.

One VMEM pass produces int32 bucket ids directly, instead of materialising
the ``[B, K*L]`` float scores and bool bits in HBM (3 HBM round-trips in the
naive lowering).  The bit-pack is expressed as a second tiny matmul against
a constant ``[K*L, L]`` selection matrix (MXU-friendly; values < 2^24 are
exact in f32).

Target layout notes (TPU v5e):
  * ``d_aug`` is padded to a multiple of 128 (lane dim) by ops.py.
  * block over batch: ``[TB, d]``; theta is small (KL <= 512 columns) and
    kept fully resident in VMEM across the grid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 256


def _kernel(x_ref, theta_ref, pack_ref, out_ref):
    x = x_ref[...].astype(jnp.float32)          # [TB, d]
    th = theta_ref[...].astype(jnp.float32)     # [d, KL]
    scores = jax.lax.dot_general(
        x, th, (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)     # [TB, KL], fp32 hash
    bits = (scores > 0).astype(jnp.float32)
    packed = jax.lax.dot_general(
        bits, pack_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)     # [TB, L]
    out_ref[...] = packed.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k_bits", "n_tables",
                                             "block_b", "interpret"))
def simhash_codes_pallas(x: jax.Array, theta: jax.Array, *, k_bits: int,
                         n_tables: int, block_b: int = DEFAULT_BLOCK_B,
                         interpret: bool = False) -> jax.Array:
    """``[B, d] x [d, K*L] -> int32 [B, L]`` (B, d pre-padded by ops.py)."""
    bsz, d = x.shape
    kl = k_bits * n_tables
    assert theta.shape == (d, kl)
    assert bsz % block_b == 0, (bsz, block_b)
    # constant pack matrix: pack[l*K + j, l] = 2^j
    eye = jnp.eye(n_tables, dtype=jnp.float32)
    w = (2.0 ** jnp.arange(k_bits, dtype=jnp.float32))
    pack = (eye[:, None, :] * w[None, :, None]).reshape(kl, n_tables)

    grid = (bsz // block_b,)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d), lambda i: (i, 0)),
            pl.BlockSpec((d, kl), lambda i: (0, 0)),
            pl.BlockSpec((kl, n_tables), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_tables), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, n_tables), jnp.int32),
        interpret=interpret,
    )(x, theta, pack)
