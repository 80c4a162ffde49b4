"""Pure-jnp oracle for the fused simhash-code kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def simhash_codes_ref(x: jax.Array, theta: jax.Array, k_bits: int,
                      n_tables: int) -> jax.Array:
    """``[B, d] x [d, K*L] -> int32 bucket ids [B, L]``.

    sign(theta^T x) bits packed little-endian within each table.  No input
    normalization: sign() is scale-invariant, hard codes don't need it.
    """
    bits = jnp.matmul(x.astype(jnp.float32), theta.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST) > 0   # fp32 hash
    shaped = bits.reshape(x.shape[0], n_tables, k_bits)
    weights = 2 ** jnp.arange(k_bits, dtype=jnp.int32)
    return jnp.sum(shaped.astype(jnp.int32) * weights, axis=-1)
