"""SimHash primitives for LSS.

A SimHash code of an input ``x`` under hyperplanes ``theta`` is the sign
pattern of ``theta^T x``.  LSS (the paper's contribution) *learns* the
hyperplanes; the hashing mechanics here are shared by the random
initialisation (SimHash / SLIDE baseline) and the learned index.

Conventions
-----------
* Neurons are augmented with their bias: ``c_i = [w_i, b_i]`` in R^{d+1}.
  Queries are augmented with a zero: ``[q, 0]``.  Helpers below do this.
* ``theta`` has shape ``[d_aug, K * L]`` — K bits for each of L tables.
* Bucket ids pack the K sign bits of one table into an int32 in
  ``[0, 2^K)``; shape ``[..., L]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = [
    "augment_neurons",
    "augment_queries",
    "init_hyperplanes",
    "unit",
    "hash_bits",
    "soft_codes",
    "pack_bits",
    "bucket_ids",
]


def augment_neurons(w: jax.Array, b: jax.Array | None = None) -> jax.Array:
    """``[m, d] (+ [m])`` -> ``[m, d+1]`` neurons ``[w_i, b_i]``."""
    if b is None:
        b = jnp.zeros((w.shape[0],), w.dtype)
    return jnp.concatenate([w, b[:, None].astype(w.dtype)], axis=-1)


def augment_queries(q: jax.Array) -> jax.Array:
    """``[..., d]`` -> ``[..., d+1]`` queries ``[q, 0]``."""
    return jnp.concatenate([q, jnp.zeros(q.shape[:-1] + (1,), q.dtype)], axis=-1)


def init_hyperplanes(key: jax.Array, d_aug: int, k_bits: int, n_tables: int,
                     dtype=jnp.float32) -> jax.Array:
    """i.i.d. N(0, 1) hyperplanes, shape ``[d_aug, K * L]`` (SimHash init)."""
    return jax.random.normal(key, (d_aug, k_bits * n_tables), dtype)


def unit(x: jax.Array) -> jax.Array:
    """L2-normalize the hashed vector.  ``sign(theta^T x)`` is invariant to
    positive scaling of x, so hard buckets are unchanged — but the tanh
    relaxation would saturate at ``|theta^T x| ~ ||x|| ~ sqrt(d)`` and kill
    IUL gradients.  Normalizing is therefore part of the hash definition
    (the fused lss_topk kernel replicates it bit-for-bit)."""
    n = jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) / jnp.maximum(n, 1e-12))


_unit = unit   # historical private name


def hash_bits(x: jax.Array, theta: jax.Array) -> jax.Array:
    """Hard hash bits ``sign(theta^T x) > 0`` -> bool ``[..., K*L]``.

    The hash is defined at fp32 precision, as the kernels compute it: a
    TPU matmul at default precision rounds its operands to bf16, and
    neurons hashed that way at index build would disagree with queries
    hashed in fp32 whenever a score is near zero."""
    return jnp.matmul(_unit(x), theta.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST) > 0


def soft_codes(x: jax.Array, theta: jax.Array) -> jax.Array:
    """Differentiable relaxation ``K(x) = tanh(theta^T x)`` (paper eq. 1)."""
    return jnp.tanh(_unit(x) @ theta.astype(jnp.float32))


def pack_bits(bits: jax.Array, k_bits: int, n_tables: int) -> jax.Array:
    """Pack bool bits ``[..., K*L]`` into int32 bucket ids ``[..., L]``.

    Bit j of table l is ``bits[..., l*K + j]`` with weight ``2^j``.
    """
    shaped = bits.reshape(bits.shape[:-1] + (n_tables, k_bits))
    weights = (2 ** jnp.arange(k_bits, dtype=jnp.int32))
    return jnp.sum(shaped.astype(jnp.int32) * weights, axis=-1)


def bucket_ids(x: jax.Array, theta: jax.Array, k_bits: int,
               n_tables: int) -> jax.Array:
    """``[..., d_aug]`` -> int32 bucket ids ``[..., L]`` in ``[0, 2^K)``."""
    return pack_bits(hash_bits(x, theta), k_bits, n_tables)
