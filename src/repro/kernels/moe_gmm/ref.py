"""The jnp oracle of ``moe_gmm``: ``jax.lax.ragged_dot`` over the layer's
matrices with fp32 accumulation (rows past the last group come out
zero)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def moe_gmm_ref(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                layer: jax.Array) -> jax.Array:
    return jax.lax.ragged_dot(lhs, rhs[layer[0]], group_sizes,
                              preferred_element_type=jnp.float32)
