"""The jnp forms of ``kv_write``: one scatter of the rows (``rows``, the
oracle) and a select of the rows into the layer's slab (``layer_select``).

The scatter drops a full slot's row (``pos == S``): JAX's ``drop`` mode
discards an out-of-range window.  XLA performs it in place in a donated
cache whose head axis lies on the lanes; where the sequence axis does, it
relayouts the whole cache.  The select writes a full slot's row nowhere
and reads and writes the layer's whole slab, in place in a donated cache,
whatever its layout or sharding."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def kv_write_ref(k_cache: jax.Array, v_cache: jax.Array, k_rows: jax.Array,
                 v_rows: jax.Array, layer: jax.Array, pos: jax.Array
                 ) -> tuple[jax.Array, jax.Array]:
    rows = jnp.arange(pos.shape[0])

    def write(cache, new):
        return cache.at[layer[0], rows, pos].set(new, mode="drop",
                                                 unique_indices=True)

    return write(k_cache, k_rows), write(v_cache, v_rows)


def step_slabs(k_cache: jax.Array, v_cache: jax.Array, k_rows: jax.Array,
               v_rows: jax.Array, layer: jax.Array, pos: jax.Array
               ) -> tuple[jax.Array, jax.Array]:
    """Layer ``layer``'s [B, S, KV, H] slabs of the caches with the rows
    selected in at ``pos``: what decode attention reads."""
    onehot = (jnp.arange(k_cache.shape[2])[None, :]
              == pos[:, None])[:, :, None, None]
    return tuple(jnp.where(onehot, new[:, None],
                           jax.lax.dynamic_index_in_dim(cache, layer[0], 0,
                                                        False))
                 for cache, new in ((k_cache, k_rows), (v_cache, v_rows)))
