"""Random weights from the seed, made on the device in one jitted call,
in the layout and dtype the program serves them in: the keys every
configuration draws from, and word2vec's weights.  A language model's
weights are its architecture's (``make_params`` of
``bench/models/<model_type>.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw uint32[2] key for ``(seed, stream)``; seeds of any width up
    to 64 bits map to distinct keys."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be a whole number in [0, 2**64), got {seed}")
    key = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jnp.asarray(key), stream)


def make_word2vec_params(cfg: dict, seed: int) -> dict:
    """word2vec weights: the input embedding table and the fp32 WOL
    (no output bias)."""
    m_in, h, m_out = cfg["input_dim"], cfg["hidden"], cfg["output_dim"]

    def build(key):
        k1, k2 = jax.random.split(key)
        return {"embed": jax.random.normal(k1, (m_in, h), jnp.float32),
                "w_out": jax.random.normal(k2, (m_out, h), jnp.float32)
                * h ** -0.5}

    return jax.block_until_ready(jax.jit(build)(seed_key(seed, 0)))


def hash_key(seed: int) -> jax.Array:
    """The key the LSS hyperplanes are drawn from (``Engine.fit_random``):
    i.i.d. N(0, 1) entries of ``[d + 1, K * L]``."""
    return seed_key(seed, 1)
