"""Random weights from the seed, made on the device in one jitted call,
in the layout and dtype the program serves them in.

The scales keep a random model from degenerating: the LM's token
embeddings are small against what its layers add to the residual stream,
so the next token depends on the whole body and not mostly on the input
token; QKV biases and norm scales are random, so each of them changes
the logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

EMBED_STD = 0.1          # LM token embedding (tied head)
BIAS_STD = 0.2           # QKV biases
NORM_JITTER = 0.1        # RMSNorm scales are 1 + NORM_JITTER * N(0, 1)


def seed_key(seed: int, stream: int) -> jax.Array:
    """A raw uint32[2] key for ``(seed, stream)``; seeds of any width up
    to 64 bits map to distinct keys."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be a whole number in [0, 2**64), got {seed}")
    key = np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.fold_in(jnp.asarray(key), stream)


def lm_shapes(cfg: dict) -> dict:
    n_l, d, f = (cfg["num_hidden_layers"], cfg["hidden_size"],
                 cfg["intermediate_size"])
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"n_l": n_l, "d": d, "f": f, "nq": nq, "nkv": nkv,
            "vocab": cfg["vocab_size"]}


def make_lm_params(cfg: dict, seed: int) -> dict:
    """Qwen2-style decoder weights: ``{"embed", "layers", "final_norm"}``
    with per-layer stacks ``[n_layers, ...]``; bf16 matrices and biases,
    fp32 norm scales."""
    s = lm_shapes(cfg)
    if not cfg.get("tie_word_embeddings", False):
        raise ValueError("only tied-embedding LMs are generated here")
    n_l, d, f, nq, nkv = s["n_l"], s["d"], s["f"], s["nq"], s["nkv"]

    def build(key):
        ks = jax.random.split(key, 16)

        def nrm(k, shape, std, dtype=jnp.bfloat16):
            return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)

        def norm_scale(k, shape):
            return 1.0 + NORM_JITTER * jax.random.normal(k, shape, jnp.float32)

        layers = {
            "ln1": norm_scale(ks[0], (n_l, d)),
            "ln2": norm_scale(ks[1], (n_l, d)),
            "wq": nrm(ks[2], (n_l, d, nq), d ** -0.5),
            "wk": nrm(ks[3], (n_l, d, nkv), d ** -0.5),
            "wv": nrm(ks[4], (n_l, d, nkv), d ** -0.5),
            "wo": nrm(ks[5], (n_l, nq, d), nq ** -0.5),
            "w_gate": nrm(ks[6], (n_l, d, f), d ** -0.5),
            "w_up": nrm(ks[7], (n_l, d, f), d ** -0.5),
            "w_down": nrm(ks[8], (n_l, f, d), f ** -0.5),
        }
        if cfg.get("qkv_bias"):
            layers["bq"] = nrm(ks[9], (n_l, nq), BIAS_STD)
            layers["bk"] = nrm(ks[10], (n_l, nkv), BIAS_STD)
            layers["bv"] = nrm(ks[11], (n_l, nkv), BIAS_STD)
        return {"embed": nrm(ks[12], (s["vocab"], d), EMBED_STD),
                "layers": layers,
                "final_norm": norm_scale(ks[13], (d,))}

    return jax.block_until_ready(jax.jit(build)(seed_key(seed, 0)))


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
