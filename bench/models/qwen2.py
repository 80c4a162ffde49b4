"""Qwen2 (arXiv:2407.10671; HF ``Qwen2ForCausalLM``, ``model_type``
``qwen2``): the harness's view of a dense decoder with grouped-query
attention, optional QKV biases and a tied or untied head.

What a decode cell needs of an architecture, looked up by the
configuration's ``model_type`` (``bench.spec.arch``):

* ``make_params(cfg, seed)``: random weights from the seed, made on the
  device in one jitted call, in the layout and dtype the program serves;
* ``program_config(cfg)``: the program's model configuration;
* ``head_table(params)``: the rows the program's head ranks;
* ``hidden(params, cfg, tokens, prec, pad_to)``: the plain reference's
  final-norm hidden states, importing nothing of the program;
* ``body_work(cfg, n_steps, contexts)``: the required operations and
  bytes of the body in ``n_steps`` fused decode steps
  (``bench.work.decode_steps`` adds the head's).

The weight scales keep a random model from degenerating: the token
embeddings are small against what the layers add to the residual
stream, so the next token depends on the whole body and not mostly on
the input token; QKV biases and norm scales are random, so each of them
changes the logits.
"""

from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import mm, rms_norm, rope
from bench.weights import seed_key
from bench.work import BF16, FP32, Work

EMBED_STD = 0.1          # token embedding
BIAS_STD = 0.2           # QKV biases
NORM_JITTER = 0.1        # RMSNorm scales are 1 + NORM_JITTER * N(0, 1)


# ------------------------------------------------------------ weights --

def _shapes(cfg: dict) -> dict:
    n_l, d, f = (cfg["num_hidden_layers"], cfg["hidden_size"],
                 cfg["intermediate_size"])
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"n_l": n_l, "d": d, "f": f, "nq": nq, "nkv": nkv,
            "vocab": cfg["vocab_size"]}


def make_params(cfg: dict, seed: int) -> dict:
    """``{"embed", "layers", "final_norm"}``, and ``"lm_head"`` where the
    head is untied, with per-layer stacks ``[n_layers, ...]``; bf16
    matrices and biases, fp32 norm scales."""
    s = _shapes(cfg)
    n_l, d, f, nq, nkv = s["n_l"], s["d"], s["f"], s["nq"], s["nkv"]
    tied = cfg["tie_word_embeddings"]

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
        params = {"embed": nrm(ks[12], (s["vocab"], d), EMBED_STD),
                  "layers": layers,
                  "final_norm": norm_scale(ks[13], (d,))}
        if not tied:                     # a stream no tied draw uses
            params["lm_head"] = nrm(ks[14], (s["vocab"], d), d ** -0.5)
        return params

    return jax.block_until_ready(jax.jit(build)(seed_key(seed, 0)))


def program_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from repro.models.transformer import TransformerConfig
    if cfg.get("torch_dtype") != "bfloat16":
        raise ValueError("decode cells serve bf16 models")
    return TransformerConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        qkv_bias=bool(cfg.get("qkv_bias")), qk_norm=False,
        rope_base=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=jnp.bfloat16)


def head_table(params: dict) -> jax.Array:
    """The head's rows: ``lm_head`` where the head is untied, else the
    token embedding (what ``LMDecoder.head_weights`` serves)."""
    return params["lm_head"] if "lm_head" in params else params["embed"]


# ---------------------------------------------------------- reference --

@functools.partial(jax.jit, static_argnames=("shape", "prec"))
def _lm_hidden(params, tokens, shape, prec):
    """RMSNorm (eps from the config), rotary embeddings on the two halves
    of each head, grouped-query attention with QKV bias and a causal
    mask, a SwiGLU MLP, a final RMSNorm (HF ``Qwen2Model``)."""
    (n_h, n_kv, hd, eps, theta) = shape
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)                # [S, d]
    pos = jnp.arange(s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = n_h // n_kv

    def layer(x, lp):
        h = rms_norm(x, lp["ln1"], eps)
        q = mm(h, lp["wq"], prec) + lp["bq"].astype(jnp.float32)
        k = mm(h, lp["wk"], prec) + lp["bk"].astype(jnp.float32)
        v = mm(h, lp["wv"], prec) + lp["bv"].astype(jnp.float32)
        q = rope(q.reshape(s, n_h, hd), pos, theta)
        k = rope(k.reshape(s, n_kv, hd), pos, theta)
        v = v.reshape(s, n_kv, hd)
        outs = []
        for head in range(n_h):                  # query head -> its KV group
            kv = head // group
            sc = mm(q[:, head], k[:, kv].T, prec) / jnp.sqrt(jnp.float32(hd))
            sc = jnp.where(causal, sc, -jnp.inf)
            outs.append(mm(jax.nn.softmax(sc, axis=-1), v[:, kv], prec))
        x = x + mm(jnp.concatenate(outs, -1), lp["wo"], prec)
        h = rms_norm(x, lp["ln2"], eps)
        g = mm(h, lp["w_gate"], prec)
        u = mm(h, lp["w_up"], prec)
        x = x + mm(jax.nn.silu(g) * u, lp["w_down"], prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps)


def hidden(params: dict, cfg: dict, tokens: np.ndarray,
           prec: str = "highest", pad_to: int | None = None) -> jax.Array:
    """Final-norm hidden states ``[S, d]`` of one full causal forward pass
    over the whole sequence, no cache.  ``pad_to`` pads the sequence at
    its end (causality keeps the real positions exact) so that sequences
    of any length share one program."""
    toks = np.asarray(tokens, np.int32)
    n = toks.shape[0]
    if pad_to is not None and pad_to > n:
        toks = np.concatenate([toks, np.zeros(pad_to - n, np.int32)])
    layers = dict(params["layers"])
    if not cfg.get("qkv_bias"):
        for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            layers[b] = jnp.zeros(layers[w].shape[::2], jnp.float32)
    shape = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["head_dim"], float(cfg["rms_norm_eps"]),
             float(cfg["rope_theta"]))
    p = {"embed": params["embed"], "layers": layers,
         "final_norm": params["final_norm"]}
    return _lm_hidden(p, jnp.asarray(toks), shape, prec)[:n]


# --------------------------------------------------------------- work --

def _layer_weights(cfg: dict) -> int:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    n = d * nq + 2 * d * nkv + nq * d + 3 * d * f
    if cfg.get("qkv_bias"):
        n += nq + 2 * nkv
    return n


def kv_bytes_per_position(cfg: dict) -> int:
    """bf16 keys and values of one position across every layer."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def body_work(cfg: dict, n_steps: int, contexts: Sequence[int]) -> Work:
    """The body of ``n_steps`` fused decode steps over rows whose contexts
    are ``contexts`` (``bench.work.decode_steps``).

    Operations: every layer's weights once per row, attention scores and
    values over the row's context.  Bytes: the bf16 layer weights and
    norms once per step, the row's embedding, its cached KV read and its
    new position written (together its context)."""
    rows = len(contexts)
    n_l = cfg["num_hidden_layers"]
    d = cfg["hidden_size"]
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    w = _layer_weights(cfg)
    ctx = float(sum(contexts))
    flops = 2.0 * w * n_l * rows + 4.0 * nq * n_l * ctx
    norms = (2 * n_l + 1) * d * FP32
    nbytes = (n_steps * (w * n_l * BF16 + norms) + rows * d * BF16
              + kv_bytes_per_position(cfg) * ctx)
    return Work(flops, nbytes)
