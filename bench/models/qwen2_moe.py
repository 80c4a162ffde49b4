"""Qwen2-MoE (Qwen1.5-MoE-A2.7B; HF ``Qwen2MoeForCausalLM``,
``model_type`` ``qwen2_moe``): the harness's view of a decoder whose
every layer is Qwen2 attention (QKV biases, rotary embeddings) followed
by a sparse block of fine-grained routed experts and one gated shared
expert, with an untied head.

The module holds what ``bench/models/qwen2.py`` lists (``make_params``,
``program_config``, ``head_table``, ``hidden``, ``body_work``), and
``expert_work``: the required work of the grouped expert products from
the program's routing counters.

The configuration is one chip's share of an expert-parallel deployment:
``num_experts`` experts held here, from ``expert_offset``, of the
``published`` count, which is the router's width.  The router scores
every expert and keeps its top ``num_experts_per_tok``; the layer adds
only the held experts' part, in the program and in the reference alike.

Weight scales keep a random model from degenerating, as Qwen2's do
(small token embeddings, random biases and norm scales), and make the
experts matter: the router's logits have a standard deviation of
``ROUTER_LOGIT_STD``, so each token's top-4 carry most of the softmax
(with ``norm_topk_prob`` false the weights are not renormalised, and a
near-uniform router would leave the routed part ~0.1 of a token's
update); every projection, the experts' included, has the fan-in scale.
Zeroing the held experts then moves the served tokens far past the
cell's limits.  Larger expert outputs make the model chaotic instead: at
4x the fan-in scale a bf16 body drifted from the fp32 reference by up to
2 logits in sound runs on a TPU v5e, about as far as an fp8 body did.

The routers are balanced, as training for balanced load leaves them: a
random router sees a random model's hidden states dominated by what all
tokens share (the value biases and attention's averaging), and sends
most tokens to the same few experts.  ``make_params`` therefore runs the
reference over ``CALIBRATION_TOKENS`` seeded tokens and gives each layer
the router ``_balanced`` over that layer's inputs, so expert load is
near uniform, as ``body_work`` counts it.
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
ROUTER_LOGIT_STD = 3.0   # the routers' logits over the calibration tokens
CALIBRATION_TOKENS = 512  # one sequence of uniform tokens from the seed


def _shapes(cfg: dict) -> dict:
    nq = cfg["num_attention_heads"] * cfg["head_dim"]
    nkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {"n_l": cfg["num_hidden_layers"], "d": cfg["hidden_size"],
            "nq": nq, "nkv": nkv, "vocab": cfg["vocab_size"],
            "held": cfg["num_experts"], "offset": cfg.get("expert_offset", 0),
            "router": router_width(cfg), "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "sf": cfg["shared_expert_intermediate_size"]}


def router_width(cfg: dict) -> int:
    """The router's outputs: the published expert count where the file
    holds a share of the experts, else every expert is held."""
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


# ------------------------------------------------------------ weights --

def make_params(cfg: dict, seed: int) -> dict:
    """``{"embed", "layers", "final_norm", "lm_head"}`` with per-layer
    stacks ``[n_layers, ...]`` and the routed experts under
    ``layers["moe"]`` (router ``[d, E]`` over every expert, stacks of
    the held ones ``[held, ...]``); bf16 matrices and biases, fp32 norm
    scales."""
    s = _shapes(cfg)
    n_l, d, nq, nkv = s["n_l"], s["d"], s["nq"], s["nkv"]
    el, f, sf = s["held"], s["f"], s["sf"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("qwen2_moe draws an untied head")

    def build(key):
        ks = jax.random.split(key, 20)

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
            "bq": nrm(ks[6], (n_l, nq), BIAS_STD),
            "bk": nrm(ks[7], (n_l, nkv), BIAS_STD),
            "bv": nrm(ks[8], (n_l, nkv), BIAS_STD),
            "moe": {
                "router": nrm(ks[9], (n_l, d, s["router"]),
                              ROUTER_LOGIT_STD * d ** -0.5),
                "w_gate": nrm(ks[10], (n_l, el, d, f), d ** -0.5),
                "w_up": nrm(ks[11], (n_l, el, d, f), d ** -0.5),
                "w_down": nrm(ks[12], (n_l, el, f, d), f ** -0.5),
            },
            "sh_gate": nrm(ks[13], (n_l, d, sf), d ** -0.5),
            "sh_up": nrm(ks[14], (n_l, d, sf), d ** -0.5),
            "sh_down": nrm(ks[15], (n_l, sf, d), sf ** -0.5),
            "sh_gate_w": nrm(ks[16], (n_l, d, 1), d ** -0.5),
        }
        return {"embed": nrm(ks[17], (s["vocab"], d), EMBED_STD),
                "layers": layers,
                "final_norm": norm_scale(ks[18], (d,)),
                "lm_head": nrm(ks[19], (s["vocab"], d), d ** -0.5)}

    params = jax.jit(build)(seed_key(seed, 0))
    tokens = jax.random.randint(seed_key(seed, 1), (CALIBRATION_TOKENS,),
                                0, s["vocab"])
    p = {"embed": params["embed"], "layers": params["layers"],
         "final_norm": params["final_norm"]}
    params["layers"]["moe"]["router"] = _balanced_routers(p, tokens,
                                                          _static(cfg))
    return jax.block_until_ready(params)


def program_config(cfg: dict):
    """The program's ``TransformerConfig`` for a configuration file."""
    from repro.models.transformer import TransformerConfig
    if cfg.get("torch_dtype") != "bfloat16":
        raise ValueError("decode cells serve bf16 models")
    if cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers"):
        raise ValueError("the program's qwen2_moe layers are all sparse")
    s = _shapes(cfg)
    return TransformerConfig(
        name=cfg["name"], n_layers=s["n_l"], d_model=s["d"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["intermediate_size"], vocab=s["vocab"],
        qkv_bias=bool(cfg.get("qkv_bias")), qk_norm=False,
        rope_base=float(cfg["rope_theta"]), tie_embeddings=False,
        moe_style="replace", n_experts=s["router"],
        n_experts_padded=s["router"], moe_top_k=s["top_k"],
        moe_d_ff=s["f"], shared_expert_ff=s["sf"],
        moe_norm_topk=bool(cfg["norm_topk_prob"]), moe_held=s["held"],
        moe_held_offset=s["offset"], dtype=jnp.bfloat16)


def head_table(params: dict) -> jax.Array:
    """The head's rows: the untied ``lm_head``."""
    return params["lm_head"]


# ---------------------------------------------------------- reference --

def _balanced(h, router):
    """The router as one trained for balanced load would see the layer's
    inputs ``h``: the random router applied to ``h`` whitened (the
    inputs' covariance, shrunk towards its mean variance, to the power
    -1/2), with the response to the inputs' mean taken out, so that every
    expert's logit has mean zero and about the same spread and no two
    are correlated over the inputs; scaled so that the logits have
    standard deviation ``ROUTER_LOGIT_STD`` (bf16)."""
    d = h.shape[1]
    mu = jnp.mean(h, axis=0)
    hc = h - mu
    cov = mm(hc.T, hc, "highest") / h.shape[0]
    ev, vec = jnp.linalg.eigh(cov)
    ev = ev + jnp.trace(cov) / d
    w = mm(vec * ev ** -0.5, mm(vec.T, router.astype(jnp.float32),
                                "highest"), "highest")
    w = w - jnp.outer(mu, mm(mu[None], w, "highest")[0]) / jnp.dot(mu, mu)
    w = w * (ROUTER_LOGIT_STD / jnp.std(mm(h, w, "highest")))
    return w.astype(router.dtype)


def _forward(params, tokens, shape, prec, calibrate):
    """HF ``Qwen2MoeModel``: RMSNorm (eps from the config), attention
    with QKV biases and rotary embeddings on the two halves of each head
    under a causal mask, then the sparse block: a softmax over every
    router output, the top-k weights (renormalised only with
    ``norm_topk_prob``), each held expert's SwiGLU computed on every
    token and weighted by its routing weight (zero off the token's
    top-k), and the shared expert's SwiGLU scaled by the sigmoid of its
    gate; a final RMSNorm.  -> (hidden, the routers used); with
    ``calibrate`` each layer's router is first ``_balanced`` over the
    layer's own inputs."""
    (n_h, n_kv, hd, eps, theta, top_k, offset, norm_topk) = shape
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

        moe = lp["moe"]
        router = _balanced(h, moe["router"]) if calibrate else moe["router"]
        probs = jax.nn.softmax(mm(h, router, prec), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top_k)
        if norm_topk:
            top_p = top_p / jnp.sum(top_p, -1, keepdims=True)

        def expert(acc, xs):
            j, wg, wu, wd = xs
            w = jnp.sum(jnp.where(top_e == offset + j, top_p, 0.0), -1)
            y = mm(jax.nn.silu(mm(h, wg, prec)) * mm(h, wu, prec), wd, prec)
            return acc + w[:, None] * y, None

        n_held = moe["w_gate"].shape[0]
        routed, _ = jax.lax.scan(
            expert, jnp.zeros_like(h),
            (jnp.arange(n_held), moe["w_gate"], moe["w_up"], moe["w_down"]))
        gate = jax.nn.sigmoid(mm(h, lp["sh_gate_w"], prec))
        shared = mm(jax.nn.silu(mm(h, lp["sh_gate"], prec))
                    * mm(h, lp["sh_up"], prec), lp["sh_down"], prec)
        return x + routed + gate * shared, router

    x, routers = jax.lax.scan(layer, x, params["layers"])
    return rms_norm(x, params["final_norm"], eps), routers


@functools.partial(jax.jit, static_argnames=("shape", "prec"))
def _moe_hidden(params, tokens, shape, prec):
    return _forward(params, tokens, shape, prec, False)[0]


@functools.partial(jax.jit, static_argnames=("shape",))
def _balanced_routers(params, tokens, shape):
    return _forward(params, tokens, shape, "highest", True)[1]


def hidden(params: dict, cfg: dict, tokens: np.ndarray,
           prec: str = "highest", pad_to: int | None = None) -> jax.Array:
    """Final-norm hidden states ``[S, d]`` of one full causal forward pass
    over the whole sequence, no cache.  ``pad_to`` pads the sequence at
    its end (causality keeps the real positions exact) so that sequences
    of any length share one program.

    Departures from HF, each what the program does too: only the held
    experts' part of the sparse block is computed (experts held on other
    chips add nothing); every product is fp32 at ``prec``, and the
    routing weights stay fp32 where HF casts them to bf16."""
    toks = np.asarray(tokens, np.int32)
    n = toks.shape[0]
    if pad_to is not None and pad_to > n:
        toks = np.concatenate([toks, np.zeros(pad_to - n, np.int32)])
    p = {"embed": params["embed"], "layers": params["layers"],
         "final_norm": params["final_norm"]}
    return _moe_hidden(p, jnp.asarray(toks), _static(cfg), prec)[:n]


def _static(cfg: dict) -> tuple:
    s = _shapes(cfg)
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), s["top_k"], s["offset"],
            bool(cfg["norm_topk_prob"]))


# --------------------------------------------------------------- work --

def _dense_weights(cfg: dict) -> int:
    """Weights of one layer that every token reads: attention with its
    biases, the router and the shared expert with its gate."""
    s = _shapes(cfg)
    d, nq, nkv = s["d"], s["nq"], s["nkv"]
    attn = d * nq + 2 * d * nkv + nq * d + nq + 2 * nkv
    return attn + d * s["router"] + 3 * d * s["sf"] + d


def _expert_weights(cfg: dict) -> int:
    s = _shapes(cfg)
    return 3 * s["d"] * s["f"]


def kv_bytes_per_position(cfg: dict) -> int:
    """bf16 keys and values of one position across every layer."""
    return (cfg["num_hidden_layers"] * 2 * cfg["num_key_value_heads"]
            * cfg["head_dim"] * BF16)


def expected_active(cfg: dict, rows: float) -> float:
    """Held experts of one layer that at least one of ``rows`` tokens
    routes to, expected under uniform routing: each token leaves a given
    expert out of its top-k with probability (E - k) / E."""
    s = _shapes(cfg)
    e, k = s["router"], s["top_k"]
    return s["held"] * (1.0 - ((e - k) / e) ** rows)


def body_work(cfg: dict, n_steps: int, contexts: Sequence[int]) -> Work:
    """The body of ``n_steps`` fused decode steps over rows whose contexts
    are ``contexts`` (``bench.work.decode_steps``).

    Operations: every layer's attention, router and shared expert once
    per row; each row's top-k assignments that fall on held experts
    (k * held / E of them, expected under uniform routing) through an
    expert's SwiGLU; attention scores and values over the row's context.
    Bytes: per step, the bf16 attention, router and shared-expert weights
    and the norms, and the held experts expected to be active for the
    step's mean rows (``expected_active``); per row its embedding, its
    cached KV read and its new position written (together its
    context).

    The expected active experts are an upper estimate: with the balanced
    routers of ``make_params``, 32 rows a step kept 10.6-11.0 held
    experts of a layer active on a TPU v5e against 13.3 expected, as the
    routing counters (``expert_work``) read."""
    s = _shapes(cfg)
    rows = len(contexts)
    n_l, d, nq = s["n_l"], s["d"], s["nq"]
    ew = _expert_weights(cfg)
    ctx = float(sum(contexts))
    held_share = s["top_k"] * s["held"] / s["router"]
    flops = (2.0 * (_dense_weights(cfg) + held_share * ew) * n_l * rows
             + 4.0 * nq * n_l * ctx)
    active = expected_active(cfg, rows / n_steps) if n_steps else 0.0
    norms = (2 * n_l + 1) * d * FP32
    nbytes = (n_steps * ((_dense_weights(cfg) + active * ew) * n_l * BF16
                         + norms)
              + rows * d * BF16 + kv_bytes_per_position(cfg) * ctx)
    return Work(flops, nbytes)


def expert_work(cfg: dict, assignments: float, active: float) -> Work:
    """The grouped expert products (``moe_gmm``: gate, up, down) for
    ``assignments`` (token, held expert) pairs over ``active`` held
    experts that received at least one, summed over layers as the
    program's routing counters count them.

    Operations: 2 * 3 * d * f per assignment.  Bytes: each active
    expert's three bf16 matrices once; per assignment its activations as
    the products move them: the bf16 row read by gate and up (2 * 2d),
    their fp32 outputs (2 * 4f), the bf16 hidden read by down (2f) and
    its fp32 output (4d)."""
    s = _shapes(cfg)
    d, f = s["d"], s["f"]
    return Work(2.0 * 3 * d * f * assignments,
                active * _expert_weights(cfg) * BF16
                + assignments * (2 * 2 * d + 2 * 4 * f + 2 * f + 4 * d))
