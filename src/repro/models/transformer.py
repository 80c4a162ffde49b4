"""Decoder-only LM covering the five assigned architectures.

One config class spans dense (qwen2-0.5b/7b, qwen3-4b) and MoE
(qwen2-moe-a2.7b: shared+routed top-4; arctic-480b: dense-residual ∥ 128e
top-2).  Layers are scanned (`jax.lax.scan`) so HLO size and compile time
are O(1) in depth, and remat policy applies per-layer.

Entry points:
  * ``lm_loss(params, batch, cfg)``     — training loss (blockwise attn).
  * ``prefill(params, tokens, cfg, max_len)`` — build a KV cache.
  * ``decode_step(params, token, cache, cfg)`` — one token; returns the
    final-norm hidden state so the serving engine can apply either the
    full vocab head or the LSS head (the paper's technique).
  * ``decode_step_pooled(params, token, k, v, lengths, cfg)`` — one token
    per POOL SLOT with per-row cache lengths (continuous batching; see
    ``repro.serve.decode``).
  * ``param_specs(cfg)`` / ``cache_specs(cfg, policy)`` — PartitionSpecs.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P  # noqa: F401

from repro.models import layers as L
from repro.models.moe import (MoEConfig, init_moe_params, moe_ffn,
                              moe_ffn_serving)
from repro.utils.sharding import maybe_shard, mesh_axis_size


class TransformerConfig(NamedTuple):
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_base: float = 1e6
    tie_embeddings: bool = False
    # MoE: style "none" | "replace" (FFN -> MoE) | "parallel" (dense + MoE)
    moe_style: str = "none"
    n_experts: int = 0
    n_experts_padded: int = 0
    moe_top_k: int = 0
    moe_d_ff: int = 0
    shared_expert_ff: int = 0     # qwen2-moe shared expert hidden size
    capacity_factor: float = 1.25
    moe_norm_topk: bool = True    # renormalise the top-k routing weights
    # the routed experts a layer holds, as one chip of an expert-parallel
    # group: moe_held experts from moe_held_offset (0 = all of them)
    moe_held: int = 0
    moe_held_offset: int = 0
    # FSDP-shard expert d_ff over 'data' — required only when expert
    # params exceed what the model axis alone can hold (arctic-480b).
    # Costs a per-layer weight all-gather; see EXPERIMENTS.md §Perf.
    moe_fsdp: bool = False
    moe_groups: int = 1           # GShard dispatch groups (= data shards)
    dtype: Any = jnp.bfloat16
    remat: bool = True
    kv_chunk: int = 512
    q_chunk: int = 2048    # long-prefill query chunking
    # "scan": O(1) HLO in depth (production). "unroll": Python loop —
    # used by the dry-run because XLA cost_analysis counts a scan body
    # ONCE (trip count ignored), which would poison the roofline.
    layers_impl: str = "scan"

    @property
    def moe_cfg(self) -> MoEConfig | None:
        if self.moe_style == "none":
            return None
        return MoEConfig(self.n_experts, self.moe_top_k, self.d_model,
                         self.moe_d_ff, self.n_experts_padded,
                         self.capacity_factor, n_groups=self.moe_groups,
                         norm_topk=self.moe_norm_topk, n_held=self.moe_held,
                         held_offset=self.moe_held_offset)

    def param_count(self) -> int:
        """Analytic parameter count (for MODEL_FLOPS cross-checks)."""
        d, f = self.d_model, self.d_ff
        nq = self.n_heads * self.head_dim
        nkv = self.n_kv_heads * self.head_dim
        attn = d * nq + 2 * d * nkv + nq * d
        if self.qkv_bias:
            attn += nq + 2 * nkv
        dense_ffn = 3 * d * f if self.moe_style in ("none", "parallel") else 0
        moe = 0
        if self.moe_style != "none":
            moe = self.n_experts * 3 * d * self.moe_d_ff + d * self.n_experts
        shared = 3 * d * self.shared_expert_ff + d if self.shared_expert_ff else 0
        if self.moe_style == "replace":
            dense_ffn = 0
        per_layer = attn + dense_ffn + moe + shared + 2 * d
        head = 0 if self.tie_embeddings else self.vocab * d
        return self.n_layers * per_layer + self.vocab * d + head + d

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if self.moe_style == "none":
            return self.param_count()
        full = self.param_count()
        inactive = (self.n_experts - self.moe_top_k) * 3 * self.d_model \
            * self.moe_d_ff * self.n_layers
        return full - inactive


# ------------------------------------------------------------------ init --

def init_params(key: jax.Array, cfg: TransformerConfig) -> dict:
    dt = cfg.dtype
    d, f = cfg.d_model, cfg.d_ff
    nq = cfg.n_heads * cfg.head_dim
    nkv = cfg.n_kv_heads * cfg.head_dim
    keys = jax.random.split(key, 16)
    s = d ** -0.5

    def nrm(k, shape, scale):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dt)

    lyr = {
        "ln1": jnp.ones((cfg.n_layers, d), jnp.float32),
        "ln2": jnp.ones((cfg.n_layers, d), jnp.float32),
        "wq": nrm(keys[0], (cfg.n_layers, d, nq), s),
        "wk": nrm(keys[1], (cfg.n_layers, d, nkv), s),
        "wv": nrm(keys[2], (cfg.n_layers, d, nkv), s),
        "wo": nrm(keys[3], (cfg.n_layers, nq, d), nq ** -0.5),
    }
    if cfg.qkv_bias:
        lyr["bq"] = jnp.zeros((cfg.n_layers, nq), dt)
        lyr["bk"] = jnp.zeros((cfg.n_layers, nkv), dt)
        lyr["bv"] = jnp.zeros((cfg.n_layers, nkv), dt)
    if cfg.qk_norm:
        lyr["q_norm"] = jnp.ones((cfg.n_layers, cfg.head_dim), jnp.float32)
        lyr["k_norm"] = jnp.ones((cfg.n_layers, cfg.head_dim), jnp.float32)
    if cfg.moe_style in ("none", "parallel"):
        lyr["w_gate"] = nrm(keys[4], (cfg.n_layers, d, f), s)
        lyr["w_up"] = nrm(keys[5], (cfg.n_layers, d, f), s)
        lyr["w_down"] = nrm(keys[6], (cfg.n_layers, f, d), f ** -0.5)
    if cfg.moe_style != "none":
        moe_keys = jax.random.split(keys[7], cfg.n_layers)
        stacked = jax.vmap(lambda k: init_moe_params(k, cfg.moe_cfg, dt))(
            moe_keys)
        lyr["moe"] = stacked
    if cfg.shared_expert_ff:
        sf = cfg.shared_expert_ff
        lyr["sh_gate"] = nrm(keys[8], (cfg.n_layers, d, sf), s)
        lyr["sh_up"] = nrm(keys[9], (cfg.n_layers, d, sf), s)
        lyr["sh_down"] = nrm(keys[10], (cfg.n_layers, sf, d), sf ** -0.5)
        lyr["sh_gate_w"] = nrm(keys[11], (cfg.n_layers, d, 1), s)

    params = {
        "embed": nrm(keys[12], (cfg.vocab, d), 1.0),
        "layers": lyr,
        "final_norm": jnp.ones((d,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = nrm(keys[13], (cfg.vocab, d), s)
    return params


def param_specs(cfg: TransformerConfig) -> dict:
    """NamedSharding PartitionSpecs (mesh axes: data, model [, pod]).

    Conventions: vocab & d_ff & experts shard over ``model``; the large
    MoE expert tensors additionally FSDP-shard d_ff over ``data`` (arctic
    would not fit otherwise); attention heads shard over ``model`` (GSPMD
    pads non-divisible head counts — waste is reported by the roofline).
    """
    lyr = {
        "ln1": P(None, None), "ln2": P(None, None),
        "wq": P(None, None, "model"),
        "wk": P(None, None, "model"),
        "wv": P(None, None, "model"),
        "wo": P(None, "model", None),
    }
    if cfg.qkv_bias:
        lyr["bq"] = P(None, "model")
        lyr["bk"] = P(None, "model")
        lyr["bv"] = P(None, "model")
    if cfg.qk_norm:
        lyr["q_norm"] = P(None, None)
        lyr["k_norm"] = P(None, None)
    if cfg.moe_style in ("none", "parallel"):
        lyr["w_gate"] = P(None, None, "model")
        lyr["w_up"] = P(None, None, "model")
        lyr["w_down"] = P(None, "model", None)
    if cfg.moe_style != "none":
        fs = "data" if cfg.moe_fsdp else None
        # held experts are this chip's own share: nothing left to split
        ex = None if cfg.moe_held else "model"
        lyr["moe"] = {
            "router": P(None, None, None),
            "w_gate": P(None, ex, None, fs),
            "w_up": P(None, ex, None, fs),
            "w_down": P(None, ex, fs, None),
        }
    if cfg.shared_expert_ff:
        lyr["sh_gate"] = P(None, None, "model")
        lyr["sh_up"] = P(None, None, "model")
        lyr["sh_down"] = P(None, "model", None)
        lyr["sh_gate_w"] = P(None, None, None)
    specs = {
        "embed": P("model", None),
        "layers": lyr,
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P("model", None)
    return specs


# -------------------------------------------------------------- forward ---

def _attn_block(x, lp, cfg: TransformerConfig, positions, mode,
                cache=None, kv_len=None):
    """Shared attention block. mode: train | prefill | decode."""
    b, s, d = x.shape
    h = L.rms_norm(x, lp["ln1"])
    q = jnp.einsum("bsd,dn->bsn", h, lp["wq"])
    k = jnp.einsum("bsd,dn->bsn", h, lp["wk"])
    v = jnp.einsum("bsd,dn->bsn", h, lp["wv"])
    if cfg.qkv_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim)
    if mode == "decode":
        # Decode moves ONE token: per-token activations are KBs while the
        # packed [*, n*h] -> [*, n, h] reshape straddles the model-axis
        # shard boundary when heads don't divide TP (qwen2-7b: 224
        # cols/shard vs h=128), triggering GSPMD "involuntary full
        # rematerialization" (26 GB/dev of gathers at decode_32k).
        # Replicating the tiny q/k/v fixes that; but when heads DO divide
        # TP (qwen2-moe: 16H/16KV) head-sharded attention is already
        # optimal and forcing replication regresses 1.4x — so the
        # constraint is alignment-conditional.  §Perf hillclimb 2.
        tp = mesh_axis_size("model")
        if tp and (cfg.n_heads % tp or cfg.n_kv_heads % tp):
            q = maybe_shard(q, P("data", None, None, None))
            k = maybe_shard(k, P("data", None, None, None))
            v = maybe_shard(v, P("data", None, None, None))
    if cfg.qk_norm:
        q = L.rms_norm(q, lp["q_norm"])
        k = L.rms_norm(k, lp["k_norm"])
    q = L.apply_rope(q, positions, cfg.rope_base)
    k = L.apply_rope(k, positions, cfg.rope_base)

    if mode == "decode":
        pos = kv_len - 1                           # write slot (traced)
        k_cache = _write_cache(cache[0], k, pos)
        v_cache = _write_cache(cache[1], v, pos)
        out = L.attention_decode(q, k_cache, v_cache, kv_len)
        new_cache = (k_cache, v_cache)
    else:
        out = L.attention_blockwise(q, k, v, causal=True,
                                    kv_chunk=cfg.kv_chunk,
                                    q_chunk=cfg.q_chunk)
        new_cache = (k, v) if mode == "prefill" else None
    out = out.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return x + jnp.einsum("bsn,nd->bsd", out, lp["wo"]), new_cache


def _write_cache(cache: jax.Array, kv: jax.Array, pos: jax.Array) -> jax.Array:
    """Write the [B, 1, KV, H] step into cache[:, pos] (traced pos, scalar
    or [B] for per-row write positions under continuous batching)."""
    pos = jnp.asarray(pos)
    if pos.ndim == 0:
        onehot = (jnp.arange(cache.shape[1]) == pos)[None, :, None, None]
    else:
        onehot = (jnp.arange(cache.shape[1])[None, :]
                  == pos[:, None])[:, :, None, None]
    return jnp.where(onehot, kv.astype(cache.dtype), cache)


def _ffn_block(x, lp, cfg: TransformerConfig, experts=None):
    """-> (x, aux loss, routed).  ``experts``: (every layer's expert
    stacks, this layer's index) for the drop-free dispatch to read in
    place, whose ``routed`` counts the held-expert assignments and active
    held experts; or None for the capacity dispatch (``_expert_stacks``
    chooses)."""
    b, s, d = x.shape
    h = L.rms_norm(x, lp["ln2"])
    aux = jnp.zeros((), jnp.float32)
    routed = jnp.zeros((2,), jnp.int32)
    out = jnp.zeros_like(h)
    if cfg.moe_style in ("none", "parallel"):
        out = out + L.swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
    if cfg.moe_style != "none":
        flat = h.reshape(b * s, d)
        if experts is None:
            moe_out, aux = moe_ffn(flat, lp["moe"], cfg.moe_cfg)
        else:
            stacks, layer = experts
            moe_out, routed = moe_ffn_serving(
                flat, dict(lp["moe"], **stacks), cfg.moe_cfg, layer=layer)
        out = out + moe_out.reshape(b, s, d)
    if cfg.shared_expert_ff:
        gate = jax.nn.sigmoid(
            jnp.einsum("bsd,dz->bsz", h, lp["sh_gate_w"]).astype(jnp.float32))
        sh = L.swiglu(h, lp["sh_gate"], lp["sh_up"], lp["sh_down"])
        out = out + (sh * gate.astype(sh.dtype))
    return x + out, aux, routed


def _layer(x, lp, cfg, positions, mode, cache=None, kv_len=None,
           experts=None):
    """-> (x, new cache, aux loss, routed) (``_ffn_block``)."""
    x, new_cache = _attn_block(x, lp, cfg, positions, mode, cache, kv_len)
    x, aux, routed = _ffn_block(x, lp, cfg, experts)
    return x, new_cache, aux, routed


_STACKS = ("w_gate", "w_up", "w_down")


def _expert_stacks(layers: dict, cfg: TransformerConfig, mode):
    """(layer params to slice per layer, expert stacks or None).

    Prefill and decode dispatch drop-free, and the routed experts'
    stacks stay whole: the grouped products read a layer's experts at
    its index, so the layer loop does not copy each layer's experts out
    of the stacked weights.  Training, and stacks that ``param_specs``
    splits over a 'model' mesh axis (every expert held), keep the
    capacity dispatch (None): GSPMD cannot partition the grouped-product
    kernel's custom call."""
    split = not cfg.moe_held and (mesh_axis_size("model") or 1) > 1
    if cfg.moe_style == "none" or mode == "train" or split:
        return layers, None
    moe = layers["moe"]
    rest = {k: v for k, v in moe.items() if k not in _STACKS}
    return dict(layers, moe=rest), {k: moe[k] for k in _STACKS}


def _scan_layers(params, x, cfg: TransformerConfig, positions, mode):
    """Run the layer stack (train/prefill). Returns (x, caches, aux)."""
    fn = _layer
    if cfg.remat and mode == "train":
        fn = jax.checkpoint(_layer, static_argnums=(2, 4))
    layers, stacks = _expert_stacks(params["layers"], cfg, mode)

    def experts(i):
        return None if stacks is None else (stacks, i)

    if cfg.layers_impl == "unroll":
        aux = jnp.zeros((), jnp.float32)
        caches = []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], layers)
            x, cache_i, aux_i, _ = fn(x, lp, cfg, positions, mode,
                                      experts=experts(i))
            aux = aux + aux_i
            caches.append(cache_i)
        if mode == "prefill":
            caches = jax.tree.map(lambda *xs: jnp.stack(xs), *caches)
        else:
            caches = None
        return x, caches, aux

    def body(carry, xs):
        h, aux_tot = carry
        lp, i = xs
        h, new_cache, aux, _ = fn(h, lp, cfg, positions, mode,
                                  experts=experts(i))
        return (h, aux_tot + aux), new_cache

    (x, aux), caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                    (layers, jnp.arange(cfg.n_layers)))
    return x, caches, aux


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            mode: str = "train"):
    """tokens [B, S] -> (hidden [B, S, D] after final norm, caches, aux)."""
    x = params["embed"][tokens].astype(cfg.dtype)
    positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    x = maybe_shard(x, P("data", None, None))
    x, caches, aux = _scan_layers(params, x, cfg, positions, mode)
    return L.rms_norm(x, params["final_norm"]), caches, aux


def logits_head(params: dict, hidden: jax.Array,
                cfg: TransformerConfig) -> jax.Array:
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,vd->bsv", hidden, head).astype(jnp.float32)


def gold_logit(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """label-logit extraction that stays sharded.

    ``take_along_axis`` over a vocab-sharded axis makes GSPMD all-gather
    the full [B, S, V] logits (measured: 33 GB/device on qwen2-0.5b).
    The iota-mask sum partitions cleanly: each shard contributes its local
    slice, combined by one tiny [B, S] all-reduce.
    """
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    sel = jnp.where(iota == labels[..., None], logits, 0)
    return sel.sum(-1)


def lm_loss(params: dict, batch: dict, cfg: TransformerConfig) -> jax.Array:
    """batch: tokens [B, S] int32, labels [B, S] (-100 = masked)."""
    hidden, _, aux = forward(params, batch["tokens"], cfg, mode="train")
    logits = logits_head(params, hidden, cfg)
    labels = batch["labels"]
    mask = labels >= 0
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = gold_logit(logits, jnp.maximum(labels, 0))
    nll = (logz - gold) * mask
    loss = nll.sum() / jnp.maximum(mask.sum(), 1)
    return loss + 0.01 * aux


# ---------------------------------------------------------------- serving --

class KVCache(NamedTuple):
    k: jax.Array       # [n_layers, B, S_max, KV, H]
    v: jax.Array
    length: jax.Array  # int32 [] — valid prefix length


def init_cache(cfg: TransformerConfig, batch: int, max_len: int,
               dtype=None) -> KVCache:
    dt = dtype or cfg.dtype
    shape = (cfg.n_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                   jnp.zeros((), jnp.int32))


def cache_specs(cfg: TransformerConfig, batch: int) -> KVCache:
    """Sharding policy: batch over data when it divides, else the sequence
    axis takes both mesh axes (long-context batch=1 decode)."""
    if batch >= 16:
        spec = P(None, "data", "model", None, None)
    else:
        spec = P(None, None, ("data", "model"), None, None)
    return KVCache(spec, spec, P())


def prefill(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            max_len: int) -> tuple[jax.Array, KVCache]:
    """Run the prompt; returns (final-norm hidden [B, S, D], cache)."""
    hidden, caches, _ = forward(params, tokens, cfg, mode="prefill")
    k, v = caches                                    # [L, B, S, KV, H]
    pad = max_len - tokens.shape[1]
    if pad > 0:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    return hidden, KVCache(k.astype(cfg.dtype), v.astype(cfg.dtype),
                           jnp.asarray(tokens.shape[1], jnp.int32))


def _decode_layers(params: dict, token: jax.Array, k: jax.Array,
                   v: jax.Array, positions: jax.Array, kv_len,
                   cfg: TransformerConfig):
    """Shared one-token layer loop.  token [B], k/v [L, B, S, KV, H],
    positions [B, 1], kv_len scalar or [B] -> (hidden [B, D], k_new,
    v_new, routed).  Every op is row-parallel over B.  ``routed``: for
    a MoE model int32 [2], the step's held-expert assignments and active
    held experts summed over layers; None for a dense one."""
    x = params["embed"][token[:, None]].astype(cfg.dtype)   # [B, 1, D]
    routed = jnp.zeros((2,), jnp.int32)
    layers, stacks = _expert_stacks(params["layers"], cfg, "decode")

    def experts(i):
        return None if stacks is None else (stacks, i)

    if cfg.layers_impl == "unroll":
        ks, vs = [], []
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], layers)
            x, (k_i, v_i), _, r = _layer(x, lp, cfg, positions, "decode",
                                         (k[i], v[i]), kv_len, experts(i))
            routed = routed + r
            ks.append(k_i)
            vs.append(v_i)
        k_new, v_new = jnp.stack(ks), jnp.stack(vs)
    else:
        def body(carry, xs):
            h, tot = carry
            lp, kc, vc, i = xs
            h, new_cache, _, r = _layer(h, lp, cfg, positions, "decode",
                                        (kc, vc), kv_len, experts(i))
            return (h, tot + r), new_cache

        (x, routed), (k_new, v_new) = jax.lax.scan(
            body, (x, routed),
            (layers, k, v, jnp.arange(cfg.n_layers)))
    hidden = L.rms_norm(x[:, 0], params["final_norm"])
    return (hidden, k_new, v_new,
            None if cfg.moe_style == "none" else routed)


def decode_step(params: dict, token: jax.Array, cache: KVCache,
                cfg: TransformerConfig) -> tuple[jax.Array, KVCache]:
    """One decode step. token [B] int32 -> (hidden [B, D], new cache).

    The caller applies the head: ``logits_head`` for exact serving or the
    LSS index (repro.core) for sub-linear WOL serving.
    """
    b = token.shape[0]
    kv_len = cache.length + 1
    positions = jnp.full((b, 1), cache.length, jnp.int32)
    hidden, k_new, v_new, _ = _decode_layers(params, token, cache.k,
                                             cache.v, positions, kv_len, cfg)
    return hidden, KVCache(k_new, v_new, kv_len)


def decode_step_pooled(params: dict, token: jax.Array, k: jax.Array,
                       v: jax.Array, lengths: jax.Array,
                       cfg: TransformerConfig):
    """One decode step over a slot pool with PER-ROW cache lengths.

    The continuous-batching counterpart of :func:`decode_step`: rows are
    independent streams at different depths, so each row reads its own
    valid prefix and writes its new KV at its own position.  token [B]
    int32, k/v [L, B, S_max, KV, H] slabs, lengths [B] int32 (current
    valid prefix per slot) -> (hidden [B, D], k_new, v_new, routed), the
    step's routing counts as :func:`_decode_layers` gives them.

    Row ``i`` computes exactly what :func:`decode_step` computes for a
    batch-1 cache of the same width ``S_max`` — every op is row-parallel —
    which is what makes interleaved decode token-exact with a blocking
    per-stream loop (asserted in tests/test_decode_stream.py).
    """
    return _decode_layers(params, token, k, v,
                          lengths[:, None].astype(jnp.int32),  # positions
                          lengths + 1, cfg)


def decode_step_paged(params: dict, token: jax.Array, k_arena: jax.Array,
                      v_arena: jax.Array, page_table: jax.Array,
                      lengths: jax.Array, cfg: TransformerConfig,
                      max_len: int):
    """:func:`decode_step_pooled` over PAGED KV storage.

    token [B] int32, k/v arenas [L, n_pages, page_tokens, KV, H],
    page_table [B, pages_per_slot] int32 (0 = unmapped -> the reserved
    scratch page), lengths [B] int32 -> (hidden [B, D], k_arena, v_arena,
    routed).

    Bit-identity with the dense layout is by construction: each row's
    pages are gathered IN ORDER into a contiguous view sliced to exactly
    ``max_len`` — the same ``[L, B, max_len, KV, H]`` operand shape the
    dense slabs present — so :func:`_decode_layers` runs the very same
    program over the very same valid contents (positions >= lengths are
    masked to exact zeros inside ``attention_decode`` either way; what
    garbage sits there — arena zeros vs stale rows — cannot matter
    because everything the model ever writes is finite).  Slicing to
    ``max_len`` (not ``pages_per_slot * page_tokens``) is load-bearing:
    XLA:CPU reductions are not shape-invariant at the ulp level, so the
    view width must equal the dense width exactly.

    Memory caveat: that gather materializes a contiguous
    ``[L, B, max_len, KV, H]`` view per step — the size of the full
    dense slab — unless the backend fuses it into attention, so the
    paged layout's savings are in PERSISTENT arena bytes (what bounds
    how many sessions a device can hold between steps), while the
    per-step transient peak can match the dense layout's.  The
    ``BENCH_decode.json`` capacity rows count persistent bytes only;
    see docs/ARCHITECTURE.md "Paged KV decode" for the trade-off.

    The new KV row is scattered back into each row's current write page
    (page ``lengths // p``, offset ``lengths % p``).  Rows that must not
    write — parked slots and rows at ``lengths == max_len`` (where the
    dense one-hot write falls off the end of the slab) — are redirected
    to scratch page 0, so a freed slot's in-flight step can never
    corrupt a recycled page.  All shapes are static: joins, leaves, and
    page-table churn cost zero recompiles.
    """
    n_l, _, p, n_kv, h_dim = k_arena.shape
    b, n_pp = page_table.shape
    w = max_len

    def view(arena):
        return arena[:, page_table].reshape(n_l, b, n_pp * p,
                                            n_kv, h_dim)[:, :, :w]

    hidden, k_new, v_new, routed = _decode_layers(
        params, token, view(k_arena), view(v_arena),
        lengths[:, None].astype(jnp.int32), lengths + 1, cfg)

    rows = jnp.arange(b)
    wpos = jnp.clip(lengths, 0, w - 1)
    pidx = jnp.clip(lengths // p, 0, n_pp - 1)
    dest = jnp.take_along_axis(page_table, pidx[:, None], axis=1)[:, 0]
    dest = jnp.where(lengths < w, dest, 0)          # full rows -> scratch
    off = jnp.where(lengths < w, lengths % p, 0)
    k_arena = k_arena.at[:, dest, off].set(
        k_new[:, rows, wpos].astype(k_arena.dtype))
    v_arena = v_arena.at[:, dest, off].set(
        v_new[:, rows, wpos].astype(v_arena.dtype))
    return hidden, k_arena, v_arena, routed
