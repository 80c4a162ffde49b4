"""Serving a qwen2-moe model: drop-free routing over the experts a layer
holds, checked against a plain fp32 reference on seeded random weights.

The reference below is written from the published description (HF
``Qwen2MoeAttention`` and ``Qwen2MoeSparseMoeBlock``): RMSNorm, rotary
embeddings on the two halves of each head, causal attention with QKV
biases, a softmax over every router output, top-k, renormalised only
where the config says, each held expert's SwiGLU weighted by its routing
weight, plus the shared expert scaled by ``sigmoid(h @ w_sg)``.  Experts
held elsewhere add nothing, in the program and in the reference alike.

Tolerances.  The program runs in fp32 here, so it differs from the
reference only in the order of fp32 sums (blockwise against whole-row
attention, grouped against per-expert products): ~1e-6 of a value's
scale.  Each test allows 1e-4 of its output's scale and shows that the
same computation with its products' operands rounded to bf16, the next
precision down, lies outside it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.moe_gmm import moe_gmm
from repro.models import transformer as T
from repro.models.moe import (MoEConfig, init_moe_params, moe_ffn,
                              moe_ffn_serving, router_topk)

HI = jax.lax.Precision.HIGHEST
# fp32 sums in another order: ~1e-6 of the scale; bf16 operands: ~4e-3
REL_TOL = 1e-4

TINY = T.TransformerConfig(
    name="tiny-qwen2-moe", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    head_dim=16, d_ff=96, vocab=256, qkv_bias=True, rope_base=1e6,
    tie_embeddings=False, moe_style="replace", n_experts=8,
    n_experts_padded=8, moe_top_k=2, moe_d_ff=32, shared_expert_ff=96,
    moe_norm_topk=False, moe_held=4, moe_held_offset=2, dtype=jnp.float32,
    kv_chunk=16, q_chunk=32)


def _params(cfg: T.TransformerConfig, seed: int = 0) -> dict:
    """The program's init, with random biases and norm scales (so each
    changes the result) and a decisive router (logits of std ~3)."""
    p = T.init_params(jax.random.PRNGKey(seed), cfg)
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    lyr = dict(p["layers"])
    for b in ("bq", "bk", "bv"):
        lyr[b] = 0.2 * jax.random.normal(next(ks), lyr[b].shape)
    for n in ("ln1", "ln2"):
        lyr[n] = 1 + 0.1 * jax.random.normal(next(ks), lyr[n].shape)
    moe = dict(lyr["moe"])
    moe["router"] = moe["router"] * 3.0
    moe["w_down"] = moe["w_down"] * 3.0         # the routed part matters
    lyr["moe"] = moe
    p["layers"] = lyr
    p["final_norm"] = 1 + 0.1 * jax.random.normal(next(ks), (cfg.d_model,))
    return p


def _round(x, bf16: bool):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if bf16 else x


def _mm(a, b, bf16=False):
    return jnp.matmul(_round(a, bf16), _round(b, bf16), precision=HI)


def _rms(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + rot * sin


def ref_moe(h, moe, cfg, *, offset, n_held, bf16=False):
    """The sparse block's routed part over the held experts: every held
    expert computed on every token and weighted by its routing weight
    (zero where it is not among the token's top-k)."""
    probs = jax.nn.softmax(_mm(h, moe["router"], bf16), -1)
    top_p, top_e = jax.lax.top_k(probs, cfg.moe_top_k)
    if cfg.moe_norm_topk:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for j in range(n_held):
        w = jnp.sum(jnp.where(top_e == offset + j, top_p, 0.0), -1)
        y = _mm(jax.nn.silu(_mm(h, moe["w_gate"][j], bf16))
                * _mm(h, moe["w_up"][j], bf16), moe["w_down"][j], bf16)
        out = out + w[:, None] * y
    return out


def ref_shared(h, lp, bf16=False):
    gate = jax.nn.sigmoid(_mm(h, lp["sh_gate_w"], bf16))
    y = _mm(jax.nn.silu(_mm(h, lp["sh_gate"], bf16))
            * _mm(h, lp["sh_up"], bf16), lp["sh_down"], bf16)
    return gate * y


def ref_logits(params, cfg, tokens, bf16=False):
    """Logits ``[S, V]`` of one full causal forward pass, no cache."""
    s = len(tokens)
    x = params["embed"][jnp.asarray(tokens)]
    nh, hd = cfg.n_heads, cfg.head_dim
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(cfg.n_layers):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = _rms(x, lp["ln1"])
        q = _rope((_mm(h, lp["wq"], bf16) + lp["bq"]).reshape(s, nh, hd),
                  cfg.rope_base)
        k = _rope((_mm(h, lp["wk"], bf16) + lp["bk"]).reshape(s, nh, hd),
                  cfg.rope_base)
        v = (_mm(h, lp["wv"], bf16) + lp["bv"]).reshape(s, nh, hd)
        heads = []
        for j in range(nh):
            sc = _mm(q[:, j], k[:, j].T, bf16) / np.sqrt(hd)
            sc = jnp.where(causal, sc, -jnp.inf)
            heads.append(_mm(jax.nn.softmax(sc, -1), v[:, j], bf16))
        x = x + _mm(jnp.concatenate(heads, -1), lp["wo"], bf16)
        h = _rms(x, lp["ln2"])
        x = x + ref_moe(h, lp["moe"], cfg, offset=cfg.moe_held_offset,
                        n_held=cfg.moe_held, bf16=bf16) + ref_shared(h, lp,
                                                                     bf16)
    return _mm(_rms(x, params["final_norm"]), params["lm_head"].T, bf16)


def _rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


# ------------------------------------------------------------ the model --

@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_prefill_then_decode_matches_reference_logits(impl):
    from repro.kernels import registry
    cfg, params = TINY, _params(TINY)
    rng = np.random.default_rng(0)
    plen, n_dec, max_len = 11, 6, 32
    seq = rng.integers(0, cfg.vocab, plen + n_dec).astype(np.int32)
    with registry.use_impl(impl):
        hidden, cache = T.prefill(params, jnp.asarray(seq[None, :plen]),
                                  cfg, max_len)
        hs = [hidden[0, plen - 1]]
        k, v = cache.k, cache.v
        for i in range(n_dec - 1):
            lengths = jnp.asarray([plen + i], jnp.int32)
            h, k, v, routed = T.decode_step_pooled(
                params, jnp.asarray(seq[plen + i:plen + i + 1]), k, v,
                lengths, cfg)
            assert routed.shape == (2,)
            hs.append(h[0])
    served = jnp.stack(hs) @ params["lm_head"].T            # [n_dec, V]
    ref = ref_logits(params, cfg, seq)[plen - 1:plen - 1 + n_dec]
    assert _rel_err(served, ref) < REL_TOL
    low = ref_logits(params, cfg, seq, bf16=True)[plen - 1:plen - 1 + n_dec]
    assert _rel_err(low, ref) > REL_TOL              # bf16 would fail it


def test_routed_part_moves_the_logits():
    """A broken expert path cannot hide: with the held experts' outputs
    zeroed the logits move far beyond the tolerance."""
    cfg, params = TINY, _params(TINY)
    seq = np.arange(12, dtype=np.int32) * 7 % cfg.vocab
    ref = ref_logits(params, cfg, seq)
    cut = jax.tree.map(lambda a: a, params)
    cut["layers"]["moe"] = dict(cut["layers"]["moe"],
                                w_down=jnp.zeros_like(
                                    cut["layers"]["moe"]["w_down"]))
    assert _rel_err(ref_logits(params=cut, cfg=cfg, tokens=seq), ref) > 0.05


# ------------------------------------------------------------ dispatch --

def _moe_cfg(**kw) -> MoEConfig:
    base = dict(n_experts=8, top_k=2, d_model=32, d_ff=16,
                n_experts_padded=8, norm_topk=False)
    base.update(kw)
    return MoEConfig(**base)


def _serve(x, params, cfg):
    """The drop-free dispatch with ``params``' experts as a one-layer
    stack."""
    stacks = {w: params[w][None] for w in ("w_gate", "w_up", "w_down")}
    return moe_ffn_serving(x, dict(params, **stacks), cfg, 0)


@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
def test_a_rows_output_does_not_depend_on_its_batch_mates(impl):
    """Every row routes to expert 3 first: the capacity path drops most
    of them (capacity 8 of 64), the serving path gives each row what it
    gives the row alone."""
    from repro.kernels import registry
    cfg = _moe_cfg()
    params = init_moe_params(jax.random.PRNGKey(0), cfg)
    params["router"] = params["router"].at[:, 3].add(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (64, 32))) + 0.5
    with registry.use_impl(impl):
        batch, _ = _serve(x, params, cfg)
        alone = jnp.stack([_serve(x[i:i + 1], params, cfg)[0][0]
                           for i in (0, 31, 63)])
    top_e, _, _ = router_topk(x, params["router"], cfg)
    assert bool(jnp.all(top_e[:, 0] == 3))
    assert _rel_err(batch[jnp.asarray([0, 31, 63])], alone) < REL_TOL
    dropped, _ = moe_ffn(x, params, cfg._replace(capacity_factor=1.0))
    assert _rel_err(dropped[63:], batch[63:]) > 0.05  # the capacity path


@pytest.mark.parametrize("norm", [True, False])
def test_topk_weights_follow_the_config(norm):
    cfg = _moe_cfg(norm_topk=norm)
    params = init_moe_params(jax.random.PRNGKey(2), cfg)
    params["router"] = params["router"] * 3
    x = jax.random.normal(jax.random.PRNGKey(3), (16, 32))
    _, top_p, _ = router_topk(x, params["router"], cfg)
    probs = jax.nn.softmax(_mm(x, params["router"]), -1)
    want = jax.lax.top_k(probs, 2)[0]
    if norm:
        want = want / want.sum(-1, keepdims=True)
    assert _rel_err(top_p, want) < REL_TOL
    assert bool(jnp.all(jnp.abs(top_p.sum(-1) - 1) < 1e-5)) == norm
    out, _ = _serve(x, params, cfg)
    ref = ref_moe(x, params, TINY._replace(moe_top_k=2, moe_norm_topk=norm),
                  offset=0, n_held=8)
    assert _rel_err(out, ref) < REL_TOL
    low = ref_moe(x, params, TINY._replace(moe_top_k=2, moe_norm_topk=norm),
                  offset=0, n_held=8, bf16=True)
    assert _rel_err(low, ref) > REL_TOL


@pytest.mark.parametrize("shares", [((0, 2), (2, 2), (4, 2), (6, 2)),
                                    ((0, 3), (3, 3), (6, 2))])
def test_shares_add_up_to_the_uncut_layer(shares):
    """Over every chip's share of the routed experts, the routed parts,
    with the shared expert that each chip computes alike counted once,
    add up to the whole layer of the uncut reference."""
    full = TINY._replace(moe_held=0, moe_held_offset=0, n_layers=1)
    params = _params(full, seed=4)
    lp = jax.tree.map(lambda a: a[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64))
    h = _rms(x[0], lp["ln2"])
    whole = ref_moe(h, lp["moe"], full, offset=0, n_held=8) \
        + ref_shared(h, lp)
    total = jnp.zeros_like(h)
    for off, n in shares:
        cfg = full._replace(moe_held=n, moe_held_offset=off)
        mine = {w: lp["moe"][w][None, off:off + n]
                for w in ("w_gate", "w_up", "w_down")}
        y, _, routed = T._ffn_block(x, lp, cfg, (mine, 0))
        total = total + (y - x)[0]
        assert int(routed[0]) == int(jnp.sum(
            (router_topk(h, lp["moe"]["router"], cfg.moe_cfg)[0] >= off)
            & (router_topk(h, lp["moe"]["router"], cfg.moe_cfg)[0]
               < off + n)))
    total = total - (len(shares) - 1) * ref_shared(h, lp)
    assert _rel_err(total, whole) < REL_TOL


def test_decode_counters_count_every_assignment_when_all_are_held():
    """With every expert held, each fused step routes all of its rows'
    top-k slots in every layer to held experts."""
    from repro.core.lss import LSSConfig
    from repro.serve.engine import LMDecoder
    cfg = TINY._replace(moe_held=0, moe_held_offset=0, name="tiny-all-held")
    dec = LMDecoder(_params(cfg), cfg, LSSConfig(k_bits=3, n_tables=1),
                    max_streams=4, max_len=32)
    prompt = np.arange(16, dtype=np.int32).reshape(2, 8)
    dec.generate(prompt, steps=5, head="full")
    st = dec.scheduler(head="full").stats()
    per_step = 4 * cfg.moe_top_k * cfg.n_layers
    assert st.moe_assignments > 0
    assert st.moe_assignments == per_step * st.n_steps
    assert 0 < st.moe_active_experts <= 8 * cfg.n_layers * st.n_steps


# -------------------------------------------------------------- moe_gmm --

def _gmm_case(m, k, n, sizes, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.standard_normal((m, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.standard_normal((len(sizes), k, n)), jnp.bfloat16)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("m,k,n,sizes", [
    (40, 64, 32, [5, 0, 20, 3]),            # empty group, rows past total
    (40, 64, 32, [0, 0, 40, 0]),            # one group holds every row
    (40, 64, 32, [0, 0, 0, 0]),             # no row at all
    (300, 256, 384, [50, 0, 100, 0, 60, 10]),   # groups across m tiles
])
def test_moe_gmm_ref_equals_pallas_interpret(m, k, n, sizes):
    lhs, rhs, gs = _gmm_case(m, k, n, sizes)
    ref = moe_gmm(lhs, rhs[None], gs, layer=0, impl="ref")
    got = moe_gmm(lhs, rhs[None], gs, layer=0, impl="pallas_interpret")
    # a layer's matrices read in place from every layer's stack
    stack = jnp.stack([rhs * 0, rhs, rhs * 0])
    in_place = moe_gmm(lhs, stack, gs, layer=jnp.int32(1),
                       impl="pallas_interpret")
    assert bool(jnp.all(in_place == got))
    assert got.dtype == jnp.float32
    total = sum(sizes)
    assert bool(jnp.all(got[total:] == 0)) and bool(jnp.all(ref[total:] == 0))
    start = 0
    for g, size in enumerate(sizes):     # each row against its own group
        want = _mm(lhs[start:start + size].astype(jnp.float32),
                   rhs[g].astype(jnp.float32))
        if size:
            assert _rel_err(ref[start:start + size], want) < REL_TOL
        start += size
    if total:
        assert _rel_err(got, ref) < REL_TOL
        assert _rel_err(_round(ref, True), ref) > REL_TOL    # bf16 output
