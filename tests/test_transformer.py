"""Transformer: loss/grads finite, decode==forward, scan==unroll."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import registry
from repro.models import transformer as T


def _cfg(**kw):
    base = dict(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                head_dim=16, d_ff=128, vocab=256, dtype=jnp.float32,
                kv_chunk=16, q_chunk=64)
    base.update(kw)
    return T.TransformerConfig(**base)


@pytest.mark.parametrize("cfg", [
    _cfg(qkv_bias=True, qk_norm=True),
    _cfg(moe_style="replace", n_experts=4, n_experts_padded=4, moe_top_k=2,
         moe_d_ff=64, shared_expert_ff=96, capacity_factor=4.0),
    _cfg(moe_style="parallel", n_experts=4, n_experts_padded=4,
         moe_top_k=2, moe_d_ff=64, capacity_factor=4.0,
         tie_embeddings=True),
], ids=["dense", "moe-shared", "moe-parallel"])
def test_decode_matches_forward(cfg):
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    loss = T.lm_loss(params, batch, cfg)
    assert jnp.isfinite(loss)
    hidden, cache = T.prefill(params, tokens, cfg, max_len=24)
    nxt = jnp.argmax(T.logits_head(params, hidden[:, -1:], cfg)[:, 0], -1)
    h2, cache2 = T.decode_step(params, nxt, cache, cfg)
    toks2 = jnp.concatenate([tokens, nxt[:, None]], 1)
    hidden_full, _, _ = T.forward(params, toks2, cfg, mode="train")
    err = float(jnp.abs(hidden_full[:, -1] - h2).max())
    assert err < 1e-3, err
    assert int(cache2.length) == 18


def test_scan_equals_unroll():
    cfg = _cfg()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    l_scan = T.lm_loss(params, batch, cfg)
    l_unroll = T.lm_loss(params, batch, cfg._replace(layers_impl="unroll"))
    np.testing.assert_allclose(float(l_scan), float(l_unroll), rtol=1e-5)
    # decode paths too
    hidden, cache = T.prefill(params, tokens, cfg, max_len=20)
    tok = tokens[:, 0]
    h_s, _ = T.decode_step(params, tok, cache, cfg)
    h_u, _ = T.decode_step(params, tok, cache,
                           cfg._replace(layers_impl="unroll"))
    np.testing.assert_allclose(np.asarray(h_s), np.asarray(h_u),
                               rtol=1e-4, atol=1e-5)


def test_gold_logit_matches_take_along():
    logits = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 32))
    labels = jax.random.randint(jax.random.PRNGKey(1), (2, 5), 0, 32)
    want = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    got = T.gold_logit(logits, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_grads_flow_everywhere():
    cfg = _cfg(qkv_bias=True, qk_norm=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    g = jax.grad(lambda p: T.lm_loss(p, {"tokens": tokens,
                                         "labels": tokens}, cfg))(params)
    for path, leaf in jax.tree_util.tree_flatten_with_path(g)[0]:
        assert bool(jnp.isfinite(leaf).all()), path
        assert float(jnp.abs(leaf).sum()) > 0, path


def _whole_cache_write(k_cache, v_cache, k, v, layer, pos):
    """The write decode made before it wrote in place: a one-hot select of
    the step's rows over the layer's whole slabs."""
    pos = jnp.asarray(pos)
    s = k_cache.shape[2]
    if pos.ndim == 0:
        onehot = (jnp.arange(s) == pos)[None, :, None, None]
    else:
        onehot = (jnp.arange(s)[None, :] == pos[:, None])[:, :, None, None]
    k_slab = jnp.where(onehot, k.astype(k_cache.dtype), k_cache[layer])
    v_slab = jnp.where(onehot, v.astype(v_cache.dtype), v_cache[layer])
    return (k_cache.at[layer].set(k_slab), v_cache.at[layer].set(v_slab),
            k_slab, v_slab)


@pytest.mark.parametrize("layers_impl", ["scan", "unroll"])
@pytest.mark.parametrize("head_dim,form,impl", [
    (128, "rows", "ref"), (128, "rows", "pallas_interpret"),
    (8, "layer_select", "ref"), (8, "lanes", "pallas_interpret")])
def test_kv_write_in_place_matches_whole_cache_write(monkeypatch, head_dim,
                                                     form, impl,
                                                     layers_impl):
    """Each write form, as the cache's head width and the impl choose it,
    gives the hidden states and both caches, bit for bit, that the
    whole-cache select gives: per-row lengths with a parked
    slot (length 0) and a full one (length == max_len, which writes
    nothing), and the scalar-length step, mid-cache and full."""
    cfg = _cfg(head_dim=head_dim, qkv_bias=True, dtype=jnp.bfloat16,
               layers_impl=layers_impl)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    b, s = 4, 12
    shape = (cfg.n_layers, b, s, cfg.n_kv_heads, head_dim)
    k = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), shape, jnp.bfloat16)
    tok = jnp.array([3, 7, 11, 0], jnp.int32)
    lengths = jnp.array([5, s, s - 1, 0], jnp.int32)

    def run():
        pooled = jax.jit(lambda k, v: T.decode_step_pooled(
            params, tok, k, v, lengths, cfg)[:3])(k, v)
        scalar = [jax.jit(lambda k, v, n=n: T.decode_step(
            params, tok, T.KVCache(k, v, jnp.int32(n)), cfg))(k, v)
            for n in (5, s)]
        return jax.tree.map(np.asarray, (pooled, scalar))

    mark = registry.dispatch_count()
    with registry.use_impl(impl):
        got = run()
    log = registry.dispatch_log(mark)
    assert {c for op, c in log if op == "decode.kv_write"} == {form}
    # only the lanes form dispatches the kernel op
    assert {c for op, c in log if op == "kv_write"} == (
        {impl} if form == "lanes" else set())
    with monkeypatch.context() as m:
        m.setattr(T, "_write_cache", _whole_cache_write)
        want = run()
    full_k = got[1][1][1].k
    np.testing.assert_array_equal(full_k, np.asarray(k))   # wrote nothing
    jax.tree.map(np.testing.assert_array_equal, got, want)
