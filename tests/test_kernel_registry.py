"""Kernel registry dispatch + fused lss_topk parity.

The acceptance bar for the fused serving path: interpret-mode kernels are
BIT-IDENTICAL to the jnp refs (assert_array_equal, no tolerances), and an
Engine pinned to ``pallas_interpret`` serves end-to-end through the fused
op (proven by the registry dispatch log, not by construction).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simhash
from repro.core.lss import LSSConfig, build_index, lss_forward
from repro.kernels import bucket_logits, lss_topk, registry, simhash_codes
from repro.serve.engine import Engine


@pytest.fixture(autouse=True)
def _clean_registry():
    registry.set_default_impl(None)
    registry.reset_dispatch_log()
    yield
    registry.set_default_impl(None)


def _fitted_index(m, d, k, l, seed=0, bucket_major=True):
    w = jax.random.normal(jax.random.PRNGKey(seed), (m, d))
    cfg = LSSConfig(k_bits=k, n_tables=l, use_bucket_major=bucket_major)
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(seed + 1),
                                     d + 1, k, l)
    return build_index(w_aug, theta, cfg), w_aug


# ------------------------------------------------------------ registry --

def test_ops_registered_with_all_impls():
    for name in ("simhash_codes", "bucket_logits", "lss_topk", "moe_gmm",
                 "kv_write"):
        op = registry.get_op(name)
        assert set(op.impls) == {"ref", "pallas", "pallas_interpret"}, name


def test_auto_resolution_prefers_ref_off_tpu():
    assert jax.default_backend() != "tpu"   # CI is CPU
    for name in registry.list_ops():
        assert registry.resolve_impl(name) == "ref"


def test_explicit_impl_wins_over_global_override():
    with registry.use_impl("pallas_interpret"):
        assert registry.resolve_impl("lss_topk") == "pallas_interpret"
        assert registry.resolve_impl("lss_topk", "ref") == "ref"
    assert registry.resolve_impl("lss_topk") == "ref"


def test_env_override(monkeypatch):
    monkeypatch.setenv(registry.ENV_VAR, "pallas_interpret")
    assert registry.resolve_impl("bucket_logits") == "pallas_interpret"
    # global override beats the env var
    with registry.use_impl("ref"):
        assert registry.resolve_impl("bucket_logits") == "ref"
    monkeypatch.setenv(registry.ENV_VAR, "not_an_impl")
    with pytest.raises(ValueError):
        registry.resolve_impl("bucket_logits")


def test_unknown_impl_rejected():
    with pytest.raises(ValueError):
        registry.resolve_impl("lss_topk", "cuda")
    with pytest.raises(KeyError):
        registry.resolve_impl("definitely_not_an_op")
    with pytest.raises(ValueError):
        registry.set_default_impl("cuda")


def test_dispatch_log_records_op_and_impl():
    q = jax.random.normal(jax.random.PRNGKey(0), (4, 16))
    theta = jax.random.normal(jax.random.PRNGKey(1), (16, 6))
    registry.reset_dispatch_log()
    simhash_codes(q, theta, 3, 2, impl="ref")
    simhash_codes(q, theta, 3, 2, impl="pallas_interpret", block_b=4)
    assert registry.dispatch_log() == (
        ("simhash_codes", "ref"), ("simhash_codes", "pallas_interpret"))
    assert registry.last_dispatch("simhash_codes") == "pallas_interpret"
    assert registry.dispatch_counts()[("simhash_codes", "ref")] == 1
    # a mark taken before a call copies only what the call added
    assert registry.dispatch_count() == 2
    assert registry.dispatch_log(1) == (("simhash_codes", "pallas_interpret"),)
    assert registry.dispatch_log(registry.dispatch_count()) == ()


# ------------------------------------- sub-op bit-exact parity (edge d/P) --

@pytest.mark.parametrize("b,d,k,l", [
    (64, 128, 4, 1), (32, 129, 6, 3), (16, 31, 2, 4), (128, 897, 10, 1),
])
def test_simhash_codes_interpret_bit_exact(b, d, k, l):
    x = jax.random.normal(jax.random.PRNGKey(b + d), (b, d))
    theta = jax.random.normal(jax.random.PRNGKey(1), (d, k * l))
    ref = simhash_codes(x, theta, k, l, impl="ref")
    out = simhash_codes(x, theta, k, l, impl="pallas_interpret", block_b=16)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("b,d,s,p,l", [
    (16, 128, 32, 128, 1), (8, 100, 48, 96, 3), (4, 64, 8, 256, 2),
    (32, 897, 16, 24, 1), (8, 31, 12, 17, 2),
])
def test_bucket_logits_interpret_bit_exact(b, d, s, p, l):
    q = jax.random.normal(jax.random.PRNGKey(b * p), (b, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (s, p, d))
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, l), 0, s)
    ref = bucket_logits(q, w, ids, impl="ref")
    out = bucket_logits(q, w, ids, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


# -------------------------------------------- fused lss_topk bit-exact --

@pytest.mark.parametrize("m,d,k,l,b", [
    (200, 16, 3, 2, 32),      # small everything
    (150, 31, 4, 1, 16),      # d+1 = 32, single table
    (300, 63, 4, 3, 8),       # d not a lane multiple, 3-way dedup
    (64, 127, 5, 2, 4),       # d_aug = 128 exactly
    (500, 40, 6, 4, 64),      # deep K: empty buckets likely
])
def test_lss_topk_interpret_matches_ref_bit_exact(m, d, k, l, b):
    index, _ = _fitted_index(m, d, k, l, seed=m + d)
    q = jax.random.normal(jax.random.PRNGKey(m), (b, d))
    q_aug = simhash.augment_queries(q).astype(jnp.float32)
    t = index.tables
    ref = lss_topk(q_aug, index.theta, t.table_ids, index.w_bucketed,
                   top_k=5, impl="ref")
    out = lss_topk(q_aug, index.theta, t.table_ids, index.w_bucketed,
                   top_k=5, impl="pallas_interpret")
    for name, r, o in zip(("top_logits", "top_ids", "sample", "cand"),
                          ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=name)


@pytest.mark.parametrize("m,d,k,l", [(200, 16, 3, 2), (300, 63, 4, 3)])
def test_lss_forward_pallas_interpret_matches_ref(m, d, k, l):
    """Full lss_forward routing: impl flows core -> registry -> kernel."""
    index, _ = _fitted_index(m, d, k, l, seed=7)
    q = jax.random.normal(jax.random.PRNGKey(3), (16, d))
    ref = lss_forward(q, index, None, top_k=5, impl="ref")
    out = lss_forward(q, index, None, top_k=5, impl="pallas_interpret")
    for name, r, o in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=name)


def test_lss_topk_all_padding_bucket():
    """A query whose slab is entirely -1 must yield all -1 ids, NEG_INF
    logits, and sample size 0 — identically in ref and interpret mode."""
    d, k, l, cap = 8, 2, 1, 4
    theta = jax.random.normal(jax.random.PRNGKey(0), (d, k * l))
    # hand-built index: every bucket empty except bucket 0
    table_ids = jnp.full((l, 2 ** k, cap), -1, jnp.int32)
    table_ids = table_ids.at[0, 0].set(jnp.arange(cap))
    w_bucketed = jnp.zeros((l, 2 ** k, cap, d), jnp.float32)
    w_bucketed = w_bucketed.at[0, 0].set(
        jax.random.normal(jax.random.PRNGKey(1), (cap, d)))
    q_aug = jax.random.normal(jax.random.PRNGKey(2), (32, d))
    ref = lss_topk(q_aug, theta, table_ids, w_bucketed, top_k=3, impl="ref")
    out = lss_topk(q_aug, theta, table_ids, w_bucketed, top_k=3,
                   impl="pallas_interpret")
    for name, r, o in zip(("top_logits", "top_ids", "sample", "cand"),
                          ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=name)
    empty = np.asarray(ref[2]) == 0            # queries hashed to a -1 slab
    assert empty.any(), "degenerate: no query hit an empty bucket"
    np.testing.assert_array_equal(np.asarray(ref[1])[empty], -1)


def test_lss_topk_dtype_bf16_slabs():
    """bf16 slabs upcast in-kernel exactly like the ref einsum."""
    index, _ = _fitted_index(128, 32, 3, 2, seed=5)
    wb = index.w_bucketed.astype(jnp.bfloat16)
    index = index._replace(w_bucketed=wb)
    q_aug = simhash.augment_queries(
        jax.random.normal(jax.random.PRNGKey(0), (8, 32)))
    t = index.tables
    ref = lss_topk(q_aug, index.theta, t.table_ids, wb, top_k=4, impl="ref")
    out = lss_topk(q_aug, index.theta, t.table_ids, wb, top_k=4,
                   impl="pallas_interpret")
    for r, o in zip(ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# ------------------------------------------------- engine end-to-end --

def _engine(impl, m=512, d=32, seed=1, head="lss", buckets=(8,)):
    w = jax.random.normal(jax.random.PRNGKey(0), (m, d))
    eng = Engine(None, w, None,
                 LSSConfig(k_bits=4, n_tables=2, use_bucket_major=True),
                 top_k=5, head=head, buckets=buckets, impl=impl)
    eng.fit_random(jax.random.PRNGKey(seed))
    return eng


def test_engine_pallas_interpret_serves_through_fused_kernel():
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (8, 32)))
    ref_eng = _engine("ref")
    fused_eng = _engine("pallas_interpret")
    registry.reset_dispatch_log()
    ref_out = ref_eng.rank(q, record=False)
    out = fused_eng.rank(q, record=False)
    # the registry actually dispatched the fused op for the serving step
    assert ("lss_topk", "pallas_interpret") in registry.dispatch_log()
    assert registry.last_dispatch("lss_topk") == "pallas_interpret"
    for name, r, o in zip(("logits", "ids", "sample_size", "cand_ids"),
                          ref_out, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=name)


def test_engine_pallas_interpret_submit_flush_roundtrip():
    fused_eng = _engine("pallas_interpret", buckets=(1, 2, 4))
    ref_eng = _engine("ref", buckets=(1, 2, 4))
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((5, 32)).astype(np.float32)
    for eng in (fused_eng, ref_eng):
        for i in range(5):
            eng.submit(xs[i], labels=i % 3)
    got = fused_eng.flush()
    want = ref_eng.flush()
    for g, w_ in zip(got, want):
        assert g.rid == w_.rid
        np.testing.assert_array_equal(g.ids, w_.ids)
        np.testing.assert_array_equal(g.logits, w_.logits)
    m = fused_eng.metrics()
    assert m.n_requests == 5 and m.avg_sample_size > 0


def test_engine_sharded_head_with_interpret_impl():
    """The fused kernel also runs inside shard_map (TP=1 mesh on CPU)."""
    eng = _engine("pallas_interpret")
    q = np.asarray(jax.random.normal(jax.random.PRNGKey(9), (4, 32)))
    lss = eng.rank(q, head="lss", record=False)
    registry.reset_dispatch_log()
    sh = eng.rank(q, head="lss-sharded", record=False)
    assert ("lss_topk", "pallas_interpret") in registry.dispatch_log()
    np.testing.assert_array_equal(np.asarray(lss.ids), np.asarray(sh.ids))
    np.testing.assert_array_equal(np.asarray(lss.sample_size),
                                  np.asarray(sh.sample_size))


def test_engine_rejects_unknown_impl():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 8))
    with pytest.raises(ValueError):
        Engine(None, w, impl="cuda")


# ------------------------------------------------- shard_index padding --

def test_shard_index_pads_non_divisible_vocab():
    from repro.core.sharded import local_topk
    from repro.serve.heads import shard_index
    m, d, n_shards = 13, 8, 2
    w = jax.random.normal(jax.random.PRNGKey(3), (m, d))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(4), d + 1, 3, 2)
    cfg = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True)
    stack, w_stack, m_local = shard_index(w_aug, theta, cfg, n_shards)
    assert m_local == 7
    ids = np.asarray(stack.tables.table_ids)
    # the final shard owns rows 7..12 -> 6 real rows; padding never enters
    assert ids[1].max() < 6
    # padded slab rows are zeroed
    wb = np.asarray(stack.w_bucketed[1])
    assert (wb[ids[1] < 0] == 0).all()
    # per-shard top-k == brute force over that query's retrieved REAL rows
    from repro.core.lss import retrieve
    q = jax.random.normal(jax.random.PRNGKey(5), (8, d))
    q_aug = simhash.augment_queries(q)
    w_np = np.asarray(w_aug)
    for s in range(n_shards):
        idx = jax.tree.map(lambda x: x[s], stack)
        n_valid = min(m - s * m_local, m_local)
        _, top_i = local_topk(q, idx, None, 3)
        cand_q = np.asarray(retrieve(q_aug, idx)[0])
        assert cand_q.max() < n_valid, "padding row retrieved"
        full = np.asarray(q_aug) @ w_np[s * m_local:s * m_local + n_valid].T
        for i in range(8):
            uniq = sorted(set(int(x) for x in cand_q[i] if x >= 0),
                          key=lambda j: -full[i, j])
            got = [int(x) for x in np.asarray(top_i[i]) if x >= 0]
            assert len(got) == min(3, len(uniq))
            assert got == uniq[:len(got)]


def test_shard_index_divisible_unchanged():
    from repro.serve.heads import shard_index
    w = jax.random.normal(jax.random.PRNGKey(3), (12, 8))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(4), 9, 3, 1)
    cfg = LSSConfig(k_bits=3, n_tables=1, use_bucket_major=True)
    stack, _, m_local = shard_index(w_aug, theta, cfg, 3)
    assert m_local == 4
    assert stack.tables.table_ids.shape[0] == 3
