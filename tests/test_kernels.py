"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret
mode (the kernel body executes on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import bucket_logits, simhash_codes


@pytest.mark.parametrize("b,d,k,l", [
    (64, 128, 4, 1), (32, 129, 6, 3), (256, 64, 8, 2), (16, 31, 2, 4),
    (128, 897, 10, 1),
])
def test_simhash_codes_sweep(b, d, k, l):
    key = jax.random.PRNGKey(b + d)
    x = jax.random.normal(key, (b, d))
    theta = jax.random.normal(jax.random.PRNGKey(1), (d, k * l))
    ref = simhash_codes(x, theta, k, l, impl="ref")
    out = simhash_codes(x, theta, k, l, impl="pallas_interpret", block_b=16)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_simhash_codes_dtypes(dtype):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 64)).astype(dtype)
    theta = jax.random.normal(jax.random.PRNGKey(1), (64, 8)).astype(dtype)
    ref = simhash_codes(x, theta, 4, 2, impl="ref")
    out = simhash_codes(x, theta, 4, 2, impl="pallas_interpret", block_b=32)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


@pytest.mark.parametrize("b,d,s,p,l", [
    (16, 128, 32, 128, 1), (8, 100, 48, 96, 3), (4, 64, 8, 256, 2),
    (32, 897, 16, 24, 1),
])
def test_bucket_logits_sweep(b, d, s, p, l):
    key = jax.random.PRNGKey(b * p)
    q = jax.random.normal(key, (b, d))
    w = jax.random.normal(jax.random.PRNGKey(1), (s, p, d))
    ids = jax.random.randint(jax.random.PRNGKey(2), (b, l), 0, s)
    ref = bucket_logits(q, w, ids, impl="ref")
    out = bucket_logits(q, w, ids, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_bucket_logits_dtypes(dtype, rtol):
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (8, 128)).astype(dtype)
    w = jax.random.normal(jax.random.PRNGKey(4), (16, 128, 128)).astype(dtype)
    ids = jax.random.randint(jax.random.PRNGKey(5), (8, 2), 0, 16)
    ref = bucket_logits(q, w, ids, impl="ref")
    out = bucket_logits(q, w, ids, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=rtol, atol=rtol)


def test_bucket_logits_matches_full_index_pipeline():
    """End-to-end: kernel output == gather-path logits of the LSS index."""
    from repro.core import simhash as sh
    from repro.core.lss import LSSConfig, build_index, retrieve, \
        sparse_logits_gather
    key = jax.random.PRNGKey(0)
    m, d, n = 300, 63, 16
    w = jax.random.normal(key, (m, d))
    q = jax.random.normal(jax.random.PRNGKey(1), (n, d))
    cfg = LSSConfig(k_bits=4, n_tables=2)
    w_aug = sh.augment_neurons(w, None)
    theta = sh.init_hyperplanes(jax.random.PRNGKey(2), d + 1, 4, 2)
    index = build_index(w_aug, theta, cfg)
    q_aug = sh.augment_queries(q)
    cand, buckets = retrieve(q_aug, index)
    want = sparse_logits_gather(q_aug, w_aug, cand)
    t = index.tables
    slabs = index.w_bucketed.reshape(-1, t.capacity, d + 1)
    slab_ids = buckets + jnp.arange(t.n_tables)[None, :] * t.n_buckets
    got = bucket_logits(q_aug, slabs, slab_ids, impl="pallas_interpret")
    got = got.reshape(n, -1)
    mask = np.asarray(cand) >= 0
    np.testing.assert_allclose(np.asarray(want)[mask],
                               np.asarray(got)[mask], rtol=1e-4, atol=1e-4)


def test_lss_topk_large_c_auto_switches_instead_of_warning():
    """Past the old ~2k comfort limit the registry now auto-switches to
    the bitonic dedup — no warning, because the bitonic working set
    still fits VMEM at this shape."""
    import warnings

    from repro.kernels import registry
    from repro.kernels.lss_topk import ops

    d_aug, cap = 8, 2560                        # C = 1 * 2560 > 2048
    q = jnp.zeros((1, d_aug))
    theta = jnp.ones((d_aug, 1))                # K=1 bit, L=1 table
    tids = jnp.full((1, 2, cap), -1, jnp.int32)
    wb = jnp.zeros((1, 2, cap, d_aug))
    registry.reset_dispatch_log()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.lss_topk(q, theta, tids, wb, top_k=3, impl="ref")
    assert ("lss_topk.dedup", "bitonic") in registry.dispatch_log()


def test_lss_topk_warns_once_past_vmem_budget():
    """The warning survives, but its limit is DERIVED from the shape:
    a dedup working set past the VMEM budget says so, once per shape."""
    import warnings

    from repro.kernels.lss_topk import ops

    d_aug, cap = 8, 2048                        # quadratic ws ~ 9*C^2
    assert ops.lss_topk_vmem_bytes(cap, d_aug, cap, dedup="quadratic") \
        > ops.VMEM_BUDGET_BYTES
    assert ops.lss_topk_vmem_bytes(cap, d_aug, cap, dedup="bitonic") \
        < ops.VMEM_BUDGET_BYTES
    q = jnp.zeros((1, d_aug))
    theta = jnp.ones((d_aug, 1))                # K=1 bit, L=1 table
    tids = jnp.full((1, 2, cap), -1, jnp.int32)
    wb = jnp.zeros((1, 2, cap, d_aug))
    ops._warn_vmem_exceeded.cache_clear()
    with pytest.warns(UserWarning, match=r"VMEM working set"):
        ops.lss_topk(q, theta, tids, wb, top_k=3, impl="ref",
                     dedup="quadratic")
    with warnings.catch_warnings():             # second call: silent
        warnings.simplefilter("error")
        ops.lss_topk(q, theta, tids, wb, top_k=3, impl="ref",
                     dedup="quadratic")
    # same shape under the auto-selected bitonic strategy: never warns
    ops._warn_vmem_exceeded.cache_clear()
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ops.lss_topk(q, theta, tids, wb, top_k=3, impl="ref")


@pytest.mark.parametrize("s,window_bytes", [(256, None), (256, 2 ** 16),
                                            (96, None)],
                         ids=["two-windows", "row-per-step", "unaligned"])
def test_kv_write_lanes_kernel_matches_ref(monkeypatch, s, window_bytes):
    """The lanes kernel (interpret mode) writes exactly the per-row
    updates: positions at both edges of a 128-lane window, at S - 1 and at
    S (which writes nothing), one or all rows per grid step, and a
    sequence 128 does not divide (one window of all of S)."""
    from repro.kernels.kv_write import ops
    if window_bytes:
        monkeypatch.setattr(ops, "WINDOW_BYTES", window_bytes)
    n_l, b, n_kv, h = 3, 6, 2, 64
    shape = (n_l, b, s, n_kv, h)
    k = jax.random.normal(jax.random.PRNGKey(0), shape, jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.bfloat16)
    k_rows = jax.random.normal(jax.random.PRNGKey(2), (b, n_kv, h))
    v_rows = jax.random.normal(jax.random.PRNGKey(3), (b, n_kv, h))
    pos = jnp.array([0, min(127, s - 2), min(128, s - 3), s - 1, s, 5],
                    jnp.int32)
    want = ops.kv_write(k, v, k_rows, v_rows, 1, pos, impl="ref")
    got = ops.kv_write(k, v, k_rows, v_rows, 1, pos,
                       impl="pallas_interpret")
    for g, w, c in zip(got, want, (k, v)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
        assert not np.array_equal(np.asarray(g), np.asarray(c))
    ref_k = np.asarray(k).copy()
    for r, p in enumerate(np.asarray(pos)):
        if p < s:
            ref_k[1, r, p] = np.asarray(k_rows[r].astype(jnp.bfloat16))
    np.testing.assert_array_equal(np.asarray(want[0]), ref_k)
