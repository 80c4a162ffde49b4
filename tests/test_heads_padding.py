"""Property-style coverage of the ``heads.shard_index`` padding path.

When ``m % n_shards != 0`` the WOL rows are padded up to the next
multiple and the final shard's tables are masked.  Across a sweep of
(m, n_shards) — hypothesis when installed, the deterministic stub sweep
otherwise — the invariants are:

  * padded (marker) rows never enter any shard's hash tables, so they
    can never be retrieved;
  * they never surface in any shard's top-k (ids stay local AND < that
    shard's real-row count), hence never in the merged global top-k
    either — on the ref path and on the fused interpret-mode kernel
    alike;
  * the shard-local ranking over real rows equals brute force, i.e.
    masking removed the padding WITHOUT disturbing real candidates.

Also covers the fused kernel's QUERY-tile padding (the other padding
axis): B not divisible by the query-block height pads with rows that
never reach any real query's top-k — interpret == ref at every B.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import simhash
from repro.core.lss import LSSConfig, retrieve
from repro.core.sharded import local_topk
from repro.serve.heads import shard_index

D = 8
TOP_K = 3
N_QUERIES = 6


def _build(m: int, n_shards: int):
    cfg = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True)
    w = jax.random.normal(jax.random.PRNGKey(m * 7 + n_shards), (m, D))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), D + 1,
                                     cfg.k_bits, cfg.n_tables)
    stack, w_stack, m_local = shard_index(w_aug, theta, cfg, n_shards)
    q = jax.random.normal(jax.random.PRNGKey(2), (N_QUERIES, D))
    return cfg, w_aug, stack, m_local, q


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=3, max_value=40),
       st.integers(min_value=2, max_value=4))
def test_shard_index_padding_invariants(m, n_shards):
    cfg, w_aug, stack, m_local, q = _build(m, n_shards)
    q_aug = np.asarray(simhash.augment_queries(q))
    w_np = np.asarray(w_aug)
    merged: list[list[tuple]] = [[] for _ in range(N_QUERIES)]
    for s in range(n_shards):
        idx = jax.tree.map(lambda x, s=s: x[s], stack)
        n_valid = min(max(m - s * m_local, 0), m_local)
        # 1. marker rows are absent from the tables entirely
        ids_tab = np.asarray(idx.tables.table_ids)
        assert ids_tab.max(initial=-1) < max(n_valid, 1)
        assert ((ids_tab >= 0) | (ids_tab == -1)).all()
        # ...and their slab rows are zeroed
        wb = np.asarray(idx.w_bucketed)
        assert (wb[ids_tab < 0] == 0).all()
        # 2. retrieval can never produce a padded id
        cand, _ = retrieve(jnp.asarray(q_aug), idx)
        cand = np.asarray(cand)
        assert cand.max(initial=-1) < max(n_valid, 1)
        # 3. shard-local top-k == brute force over the REAL rows
        logits, top_i = local_topk(q, idx, None, TOP_K)
        top_i = np.asarray(top_i)
        logits = np.asarray(logits)
        assert top_i.max(initial=-1) < max(n_valid, 1), \
            "padding row surfaced in top-k"
        full = q_aug @ w_np[s * m_local:s * m_local + n_valid].T \
            if n_valid else np.zeros((N_QUERIES, 0))
        for i in range(N_QUERIES):
            uniq = sorted({int(x) for x in cand[i] if x >= 0},
                          key=lambda j: -full[i, j])
            got = [int(x) for x in top_i[i] if x >= 0]
            assert got == uniq[:len(got)]
            assert len(got) == min(TOP_K, len(uniq))
            for r, j in enumerate(got):        # merged-view bookkeeping
                merged[i].append((float(logits[i, r]),
                                  s * m_local + j))
    # 4. the cross-shard merge (what make_sharded_lss_head's all-gather
    # + global top-k computes) contains only REAL global ids
    for i in range(N_QUERIES):
        top = sorted(merged[i], reverse=True)[:TOP_K]
        assert all(0 <= gid < m for _, gid in top)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=1, max_value=19))
def test_query_block_padding_parity(b):
    """The fused kernel's query-blocked grid pads B up to the tile
    multiple with rows that can never reach a real query's top-k: at
    every B — divisible by the tile height or not — interpret-mode
    outputs are bit-identical to the ref across all LSSForward fields,
    and the planned grid covers exactly ceil(B / Bq) tiles (the
    query-tile analogue of shard_index's marker-row invariants)."""
    from repro.core.lss import lss_forward
    from repro.kernels.lss_topk.ops import effective_block_q, grid_steps

    cfg = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True)
    w = jax.random.normal(jax.random.PRNGKey(b * 11 + 1), (40, D))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), D + 1,
                                     cfg.k_bits, cfg.n_tables)
    from repro.core.lss import build_index
    index = build_index(w_aug, theta, cfg)
    q = jax.random.normal(jax.random.PRNGKey(b), (b, D))
    ref = lss_forward(q, index, None, top_k=TOP_K, impl="ref")
    out = lss_forward(q, index, None, top_k=TOP_K,
                      impl="pallas_interpret")
    for name, r, o in zip(ref._fields, ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=f"B={b} {name}")
        assert np.asarray(o).shape[0] == b       # padding sliced off
    bq = effective_block_q(b)
    assert grid_steps(b) == -(-b // bq)


@settings(max_examples=8, deadline=None)
@given(st.integers(min_value=3, max_value=40),
       st.integers(min_value=2, max_value=4))
def test_shard_index_padding_masks_w_scale(m, n_shards):
    """int8 slab: the padded tail's ``w_scale`` rows are zeroed exactly
    like the marker weight rows.  The pad rows carry a NEG_INF sentinel
    bias column, so quantizing them would otherwise bake a garbage
    (inf-derived) scale into the slab — the mask keeps every marker
    slot's (weight, scale) pair identically zero, and the padded shard
    still ranks bit-identically on the ref and fused interpret paths."""
    cfg = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True,
                    slab_dtype="int8")
    w = jax.random.normal(jax.random.PRNGKey(m * 13 + n_shards), (m, D))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), D + 1,
                                     cfg.k_bits, cfg.n_tables)
    stack, _, m_local = shard_index(w_aug, theta, cfg, n_shards)
    for s in range(n_shards):
        idx = jax.tree.map(lambda x, s=s: x[s], stack)
        ids_tab = np.asarray(idx.tables.table_ids)
        ws = np.asarray(idx.w_scale)
        assert ws.shape == ids_tab.shape
        # real slots keep a usable (finite) scale everywhere
        assert np.isfinite(ws[ids_tab >= 0]).all()
    if m % n_shards:                       # the tail shard got masked:
        last = jax.tree.map(lambda x: x[-1], stack)
        ids_tab = np.asarray(last.tables.table_ids)
        ws = np.asarray(last.w_scale)
        # EVERY empty slot's scale is zeroed exactly like the weight
        # rows (no NEG_INF-derived garbage survives the mask)
        assert (ws[ids_tab < 0] == 0).all()
        assert (np.asarray(last.w_bucketed)[ids_tab < 0] == 0).all()
    q = jax.random.normal(jax.random.PRNGKey(2), (N_QUERIES, D))
    last = jax.tree.map(lambda x: x[-1], stack)      # the padded shard
    ref_l, ref_i = local_topk(q, last, None, TOP_K, impl="ref")
    out_l, out_i = local_topk(q, last, None, TOP_K,
                              impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(out_i))
    np.testing.assert_array_equal(np.asarray(ref_l), np.asarray(out_l))


@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=5, max_value=23),
       st.integers(min_value=2, max_value=3))
def test_shard_index_padding_fused_kernel_parity(m, n_shards):
    """The invariants hold identically through the fused interpret-mode
    kernel: padded shards rank exactly like the ref path."""
    cfg, _, stack, m_local, q = _build(m, n_shards)
    last = jax.tree.map(lambda x: x[-1], stack)    # the padded shard
    ref_l, ref_i = local_topk(q, last, None, TOP_K, impl="ref")
    out_l, out_i = local_topk(q, last, None, TOP_K,
                              impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(ref_i), np.asarray(out_i))
    np.testing.assert_array_equal(np.asarray(ref_l), np.asarray(out_l))
    n_valid = min(max(m - (n_shards - 1) * m_local, 0), m_local)
    assert np.asarray(out_i).max(initial=-1) < max(n_valid, 1)


@pytest.mark.parametrize("slab_dtype", ["fp32", "bf16", "int8"])
def test_shard_index_padding_masks_aligned_storage(slab_dtype):
    """Shards built for the TPU kernel's aligned layout mask the padded
    tail in that layout: the tail's ids read -1 in ``slab_ids`` too, and
    its slabs and scales are, once sliced back, exactly the logical
    build's, so the shard ranks as the logical one does."""
    from repro.kernels.lss_topk.slabs import logical_slabs
    m, n_shards = 13, 3
    cfg = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True,
                    slab_dtype=slab_dtype)
    w = jax.random.normal(jax.random.PRNGKey(5), (m, D))
    w_aug = simhash.augment_neurons(w, None)
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), D + 1,
                                     cfg.k_bits, cfg.n_tables)
    lo, _, m_local = shard_index(w_aug, theta, cfg, n_shards, impl="ref")
    al, _, _ = shard_index(w_aug, theta, cfg, n_shards, impl="pallas")
    last_lo = jax.tree.map(lambda x: x[-1], lo)
    last_al = jax.tree.map(lambda x: x[-1], al)
    n_valid = m - (n_shards - 1) * m_local
    ids = np.asarray(last_al.slab_ids)
    assert ids.max() < n_valid
    w_al = np.asarray(last_al.w_bucketed.astype(jnp.float32))
    assert (w_al[ids[:, 0] < 0] == 0).all()
    if slab_dtype == "int8":
        assert (np.asarray(last_al.w_scale)[ids < 0] == 0).all()
    shape = last_lo.tables.table_ids.shape
    for a, b in zip(logical_slabs(last_al.w_bucketed, last_al.w_scale,
                                  shape, D + 1),
                    (last_lo.w_bucketed, last_lo.w_scale)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    q = jax.random.normal(jax.random.PRNGKey(2), (N_QUERIES, D))
    for a, b in zip(local_topk(q, last_lo, None, TOP_K, impl="ref"),
                    local_topk(q, last_al, None, TOP_K, impl="ref")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
