"""Quantized slab storage (``lss_topk.slab_dtype``): exactness, strategy
resolution, refit requantization, and the DMA/VMEM accounting.

The acceptance bar: for EVERY storage format (fp32 | bf16 | int8) the
jnp ref and the pallas-interpret kernel are BIT-IDENTICAL across the
dedup strategies and the C sweep — dequantization is elementwise on
both sides, so the fp32 path's exact-equality contract carries over —
while int8 cuts the per-query slab DMA bytes >= 3x and costs <= 0.5%
top-k label recall on a synthetic WOL.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import simhash
from repro.core.lss import LSSConfig, build_index, lss_forward
from repro.kernels import registry
from repro.kernels.lss_topk import dedup as D
from repro.kernels.lss_topk import slabs as S
from repro.kernels.lss_topk.ops import lss_topk, lss_topk_vmem_bytes

FIELDS = ("top_logits", "top_ids", "sample_size", "cand_ids")


@pytest.fixture(autouse=True)
def _clean_registry():
    registry.set_default_impl(None)
    registry.set_default_strategy("lss_topk.dedup", None)
    registry.set_default_strategy("lss_topk.slab_dtype", None)
    D.set_dedup_auto_threshold(None)
    os.environ.pop(S.SLAB_DTYPE_ENV_VAR, None)
    registry.reset_dispatch_log()
    yield
    registry.set_default_impl(None)
    registry.set_default_strategy("lss_topk.dedup", None)
    registry.set_default_strategy("lss_topk.slab_dtype", None)
    D.set_dedup_auto_threshold(None)
    os.environ.pop(S.SLAB_DTYPE_ENV_VAR, None)


def _case(c, b=4, d=16, n_tables=2, k_bits=2, seed=0, slab_dtype="fp32"):
    """Synthetic bucket-major index (heavy cross-table duplicates) with
    the slabs stored in the requested format."""
    cap = c // n_tables
    assert cap * n_tables == c, (c, n_tables)
    n_buckets = 2 ** k_bits
    kt, kw, kq = jax.random.split(jax.random.PRNGKey(seed), 3)
    table_ids = jax.random.randint(kt, (n_tables, n_buckets, cap), -1,
                                   max(c // 2, 2), jnp.int32)
    w_fp32 = jax.random.normal(kw, (n_tables, n_buckets, cap, d))
    wb, w_scale = S.quantize_slabs(w_fp32, slab_dtype)
    theta = jax.random.normal(jax.random.PRNGKey(seed + 1),
                              (d, k_bits * n_tables))
    q = jax.random.normal(kq, (b, d), jnp.float32)
    return q, theta, table_ids, wb, w_scale


def _assert_same(ref, out, msg=""):
    for name, r, o in zip(FIELDS, ref, out):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o),
                                      err_msg=f"{msg} {name}")


# ------------------------------------- ref == interpret, full knob grid --

@pytest.mark.parametrize("slab_dtype", S.SLAB_DTYPE_CHOICES)
@pytest.mark.parametrize("dedup", ["quadratic", "bitonic"])
@pytest.mark.parametrize("c", [512, 2048, 8192])
def test_ref_matches_interpret_per_format(slab_dtype, dedup, c):
    """Bit-identity of ref vs pallas-interpret for every storage format
    x dedup strategy across the C sweep — the fp32 exactness contract
    must survive quantized storage unchanged."""
    if c >= 8192 and dedup == "quadratic":
        pytest.skip("quadratic [C,C] at 8k is test_dedup's slow regime; "
                    "the storage format is orthogonal to the mask")
    b = 2 if c >= 8192 else 4
    q, theta, tids, wb, w_scale = _case(c, b=b, seed=c,
                                        slab_dtype=slab_dtype)
    ref = lss_topk(q, theta, tids, wb, top_k=5, impl="ref", dedup=dedup,
                   w_scale=w_scale)
    out = lss_topk(q, theta, tids, wb, top_k=5, impl="pallas_interpret",
                   dedup=dedup, w_scale=w_scale)
    _assert_same(ref, out, f"{slab_dtype}/{dedup}/C={c}")


@pytest.mark.parametrize("slab_dtype", ["bf16", "int8"])
def test_non_lane_aligned_shapes(slab_dtype):
    """Non-128 d and capacity (the interpret path runs unpadded; ops.py
    pads P with -1 ids and zero scales only on real TPUs)."""
    q, theta, tids, wb, w_scale = _case(2 * 13, b=3, d=17, n_tables=2,
                                        slab_dtype=slab_dtype, seed=7)
    assert wb.shape[2] == 13 and wb.shape[3] == 17
    ref = lss_topk(q, theta, tids, wb, top_k=4, impl="ref",
                   w_scale=w_scale)
    out = lss_topk(q, theta, tids, wb, top_k=4, impl="pallas_interpret",
                   w_scale=w_scale)
    _assert_same(ref, out, f"{slab_dtype} d=17 P=13")


@pytest.mark.parametrize("slab_dtype", S.SLAB_DTYPE_CHOICES)
def test_all_empty_buckets(slab_dtype):
    """All-(-1) tables: empty slots quantize to zero rows in every
    format (the eps scale keeps int8 dequantizing to exactly 0), so the
    outputs are all-(-1) ids / NEG_INF logits / zero sample sizes."""
    q, theta, _, wb_f, _ = _case(8, b=3, d=8, slab_dtype="fp32", seed=3)
    tids = jnp.full((2, 4, 4), -1, jnp.int32)
    wb, w_scale = S.quantize_slabs(jnp.zeros_like(
        S.dequantize_slabs(wb_f, None)), slab_dtype)
    for impl in ("ref", "pallas_interpret"):
        out = lss_topk(q, theta, tids, wb, top_k=3, impl=impl,
                       w_scale=w_scale)
        assert np.all(np.asarray(out[1]) == -1), impl
        assert np.all(np.asarray(out[2]) == 0), impl


def test_w_scale_contract_enforced():
    """int8 slabs without scales (and scales without int8 slabs) are
    rejected loudly, not served wrongly."""
    q, theta, tids, wb, w_scale = _case(8, b=2, d=8, slab_dtype="int8")
    with pytest.raises(ValueError, match="w_scale"):
        lss_topk(q, theta, tids, wb, top_k=2, impl="ref")
    wb_f, _ = _case(8, b=2, d=8, slab_dtype="fp32")[3], None
    with pytest.raises(ValueError, match="w_scale"):
        lss_topk(q, theta, tids, wb_f, top_k=2, impl="ref",
                 w_scale=w_scale)


# ----------------------------------------------- strategy resolution --

def test_resolution_order_and_log():
    """Explicit arg > process override > env var > auto(fp32), with
    every resolution recorded in the dispatch log."""
    assert S.resolve_slab_dtype(None) == "fp32"                 # auto
    os.environ[S.SLAB_DTYPE_ENV_VAR] = "int8"
    assert S.resolve_slab_dtype(None) == "int8"                 # env
    with registry.use_strategy("lss_topk.slab_dtype", "bf16"):
        assert S.resolve_slab_dtype(None) == "bf16"             # process
        assert S.resolve_slab_dtype("fp32") == "fp32"           # explicit
    log = [c for (k, c) in registry.dispatch_log()
           if k == "lss_topk.slab_dtype"]
    assert log == ["fp32", "int8", "bf16", "fp32"]
    with pytest.raises(Exception):
        S.resolve_slab_dtype("int4")


def test_build_index_resolves_from_env(monkeypatch):
    monkeypatch.setenv(S.SLAB_DTYPE_ENV_VAR, "int8")
    w_aug = simhash.augment_neurons(
        jax.random.normal(jax.random.PRNGKey(0), (64, 8)))
    cfg = LSSConfig(k_bits=2, n_tables=2)        # slab_dtype=None -> env
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1),
                                     w_aug.shape[1], 2, 2)
    index = build_index(w_aug, theta, cfg)
    assert index.w_bucketed.dtype == jnp.int8
    assert index.w_scale is not None
    assert index.w_scale.shape == index.tables.table_ids.shape
    # explicit config wins over the env
    idx2 = build_index(w_aug, theta, cfg._replace(slab_dtype="bf16"))
    assert idx2.w_bucketed.dtype == jnp.bfloat16
    assert idx2.w_scale is None


# ------------------------------------------------------- slab layout --

def _fitted(m=100, d=12, k_bits=3, n_tables=2, slab_dtype="fp32"):
    w_aug = simhash.augment_neurons(
        jax.random.normal(jax.random.PRNGKey(0), (m, d)))
    theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), d + 1,
                                     k_bits, n_tables)
    cfg = LSSConfig(k_bits=k_bits, n_tables=n_tables, slab_dtype=slab_dtype)
    return w_aug, theta, cfg


# The logical index as the IUL rebuild makes it, in one program: the
# aligned build is one program too, and XLA compiles int8's division by
# 127 into a product, so an eager build's scales may differ in the last
# bit from either.
_BUILD_JIT = jax.jit(build_index, static_argnames=("cfg", "impl"))


@pytest.mark.parametrize("slab_dtype", S.SLAB_DTYPE_CHOICES)
@pytest.mark.parametrize("m,k_bits", [(100, 3), (5000, 7)])
def test_aligned_build_holds_the_logical_index(slab_dtype, m, k_bits):
    """Built for ``pallas``, the index stores the kernel's aligned layout
    and nothing else: the same tables (ids, overflow) as the logical
    build, its slab rows and scales bit for bit, and empty slots (id
    -1, zero row, zero scale) in every added lane — for one build chunk
    of slabs and for several."""
    w_aug, theta, cfg = _fitted(m=m, k_bits=k_bits, slab_dtype=slab_dtype)
    lo = _BUILD_JIT(w_aug, theta, cfg, impl="ref")
    al = build_index(w_aug, theta, cfg, impl="pallas")
    n_tables, n_buckets, cap = lo.tables.table_ids.shape
    d = w_aug.shape[1]
    assert lo.slab_ids is None and lo.w_bucketed.ndim == 4
    n_slabs = n_tables * n_buckets
    assert al.w_bucketed.shape == (n_slabs, 128, 128)
    assert al.w_bucketed.dtype == lo.w_bucketed.dtype
    assert al.slab_ids.shape == (n_slabs, 1, 128)
    np.testing.assert_array_equal(al.tables.table_ids, lo.tables.table_ids)
    np.testing.assert_array_equal(al.tables.n_dropped, lo.tables.n_dropped)
    assert al.tables.capacity == lo.tables.capacity == cap
    ids = np.asarray(al.slab_ids)[:, 0]
    np.testing.assert_array_equal(
        ids[:, :cap], np.asarray(lo.tables.table_ids).reshape(n_slabs, cap))
    assert (ids[:, cap:] == -1).all()
    w = np.asarray(al.w_bucketed.astype(jnp.float32))
    assert (w[:, cap:] == 0).all() and (w[:, :, d:] == 0).all()
    wl, sl = S.logical_slabs(al.w_bucketed, al.w_scale,
                             lo.tables.table_ids.shape, d)
    np.testing.assert_array_equal(np.asarray(wl), np.asarray(lo.w_bucketed))
    if slab_dtype == "int8":
        np.testing.assert_array_equal(np.asarray(sl), np.asarray(lo.w_scale))
        assert al.w_scale.shape == (n_slabs, 1, 128)
        assert (np.asarray(al.w_scale)[:, 0, cap:] == 0).all()
    else:
        assert al.w_scale is None


@pytest.mark.parametrize("impl", ["ref", "pallas"])
def test_layout_follows_the_resolved_impl(monkeypatch, impl):
    """No knob of its own: the layout follows the impl the registry
    resolves for ``lss_topk`` (explicit > process default > env)."""
    w_aug, theta, cfg = _fitted()
    rank = 3 if impl == "pallas" else 4
    assert S.slab_layout_for(impl) == ("aligned" if impl == "pallas"
                                       else "logical")
    assert build_index(w_aug, theta, cfg, impl=impl).w_bucketed.ndim == rank
    with registry.use_impl(impl):
        assert build_index(w_aug, theta, cfg).w_bucketed.ndim == rank
    monkeypatch.setenv(registry.ENV_VAR, impl)
    assert build_index(w_aug, theta, cfg).w_bucketed.ndim == rank


@pytest.mark.parametrize("slab_dtype", S.SLAB_DTYPE_CHOICES)
@pytest.mark.parametrize("build_impl,layout",
                         [("pallas", "stored"), ("ref", "padded_per_call")])
def test_kernel_reads_stored_layout_and_logs_it(slab_dtype, build_impl,
                                                layout):
    """The kernel impl tells the layouts apart by shape alone and records
    which it served in the dispatch log; over either it ranks the same
    ids as the ref over the logical index (logits to within a rounding,
    as the aligned contraction is longer)."""
    w_aug, theta, cfg = _fitted(slab_dtype=slab_dtype)
    index = build_index(w_aug, theta, cfg, impl=build_impl)
    ref = lss_forward(jax.random.normal(jax.random.PRNGKey(2), (5, 12)),
                      _BUILD_JIT(w_aug, theta, cfg, impl="ref"), None,
                      top_k=3, impl="ref")
    registry.reset_dispatch_log()
    out = lss_forward(jax.random.normal(jax.random.PRNGKey(2), (5, 12)),
                      index, None, top_k=3, impl="pallas_interpret")
    assert [c for k, c in registry.dispatch_log()
            if k == "lss_topk.slab_layout"] == [layout]
    for name, r, o in zip(FIELDS, ref, out):
        if name == "top_logits":
            np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                       rtol=1e-5, atol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(o), np.asarray(r),
                                          err_msg=name)


def test_unfused_readers_take_aligned_storage():
    """The unfused bucket-logits path and the ref impl slice aligned
    storage back to the logical index and see exactly what it holds."""
    from repro.core.lss import retrieve, sparse_logits_bucketed
    w_aug, theta, cfg = _fitted(slab_dtype="int8")
    lo = _BUILD_JIT(w_aug, theta, cfg, impl="ref")
    al = build_index(w_aug, theta, cfg, impl="pallas")
    q = jax.random.normal(jax.random.PRNGKey(2), (5, 12))
    q_aug = simhash.augment_queries(q)
    _, buckets = retrieve(q_aug, lo)
    for r, o in zip(sparse_logits_bucketed(q_aug, lo, buckets, impl="ref"),
                    sparse_logits_bucketed(q_aug, al, buckets, impl="ref")):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
    _assert_same(lss_forward(q, lo, None, top_k=3, impl="ref"),
                 lss_forward(q, al, None, top_k=3, impl="ref"))


def _build_with(path: str, impl: str):
    """The index a build ``path`` makes for an engine pinned to ``impl``."""
    from repro.serve.engine import Engine
    from repro.serve.heads import shard_index
    from repro.serve.refresh import IndexRefresher, RefreshConfig

    w = jax.random.normal(jax.random.PRNGKey(0), (96, 12))
    eng = Engine(None, w, None,
                 LSSConfig(k_bits=3, n_tables=2, iul_epochs=1,
                           iul_batch=32, iul_inner_steps=1),
                 top_k=3, buckets=(4,), impl=impl)
    if path == "shard_index":
        theta = simhash.init_hyperplanes(jax.random.PRNGKey(1), 13, 3, 2)
        stack, _, _ = shard_index(eng._w_aug, theta, eng.lss_cfg, 2,
                                  impl=impl)
        return jax.tree.map(lambda x: x[-1], stack)
    if path == "swap_from_theta":
        eng.swap_from_theta(
            simhash.init_hyperplanes(jax.random.PRNGKey(1), 13, 3, 2))
        return eng.index
    q = jax.random.normal(jax.random.PRNGKey(2), (64, 12))
    labels = jax.random.randint(jax.random.PRNGKey(3), (64, 2), 0, 96)
    eng.fit_from_queries(jax.random.PRNGKey(4), q, labels)   # IUL rebuild
    if path == "fit":
        return eng.index
    return IndexRefresher(eng, None, RefreshConfig())._refit()[0]


@pytest.mark.parametrize("impl", ["ref", "pallas"])
@pytest.mark.parametrize("path",
                         ["shard_index", "swap_from_theta", "fit", "refresh"])
def test_every_build_path_stores_its_impls_layout(path, impl):
    index = _build_with(path, impl)
    if impl == "pallas":
        assert index.w_bucketed.ndim == 3 and index.slab_ids is not None
        assert S.is_aligned(index.w_bucketed, 13)
    else:
        assert index.w_bucketed.ndim == 4 and index.slab_ids is None


# ------------------------------------------------ refit requantization --

def test_refit_requantizes_and_invalidates_steps():
    """A refit rebuilds the index through build_index (requantizing from
    the new fp32 weights) and drops the engine's LSS jitted steps, so
    no step can serve stale scales."""
    from repro.serve.engine import Engine

    w = jax.random.normal(jax.random.PRNGKey(0), (256, 12))
    q = jax.random.normal(jax.random.PRNGKey(1), (4, 12))
    eng = Engine(None, w, None, LSSConfig(k_bits=3, n_tables=2),
                 top_k=3, buckets=(4,), impl="ref", slab_dtype="int8")
    eng.fit_random(jax.random.PRNGKey(2))
    assert eng.index.w_bucketed.dtype == jnp.int8
    scale0 = np.asarray(eng.index.w_scale)
    eng.rank(q, record=False)
    assert eng.compile_counts[("lss", 4)] == 1
    eng.fit_random(jax.random.PRNGKey(3))        # refit: new hyperplanes
    assert eng.index.w_bucketed.dtype == jnp.int8
    assert not np.array_equal(scale0, np.asarray(eng.index.w_scale))
    eng.rank(q, record=False)                    # step was invalidated
    assert eng.compile_counts[("lss", 4)] == 2


# --------------------------------------------- recall + byte accounting --

def test_int8_recall_within_half_percent_of_fp32():
    """Synthetic WOL: quantized ranking loses <= 0.5% top-k label recall
    vs the fp32 index (candidate retrieval is identical by construction
    — tables hash the fp32 weights)."""
    m, d, b, top_k = 2048, 31, 32, 10
    w = jax.random.normal(jax.random.PRNGKey(0), (m, d), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(1), (b, d), jnp.float32)
    w_aug = simhash.augment_neurons(w)
    exact = jax.lax.top_k(simhash.augment_queries(q) @ w_aug.T, top_k)[1]
    recall = {}
    cands = {}
    for sdt in ("fp32", "int8"):
        cfg = LSSConfig(k_bits=3, n_tables=4, slab_dtype=sdt)
        theta = simhash.init_hyperplanes(jax.random.PRNGKey(2),
                                         w_aug.shape[1], 3, 4)
        out = lss_forward(q, build_index(w_aug, theta, cfg), None,
                          top_k=top_k, impl="ref")
        hit = (exact[:, :, None] == out.top_ids[:, None, :]).any(-1)
        recall[sdt] = float(jnp.mean(hit))
        cands[sdt] = np.asarray(out.cand_ids)
    # retrieval is storage-independent; only ranking may differ
    np.testing.assert_array_equal(cands["fp32"], cands["int8"])
    assert recall["fp32"] - recall["int8"] <= 0.005, recall


def test_dma_and_vmem_accounting():
    """int8 slab DMA bytes are >= 3x below fp32 at serving dims, and the
    VMEM model's slab term shrinks with the storage itemsize (while
    keeping its pre-slab_dtype positional signature)."""
    L, P, d = 4, 512, 64
    fp32 = S.lss_topk_slab_dma_bytes(L, P, d, "fp32")
    int8 = S.lss_topk_slab_dma_bytes(L, P, d, "int8")
    assert fp32 / int8 >= 3.0, (fp32, int8)
    assert S.lss_topk_slab_dma_bytes(L, P, d, "bf16") < fp32
    # VMEM estimate: int8 scratch (1B/elt + scale rows) < bf16 < fp32
    kw = dict(block_q=8, dedup="bitonic", kl=16)
    v = {s: lss_topk_vmem_bytes(L * P, d, P, slab_dtype=s, **kw)
         for s in S.SLAB_DTYPE_CHOICES}
    assert v["int8"] < v["bf16"] < v["fp32"]
    # legacy positional call (no slab_dtype) still works == fp32
    assert lss_topk_vmem_bytes(L * P, d, P, **kw) == v["fp32"]


def test_quantize_roundtrip_properties():
    """Rowwise int8: zero rows round-trip to exactly 0, values stay
    within one scale step, and bf16/fp32 return no scale table."""
    x = jnp.concatenate([jax.random.normal(jax.random.PRNGKey(0), (7, 9)),
                         jnp.zeros((1, 9))])
    q8, scale = S.quantize_slabs(x[None, None], "int8")
    deq = S.dequantize_slabs(q8, scale)
    assert np.all(np.asarray(deq[0, 0, -1]) == 0.0)
    err = np.abs(np.asarray(deq - x[None, None]))
    assert err.max() <= np.asarray(scale).max() / 2 + 1e-7
    for sdt in ("fp32", "bf16"):
        _, none_scale = S.quantize_slabs(x[None, None], sdt)
        assert none_scale is None
    with pytest.raises(ValueError):
        S.quantize_slabs(x[None, None], "fp64")
    with pytest.raises(ValueError):
        S.slab_dtype_of(x.astype(jnp.float16))
