"""LSS retrieval + sparse-WOL inference (paper Algorithm 2, TPU-native).

Pipeline per query embedding q (from the layer below the WOL):

    q --augment--> [q,0] --theta--> L bucket ids --tables--> candidate ids
      --bucket-major slab / gather--> sparse logits --dedup+mask--> top-k

Everything is static-shape: the candidate set is ``[B, L*P]`` with -1
padding; duplicates across tables are masked (not compacted) before
ranking, which preserves exact top-k semantics.

Retrieval and scoring dispatch through the kernel registry
(``repro.kernels.registry``): on a bucket-major index, ``lss_forward``
routes the whole pipeline through the fused ``lss_topk`` op (one Pallas
pass on TPU, the jnp oracle on CPU); ``retrieve`` and
``sparse_logits_bucketed`` route through the ``simhash_codes`` /
``bucket_logits`` ops.  Pass ``impl=`` to pin an implementation
(``ref`` | ``pallas`` | ``pallas_interpret``) or leave ``None`` for
backend auto-selection.  ``dedup=`` likewise pins the cross-table dedup
algorithm (``quadratic`` | ``bitonic``); left ``None``, the registry
auto-switches to the bitonic sorting network once C = L*P crosses the
measured crossover, so large candidate counts are a strategy change,
not a hard wall — a warning fires only past the VMEM budget derived
from the actual (C, d, P) shape (``kernels.lss_topk.ops``).

Slab storage is a third knob, resolved HERE at :func:`build_index` time
rather than per call: ``LSSConfig.slab_dtype`` (``fp32`` | ``bf16`` |
``int8``; None = the ``lss_topk.slab_dtype`` registry strategy, env
``REPRO_LSS_SLAB_DTYPE``).  A quantized index stores its bucket-major
slabs in the compressed format (int8 carries a per-neuron-row scale
table in ``LSSIndex.w_scale``) and both lss_topk impls dequantize on
the fly.  Because ``fit_lss`` rebuilds the index through this same
constructor every IUL epoch, refits REQUANTIZE automatically — there is
no path that silently mixes fp32 tables with stale quantized slabs.

The slab LAYOUT is decided at build time the same way: storage follows
the impl that will serve it (``build_index(..., impl=)``, resolved like
the ``lss_topk`` op's impl).  ``pallas`` gets the TPU kernel's aligned
layout (``kernels.lss_topk.slabs.kernel_slabs``), stored INSTEAD of the
logical ``[L, 2^K, P, d]`` tensor, so no call re-lays the index out;
``ref`` and ``pallas_interpret`` keep the logical layout.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import simhash
from repro.core.tables import LSSTables, build_tables, bucketize_weights
from repro.kernels import bucket_logits, lss_topk, simhash_codes
from repro.kernels.lss_topk.slabs import (dequantize_slabs, kernel_slabs,
                                          logical_slabs, quantize_slabs,
                                          resolve_slab_dtype, slab_layout_for)

__all__ = [
    "LSSConfig", "LSSIndex", "build_index", "retrieve", "dedup_mask",
    "sparse_logits_gather", "sparse_logits_bucketed", "lss_forward",
    "lss_predict", "label_recall", "precision_at_k", "avg_sample_size",
]

NEG_INF = -1e30


class LSSConfig(NamedTuple):
    k_bits: int = 4
    n_tables: int = 1
    capacity: int = 0          # 0 -> auto: 2 * m / 2^K rounded up to 8
    use_bucket_major: bool = True   # materialise [L, 2^K, P, d] weight slabs
    # slab storage format: fp32 | bf16 | int8, None = registry strategy
    # (lss_topk.slab_dtype / $REPRO_LSS_SLAB_DTYPE, auto -> fp32)
    slab_dtype: str | None = None
    # IUL pair-mining thresholds (inner-product quantiles; see iul.py)
    t1_quantile: float = 0.3
    t2_quantile: float = 0.7
    iul_lr: float = 1e-3
    iul_epochs: int = 8
    iul_batch: int = 256
    iul_inner_steps: int = 8   # gradient steps per mined pair batch

    def resolve_capacity(self, m: int) -> int:
        if self.capacity:
            return self.capacity
        p = -(-2 * m // 2 ** self.k_bits)        # 2x the perfectly-even load
        return max(8, -(-p // 8) * 8)            # round up to a lane multiple


class LSSIndex(NamedTuple):
    """The frozen serving-time index (a pytree; shardable under pjit).

    ``w_bucketed`` may store fp32, bf16 or int8 slabs — the storage
    format is recovered from the array dtype, and ``w_scale`` is the
    int8 format's per-neuron-row fp32 scale table (None otherwise).
    Hash tables are always built from the fp32 ``w_aug``, so candidate
    retrieval (the paper's label recall) is identical across formats;
    only the ranked logits see quantization error.

    In the aligned layout (an index built for the ``pallas`` impl) the
    slabs are ``[L*2^K, P', d']``, the scales ``[L*2^K, 1, P']`` and
    ``slab_ids`` holds the matching ``[L*2^K, 1, P']`` ids; ``tables``
    stays logical (``capacity`` is P) in both layouts.
    """

    theta: jax.Array             # [d_aug, K*L] learned hyperplanes
    tables: LSSTables            # bucket-major neuron ids
    w_bucketed: jax.Array | None  # [L, 2^K, P, d_aug] or None (gather path)
    w_scale: jax.Array | None = None  # [L, 2^K, P] f32, int8 storage only
    slab_ids: jax.Array | None = None  # [L*2^K, 1, P'] i32, aligned only


jax.tree_util.register_pytree_node(
    LSSIndex,
    lambda i: ((i.theta, i.tables, i.w_bucketed, i.w_scale, i.slab_ids),
               None),
    lambda _, leaves: LSSIndex(*leaves),
)


def _aligned_slabs(w_aug: jax.Array, tables: LSSTables, slab_dtype: str
                   ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Bucketize, quantize and lay out a few slabs at a time, straight
    into the aligned tensor: the logical tensor is never whole, so the
    build holds little beyond what it returns."""
    n_slabs = tables.n_tables * tables.n_buckets
    chunk = math.gcd(n_slabs, 64)

    def some_slabs(ids: jax.Array):                      # [chunk, P]
        part = tables._replace(table_ids=ids[None])
        wb, w_scale = quantize_slabs(bucketize_weights(w_aug, part),
                                     slab_dtype)
        return kernel_slabs(part.table_ids, wb, w_scale)

    out = jax.lax.map(some_slabs, tables.table_ids.reshape(
        n_slabs // chunk, chunk, tables.capacity))
    return jax.tree.map(lambda x: x.reshape(n_slabs, *x.shape[2:]), out)


_ALIGNED_SLABS_JIT = jax.jit(_aligned_slabs, static_argnames=("slab_dtype",))


def build_index(w_aug: jax.Array, theta: jax.Array, cfg: LSSConfig, *,
                impl: str | None = None) -> LSSIndex:
    """(Re)build tables (and slabs) for the current hyperplanes.

    Resolves the slab storage format (``cfg.slab_dtype`` >
    ``lss_topk.slab_dtype`` strategy) and quantizes the bucket-major
    slabs at construction, so every rebuild — including each IUL refit
    epoch inside ``fit_lss``'s jitted ``rebuild`` — requantizes from the
    current fp32 weights.  ``impl`` is the ``lss_topk`` impl that will
    serve the index (None = the registry's resolution): ``pallas`` gets
    the aligned slab layout, the others the logical one.  The tables
    are built at the logical capacity either way, so both layouts hold
    the same neurons and drop the same overflow.
    """
    cap = cfg.resolve_capacity(w_aug.shape[0])
    tables = build_tables(w_aug, theta, cfg.k_bits, cfg.n_tables, cap)
    if not cfg.use_bucket_major:
        return LSSIndex(theta, tables, None, None)
    slab_dtype = resolve_slab_dtype(cfg.slab_dtype)
    if slab_layout_for(impl) == "aligned":
        ids, wb, w_scale = _ALIGNED_SLABS_JIT(w_aug, tables,
                                              slab_dtype=slab_dtype)
        return LSSIndex(theta, tables, wb, w_scale, ids)
    wb, w_scale = quantize_slabs(bucketize_weights(w_aug, tables), slab_dtype)
    return LSSIndex(theta, tables, wb, w_scale)


def retrieve(q_aug: jax.Array, index: LSSIndex, impl: str | None = None
             ) -> tuple[jax.Array, jax.Array]:
    """Query the L tables.

    Returns:
      cand_ids: int32 ``[B, L*P]`` neuron ids (-1 = empty slot)
      buckets:  int32 ``[B, L]`` the bucket hit in each table
    """
    t = index.tables
    # registry-dispatched simhash_codes on the normalized queries is
    # exactly simhash.bucket_ids (sign is scale-invariant; the ref impls
    # share the same fp32 op sequence)
    buckets = simhash_codes(simhash.unit(q_aug), index.theta, t.k_bits,
                            t.n_tables, impl=impl)
    # table_ids[l, buckets[b, l]] for every (b, l)
    cand = jnp.take_along_axis(
        t.table_ids[None],                       # [1, L, 2^K, P]
        buckets.T[None, :, :, None],             # [1, L, B, 1]
        axis=2,
    )[0]                                         # [L, B, P]
    cand_ids = jnp.swapaxes(cand, 0, 1).reshape(q_aug.shape[0], -1)
    return cand_ids, buckets


def dedup_mask(ids: jax.Array) -> jax.Array:
    """Bool mask ``[B, C]``: True for the first occurrence of each non-neg id.

    Sort-based: duplicates and -1 padding get False.  Static shape.
    """
    order = jnp.argsort(ids, axis=-1, stable=True)
    sorted_ids = jnp.take_along_axis(ids, order, axis=-1)
    first = jnp.concatenate(
        [jnp.ones_like(sorted_ids[:, :1], bool),
         sorted_ids[:, 1:] != sorted_ids[:, :-1]], axis=-1)
    first &= sorted_ids >= 0
    # scatter back to original positions
    b = jnp.arange(ids.shape[0])[:, None]
    mask = jnp.zeros(ids.shape, bool).at[b, order].set(first)
    return mask


def sparse_logits_gather(q_aug: jax.Array, w_aug: jax.Array,
                         cand_ids: jax.Array) -> jax.Array:
    """Reference path: random-gather W rows then batched dot.

    ``[B, d] x [m, d] x [B, C] -> [B, C]``; -1 slots get NEG_INF.
    """
    rows = w_aug[jnp.maximum(cand_ids, 0)]              # [B, C, d_aug]
    logits = jnp.einsum("bd,bcd->bc", q_aug.astype(jnp.float32),
                        rows.astype(jnp.float32))
    return jnp.where(cand_ids >= 0, logits, NEG_INF)


def sparse_logits_bucketed(q_aug: jax.Array, index: LSSIndex,
                           buckets: jax.Array, impl: str | None = None
                           ) -> tuple[jax.Array, jax.Array]:
    """Bucket-major path: one contiguous ``[P, d]`` slab per (query, table).

    Routes through the registry ``bucket_logits`` op on the flattened
    ``[S, P, d]`` slab layout (S = L * 2^K) — the jnp ref for the XLA
    path, the scalar-prefetch Pallas kernel on TPU.  Aligned storage is
    sliced back to the logical layout first.
    """
    t = index.tables
    # this unfused path hands whole slabs to bucket_logits, so widen
    # quantized storage up front (the fused lss_topk path widens in-kernel)
    wb = dequantize_slabs(*logical_slabs(
        index.w_bucketed, index.w_scale, t.table_ids.shape,
        q_aug.shape[1]))                                  # [L, 2^K, P, d]
    w_flat = wb.reshape(t.n_tables * t.n_buckets, t.capacity, wb.shape[-1])
    slab_ids = buckets + jnp.arange(
        t.n_tables, dtype=buckets.dtype)[None, :] * t.n_buckets   # [B, L]
    logits = bucket_logits(q_aug, w_flat, slab_ids, impl=impl)    # [B,L,P]
    ids = t.table_ids.reshape(-1, t.capacity)[slab_ids]           # [B,L,P]
    ids = ids.reshape(q_aug.shape[0], -1)
    logits = logits.reshape(q_aug.shape[0], -1)
    return jnp.where(ids >= 0, logits, NEG_INF), ids


class LSSForward(NamedTuple):
    """Everything Algorithm 2 produces from ONE retrieval pass.

    The serving engine ranks from ``top_logits``/``top_ids`` and computes
    its sample-size / recall metrics from ``sample_size``/``cand_ids`` —
    no second ``retrieve`` call."""

    top_logits: jax.Array        # [B, k]
    top_ids: jax.Array           # [B, k]   (-1 beyond the candidate count)
    sample_size: jax.Array       # [B]      unique neurons scored per query
    cand_ids: jax.Array          # [B, C]   retrieved ids, -1 padded


def lss_forward(q: jax.Array, index: LSSIndex, w_aug: jax.Array | None,
                top_k: int = 5, *, impl: str | None = None,
                dedup: str | None = None) -> LSSForward:
    """Full Algorithm 2 with serving metrics, single retrieval pass.

    On a bucket-major index the whole retrieve -> slab logits -> dedup ->
    top-k pipeline is one registry-dispatched ``lss_topk`` op (a single
    fused Pallas pass on TPU); ``dedup`` pins its cross-table dedup
    strategy (``quadratic`` | ``bitonic``, None = auto on C).  ``w_aug``
    is only needed for the gather path (``w_bucketed is None``), which
    keeps the XLA gather lowering.
    """
    q_aug = simhash.augment_queries(q)
    if index.w_bucketed is not None:
        t = index.tables
        out = lss_topk(q_aug, index.theta, t.table_ids, index.w_bucketed,
                       top_k=top_k, impl=impl, dedup=dedup,
                       w_scale=index.w_scale, slab_ids=index.slab_ids)
        return LSSForward(*out)
    cand_ids, _ = retrieve(q_aug, index, impl=impl)
    logits = sparse_logits_gather(q_aug, w_aug, cand_ids)
    mask = dedup_mask(cand_ids)
    logits = jnp.where(mask, logits, NEG_INF)
    top_logits, pos = jax.lax.top_k(logits, top_k)
    top_ids = jnp.take_along_axis(cand_ids, pos, axis=-1)
    top_ids = jnp.where(top_logits > NEG_INF / 2, top_ids, -1)
    return LSSForward(top_logits, top_ids, jnp.sum(mask, axis=-1), cand_ids)


def lss_predict(q: jax.Array, index: LSSIndex, w_aug: jax.Array | None,
                top_k: int = 5, *, impl: str | None = None,
                dedup: str | None = None) -> tuple[jax.Array, jax.Array]:
    """(top-k logits, top-k neuron ids) ``[B, k]`` — see ``lss_forward``."""
    out = lss_forward(q, index, w_aug, top_k, impl=impl, dedup=dedup)
    return out.top_logits, out.top_ids


# ---------------------------------------------------------------- metrics --

def label_recall(cand_ids: jax.Array, labels: jax.Array) -> jax.Array:
    """Paper's Label Retrieval Rate: fraction of true labels retrieved.

    labels: int32 ``[B, NL]`` padded with -1.
    """
    hit = (labels[:, :, None] == cand_ids[:, None, :]).any(-1)   # [B, NL]
    valid = labels >= 0
    return jnp.sum(hit & valid) / jnp.maximum(jnp.sum(valid), 1)


def precision_at_k(pred_ids: jax.Array, labels: jax.Array, k: int) -> jax.Array:
    """Standard XMC P@k: mean over samples of |top-k ∩ labels| / k."""
    topk = pred_ids[:, :k]
    hit = (topk[:, :, None] == labels[:, None, :]) & (labels >= 0)[:, None, :]
    return jnp.mean(jnp.sum(hit.any(-1) & (topk >= 0), axis=-1) / k)


def avg_sample_size(cand_ids: jax.Array) -> jax.Array:
    """Paper's Sample Size: mean #unique neurons scored per query."""
    return jnp.mean(jnp.sum(dedup_mask(cand_ids), axis=-1))
