"""Pluggable WOL head protocol shared by the score and decode paths.

A *head* is a pure function ``q [B, d] -> HeadOutput`` ranking the wide
output layer for a batch of query embeddings.  Three implementations:

  * ``full``         — exact ``q @ W.T + b`` then top-k (the baseline the
    paper speeds up).
  * ``lss``          — Algorithm 2 over a fitted :class:`LSSIndex`
    (single retrieval pass; sample size comes from the same pass).
  * ``lss-sharded``  — the vocab-sharded index from ``core.sharded``:
    shard-local retrieve + top-k, O(TP*k) all-gather, global top-k.

All heads return the same :class:`HeadOutput`, so the engine's batcher,
metrics, and the LM decode loop are head-agnostic.

Every head also carries its arrays as ``head.operands`` and the
operand-taking form ``head.with_operands(q, *operands)``.  Jitted steps
pass the operands as arguments: an array a jitted function closes over
is baked into the program as a constant (GB-scale for a full-vocab WOL,
and again in every compile-cache entry), and a multi-process jit cannot
close over arrays that span other hosts at all.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.lss import NEG_INF, LSSConfig, LSSIndex, lss_forward
from repro.core.sharded import (build_local_index, make_multihost_predict,
                                make_sharded_predict)
from repro.core.tables import LSSTables

__all__ = ["HeadOutput", "HEAD_KINDS", "make_full_head", "make_lss_head",
           "make_sharded_lss_head", "make_multihost_lss_head",
           "shard_index"]

HEAD_KINDS = ("full", "lss", "lss-sharded")


class HeadOutput(NamedTuple):
    """What every head returns for a query batch."""

    logits: jax.Array            # [B, k] top-k scores
    ids: jax.Array               # [B, k] top-k neuron ids (-1 = none)
    sample_size: jax.Array       # [B]    neurons actually scored
    cand_ids: jax.Array | None   # [B, C] retrieved set (None: full/sharded)


def _with_operands(with_operands: Callable, operands: tuple
                   ) -> Callable[[jax.Array], HeadOutput]:
    """The head ``q -> with_operands(q, *operands)``, with both parts
    exposed for jitted steps (see the module docstring)."""

    def head(q: jax.Array) -> HeadOutput:
        return with_operands(q, *operands)

    head.operands = operands
    head.with_operands = with_operands
    return head


def make_full_head(w: jax.Array, b: jax.Array, top_k: int
                   ) -> Callable[[jax.Array], HeadOutput]:
    """Exact WOL: every neuron is scored (sample size == m)."""
    m = w.shape[0]

    def with_operands(q: jax.Array, w: jax.Array, b: jax.Array
                      ) -> HeadOutput:
        logits = q.astype(jnp.float32) @ w.T.astype(jnp.float32) + b
        top, ids = jax.lax.top_k(logits, top_k)
        return HeadOutput(top, ids,
                          jnp.full((q.shape[0],), m, jnp.int32), None)

    return _with_operands(with_operands, (w, b))


def make_lss_head(index: LSSIndex, w_aug: jax.Array | None, top_k: int,
                  impl: str | None = None, dedup: str | None = None
                  ) -> Callable[[jax.Array], HeadOutput]:
    """Algorithm 2 over one fitted index (single-device).

    ``impl`` pins the kernel-registry implementation serving the path
    (``ref`` | ``pallas`` | ``pallas_interpret``; None = backend auto);
    ``dedup`` pins the cross-table dedup strategy (``quadratic`` |
    ``bitonic``; None = auto-select on the candidate count).
    """

    def with_operands(q: jax.Array, index: LSSIndex,
                      w_aug: jax.Array | None) -> HeadOutput:
        out = lss_forward(q.astype(jnp.float32), index, w_aug, top_k,
                          impl=impl, dedup=dedup)
        return HeadOutput(out.top_logits, out.top_ids, out.sample_size,
                          out.cand_ids)

    return _with_operands(with_operands, (index, w_aug))


def _mask_index_tail(index: LSSIndex, n_valid: int) -> LSSIndex:
    """Remove local row ids >= ``n_valid`` (vocab padding) from a shard's
    tables: their slots become -1 and their slab rows zero, so padded
    neurons are simply never retrieved.  Works on either slab layout:
    the slab rows are masked by the ids of their own layout."""
    t = index.tables
    ids = jnp.where(t.table_ids < n_valid, t.table_ids, -1)
    tables = LSSTables(ids, t.n_dropped, t.k_bits, t.n_tables, t.capacity)
    slab_ids = index.slab_ids
    if slab_ids is not None:
        slab_ids = jnp.where(slab_ids < n_valid, slab_ids, -1)
    keep = (ids if slab_ids is None else slab_ids) >= 0
    wb = index.w_bucketed
    if wb is not None:
        # zeroing works for every slab_dtype: an int8 zero code (and a
        # zeroed scale) dequantizes to exactly 0, same as fp32/bf16
        wb = jnp.where(keep.reshape(wb.shape[:-1])[..., None], wb,
                       jnp.zeros_like(wb))
    ws = index.w_scale
    if ws is not None:
        # pad rows carry the NEG_INF sentinel bias, so their per-row
        # scale is a huge garbage value; mask it like the weight rows so
        # a masked slot is all-zero in BOTH leaves (0 * scale is already
        # exactly 0 in fp32, but interpret-mode buffers and dumps must
        # not carry the sentinel through)
        ws = jnp.where(keep, ws, jnp.zeros_like(ws))
    return LSSIndex(index.theta, tables, wb, ws, slab_ids)


def shard_index(w_aug: jax.Array, theta: jax.Array, cfg: LSSConfig,
                n_shards: int, *, shard_range: tuple[int, int] | None = None,
                m_total: int | None = None, impl: str | None = None):
    """Split the WOL rows into ``n_shards`` contiguous vocab shards, build
    one local index per shard, and stack the leaves ([n_built, ...]).

    When ``m % n_shards != 0`` the rows are padded up to the next multiple
    and the padded ids are masked out of the final shard's tables
    (:func:`_mask_index_tail`), so a padded neuron can never be retrieved
    and arbitrary vocab sizes shard without changing any real query's
    result.  The pad rows carry a NEG_INF bias column purely as a
    sentinel for humans inspecting ``w_stack`` dumps — queries are
    augmented with 0, so a bias never reaches a logit; the table masking
    is what excludes padding, not the sentinel.

    ``shard_range=(lo, hi)`` builds ONLY shards [lo, hi): ``w_aug`` then
    holds just the global rows those shards cover —
    ``[lo * m_local, min(hi * m_local, m_total))`` — and ``m_total``
    (the full vocab size) is required for the pad/mask math.  This is
    the multi-host build path: each process constructs the shards it
    addresses from its own row slice and no process ever materializes
    the full ``[m, d]`` weight.  The per-shard indexes (including the
    int8 ``w_scale`` leaf) are bit-identical to the same shards of a
    full-range build.  ``impl`` picks the shards' slab layout, as in
    ``core.lss.build_index``.

    Returns (stacked_index, stacked_w_aug or None, m_local).
    """
    if shard_range is None:
        if m_total is not None and m_total != w_aug.shape[0]:
            raise ValueError(f"m_total={m_total} disagrees with "
                             f"w_aug rows {w_aug.shape[0]}")
        m_total = w_aug.shape[0]
        shard_range = (0, n_shards)
    elif m_total is None:
        raise ValueError("shard_range requires m_total (the FULL vocab "
                         "size; w_aug holds only the range's rows)")
    lo, hi = shard_range
    if not (0 <= lo < hi <= n_shards):
        raise ValueError(f"shard_range {shard_range} outside "
                         f"[0, {n_shards})")
    m = m_total
    m_pad = -(-m // n_shards) * n_shards
    m_local = m_pad // n_shards
    row0 = lo * m_local
    n_rows_need = min(hi * m_local, m) - row0
    if w_aug.shape[0] != n_rows_need:
        raise ValueError(
            f"shard_range {shard_range} of m={m} needs rows "
            f"[{row0}, {row0 + n_rows_need}) = {n_rows_need} rows, "
            f"got {w_aug.shape[0]}")
    if hi * m_local > row0 + n_rows_need:         # padded vocab tail
        pad_rows = jnp.zeros((hi * m_local - row0 - n_rows_need,
                              w_aug.shape[-1]), w_aug.dtype)
        pad_rows = pad_rows.at[:, -1].set(NEG_INF)  # sentinel bias column
        w_aug = jnp.concatenate([w_aug, pad_rows], axis=0)
    locals_ = []
    for i in range(lo, hi):
        idx = build_local_index(
            w_aug[(i - lo) * m_local:(i - lo + 1) * m_local], theta, cfg,
            impl=impl)
        n_valid = min(max(m - i * m_local, 0), m_local)
        if n_valid < m_local:
            idx = _mask_index_tail(idx, n_valid)
        locals_.append(idx)
    stack = jax.tree.map(lambda *xs: jnp.stack(xs), *locals_)
    w_stack = None
    if not cfg.use_bucket_major:
        w_stack = w_aug.reshape(hi - lo, m_local, w_aug.shape[-1])
    return stack, w_stack, m_local


def make_sharded_lss_head(index_stack, w_stack, mesh, cfg: LSSConfig,
                          m_local: int, top_k: int,
                          model_axis: str = "model",
                          impl: str | None = None,
                          dedup: str | None = None
                          ) -> Callable[[jax.Array], HeadOutput]:
    """Vocab-sharded Algorithm 2 (sample size psum'd across shards).

    ``cand_ids`` is None: the retrieved sets live shard-local and only the
    O(TP*k) winners cross the interconnect — recall metrics fall back to
    the top-k set.
    """
    fwd = make_sharded_predict(mesh, model_axis, cfg, m_local, top_k,
                               with_aux=True, impl=impl, dedup=dedup)

    def with_operands(q: jax.Array, index_stack, w_stack) -> HeadOutput:
        logits, ids, sample = fwd(q.astype(jnp.float32), index_stack,
                                  w_stack)
        return HeadOutput(logits, ids, sample, None)

    return _with_operands(with_operands, (index_stack, w_stack))


def make_multihost_lss_head(index_stack, w_stack, mesh, cfg: LSSConfig,
                            m_local: int, top_k: int,
                            host_axis: str = "host",
                            model_axis: str = "model",
                            impl: str | None = None,
                            dedup: str | None = None
                            ) -> Callable[[jax.Array], HeadOutput]:
    """:func:`make_sharded_lss_head` over a multi-process (host, model)
    mesh: per-shard retrieve, hierarchical O(hosts*k) cross-host merge
    (``core.sharded.make_multihost_predict``), sample size psum'd over
    the whole fleet.  ``index_stack`` leaves are GLOBAL arrays sharded
    ``P((host_axis, model_axis))`` on the leading [n_shards] dim — build
    them with ``shard_index(..., shard_range=...)`` +
    ``jax.make_array_from_process_local_data``.
    """
    fwd = make_multihost_predict(mesh, host_axis, model_axis, cfg,
                                 m_local, top_k, with_aux=True,
                                 impl=impl, dedup=dedup)

    def with_operands(q: jax.Array, index_stack, w_stack) -> HeadOutput:
        logits, ids, sample = fwd(q.astype(jnp.float32), index_stack,
                                  w_stack)
        return HeadOutput(logits, ids, sample, None)

    return _with_operands(with_operands, (index_stack, w_stack))
