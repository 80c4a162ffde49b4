"""Fused Pallas TPU kernel: the whole LSS serving pipeline in one pass.

Per grid step — one ``[Bq, d]`` QUERY TILE, not one query:

    simhash codes for the tile (hash matmul + sign + bit-pack)
      -> double-buffered data-dependent slab DMA (bucket-major weights
         stay in HBM; only hit slabs ever reach VMEM, and table fetch
         t+1 overlaps the MXU matmul of fetch t)
      -> slab logits as [Bq, d] @ [d, P] MXU matmuls
      -> cross-table dedup (quadratic [C, C] mask or bitonic sorting
         network, per the ``lss_topk.dedup`` strategy)
      -> first-occurrence top-k

The slab index depends on the hash computed INSIDE the kernel, so the
canonical scalar-prefetch trick (``bucket_logits``) cannot express it:
instead ``w_slabs``/``table_ids`` are bound with ``memory_space=ANY`` and
fetched with ``pltpu.make_async_copy`` at a runtime-computed index — the
same manual-DMA pattern as paged attention, but through a 2-deep rotating
scratch (``w_vmem[2, P, d]``) so the fetch for slot ``s^1`` is in flight
while the matmul consumes slot ``s``.  Nothing wider than two ``[P, d]``
slabs is ever materialised, which is the point of LSS: the full head
streams ``m*d`` weights per batch, this kernel streams ``L*P*d`` per
query with no HBM round-trips for the intermediate codes or logits.

Quantized slab storage (``lss_topk.slab_dtype``, see
``kernels.lss_topk.slabs``): the slab scratch inherits the storage dtype,
so bf16 slabs halve and int8 slabs quarter the DMA bytes per fetch.  For
int8 each fetch also streams the slab's ``[P]`` fp32 scale row through a
third rotating scratch, and the kernel dequantizes IN VMEM right before
the matmul (``w.astype(f32) * scale[:, None]`` — elementwise, so the
operand matrix is bit-identical to the jnp oracle's up-front widening and
the interpret-mode exactness contract below extends to every format).

Query blocking (``grid=(ceil(B/Bq),)``) amortises per-step dispatch and
turns the slab product into an MXU-shaped ``[Bq, d] @ [d, P]`` matmul
(row b of the product is that query's logits; the other rows ride the
same MXU pass for free) instead of a degenerate ``[1, d]`` GEMV.  The
fetch schedule is shared: one double-buffered stream of ``Bq*L`` slab
copies per tile.

Bit-exactness contract (interpret mode, CPU): every fp32 reduction is
expressed so XLA lowers it to the same gemm the jnp oracle uses — XLA's
CPU gemm is row-consistent across leading-dim shapes, so slicing row b
out of the ``[Bq, d] @ [d, P]`` product is bit-identical to the ref's
einsum row (exact-equality tested across the C/B/d sweep).  ``ops.py``
skips lane padding in interpret mode so contraction lengths match the
ref, and pads B up to the tile multiple with zero rows that are sliced
off after the call.

Dedup strategies (see ``kernels.lss_topk.dedup``):

* ``quadratic`` — the original per-row ``[C, C]`` compare + original-
  order top-k.  VMEM cost grows with C^2; right answer below ~2k
  candidates.
* ``bitonic`` — sort (id, pos, logit) rows with an O(C log^2 C) network,
  mark first occurrences with one neighbor compare, then run top-k IN
  THE SORTED DOMAIN, breaking logit ties by the carried original
  position.  Because ties break on the same key and the surviving
  (logit, pos) multiset is identical, the outputs are bit-identical to
  the quadratic path — tested, not assumed.

Top-k is k passes of masked max with first-occurrence argmin-of-index,
which reproduces ``jax.lax.top_k``'s stable lower-index-first
tie-breaking exactly (k is small: 1-10 in every serving config).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lss_topk.dedup import sorted_dedup

NEG_INF = -1e30   # matches repro.core.lss.NEG_INF (kept import-free)

DEFAULT_BLOCK_Q = 8   # MXU-friendly query-tile rows per grid step

# f32 operands stay f32 on the MXU: hash signs and logits match the
# jnp oracle under jax.default_matmul_precision("highest")
_HIGHEST = jax.lax.Precision.HIGHEST


def _set_col(out, col, i, val):
    """``out[:, i] = val`` as a lane select (Mosaic has no narrow
    unaligned concatenates or scalar stores; a select lowers anywhere)."""
    return jnp.where(col == i, val, out)


def _row_to_col(x_row):
    """``[1, n] -> [n, 1]`` without a transpose: a diagonal select plus a
    lane reduction, which adds only exact zeros (Mosaic cannot cast a
    lane vector to a sublane vector)."""
    n = x_row.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(row == col, x_row, jnp.zeros((), x_row.dtype)),
                   axis=1, keepdims=True)


def _topk_quadratic_row(cand_row, logits_row, top_k):
    """Original-order dedup + top-k for one ``[1, C]`` candidate row.
    Returns (top_l [1,k], top_i [1,k], sample [1,1]).

    The mask math restates ``dedup.dedup_mask_quadratic`` in strictly
    2-D, transpose-free form: the ids' column copy comes from
    :func:`_row_to_col`, and "no earlier equal id" is a sublane
    reduction of the ``[C, C]`` compare, which lands directly in
    ``[1, C]`` row form."""
    c = cand_row.shape[1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eq = _row_to_col(cand_row) == cand_row                    # [C, C]
    n_earlier = jnp.sum((eq & (row < col)).astype(jnp.int32),
                        axis=0, keepdims=True)                # [1, C]
    valid = (n_earlier == 0) & (cand_row >= 0)                # [1, C]
    work = jnp.where(valid, logits_row, NEG_INF)
    sample = jnp.sum(valid.astype(jnp.int32), axis=1, keepdims=True)

    pos = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (1, top_k), 1)
    tl = jnp.zeros((1, top_k), jnp.float32)
    ti = jnp.zeros((1, top_k), jnp.int32)
    for i in range(top_k):                    # static unroll over k
        best = jnp.max(work, axis=1, keepdims=True)           # [1, 1]
        first = jnp.min(jnp.where(work == best, pos, c),
                        axis=1, keepdims=True)                # [1, 1]
        sel = pos == first                                    # [1, C]
        cid = jnp.sum(jnp.where(sel, cand_row, 0), axis=1,
                      keepdims=True)                          # [1, 1]
        tl = _set_col(tl, kcol, i, best)
        ti = _set_col(ti, kcol, i, jnp.where(best > NEG_INF / 2, cid, -1))
        work = jnp.where(sel, NEG_INF, work)
    return tl, ti, sample


def _topk_bitonic_tile(cand, logits, top_k):
    """Sorted-domain dedup + top-k for a whole ``[Bq, C]`` tile.
    Returns (top_l [Bq,k], top_i [Bq,k], sample [Bq,1])."""
    sids, spos, slog, first = sorted_dedup(cand, logits,
                                           roll=pltpu.roll)   # [Bq, n]
    n = sids.shape[-1]
    bq = cand.shape[0]
    sample = jnp.sum(first.astype(jnp.int32), axis=1, keepdims=True)
    work = jnp.where(first, slog, NEG_INF)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (bq, top_k), 1)
    tl = jnp.zeros((bq, top_k), jnp.float32)
    ti = jnp.zeros((bq, top_k), jnp.int32)
    for i in range(top_k):                    # static unroll over k
        best = jnp.max(work, axis=1, keepdims=True)           # [Bq, 1]
        # ties break on the carried ORIGINAL position — the exact
        # lower-index-wins contract of the quadratic path / lax.top_k
        firstpos = jnp.min(jnp.where(work == best, spos, n),
                           axis=1, keepdims=True)             # [Bq, 1]
        sel = spos == firstpos                                # [Bq, n]
        cid = jnp.sum(jnp.where(sel, sids, 0), axis=1, keepdims=True)
        tl = _set_col(tl, kcol, i, best)
        ti = _set_col(ti, kcol, i, jnp.where(best > NEG_INF / 2, cid, -1))
        work = jnp.where(sel, NEG_INF, work)
    return tl, ti, sample


def _make_kernel(k_bits: int, n_tables: int, top_k: int, cap: int,
                 block_q: int, dedup: str, quantized: bool):
    n_buckets = 2 ** k_bits

    def kernel(*refs):
        # int8 storage threads one extra HBM input (the per-row scales)
        # and one extra rotating scratch + semaphore through the ref list
        if quantized:
            (q_ref, theta_ref, pack_ref, tids_hbm, w_hbm, scales_hbm,
             top_l_ref, top_i_ref, sample_ref, cand_ref,
             w_vmem, ids_vmem, scale_vmem, sem_w, sem_i, sem_s) = refs
        else:
            (q_ref, theta_ref, pack_ref, tids_hbm, w_hbm,
             top_l_ref, top_i_ref, sample_ref, cand_ref,
             w_vmem, ids_vmem, sem_w, sem_i) = refs
            scales_hbm = scale_vmem = sem_s = None
        # ---- stage 1: simhash codes for the whole tile ----------------
        q = q_ref[...].astype(jnp.float32)                    # [Bq, d]
        # same normalization as core.simhash.unit (hash definition)
        norm = jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True))
        qn = q / jnp.maximum(norm, 1e-12)
        scores = jnp.matmul(qn, theta_ref[...].astype(jnp.float32),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32)  # [Bq, KL]
        bits = (scores > 0).astype(jnp.float32)
        packed = jnp.matmul(bits, pack_ref[...], precision=_HIGHEST,
                            preferred_element_type=jnp.float32)  # [Bq, L]
        buckets = packed.astype(jnp.int32)

        # ---- stage 2: double-buffered slab DMA + MXU logits -----------
        # One shared fetch schedule for the tile: Bq*L slab copies
        # through a 2-slot rotating scratch; copy i+1 is started before
        # copy i is consumed, so DMA overlaps the matmul.  Ids and scales
        # are stored [S, 1, P], so one slab's row is a whole-tile DMA.
        n_fetch = block_q * n_tables

        def slab_of(idx):
            b, t = divmod(idx, n_tables)
            return t * n_buckets + buckets[b, t]

        def copies(idx, slot):
            slab = slab_of(idx)
            cps = (pltpu.make_async_copy(w_hbm.at[slab], w_vmem.at[slot],
                                         sem_w.at[slot]),
                   pltpu.make_async_copy(tids_hbm.at[slab],
                                         ids_vmem.at[slot],
                                         sem_i.at[slot]))
            if quantized:
                cps += (pltpu.make_async_copy(scales_hbm.at[slab],
                                              scale_vmem.at[slot],
                                              sem_s.at[slot]),)
            return cps

        for cp in copies(0, 0):
            cp.start()
        # row b of each table's [Bq, P] tile is filled by fetch (b, t)
        # with a sublane select, so no 1-row pieces are ever concatenated
        row = jax.lax.broadcasted_iota(jnp.int32, (block_q, cap), 0)
        logit_tiles = [jnp.zeros((block_q, cap), jnp.float32)] * n_tables
        id_tiles = [jnp.zeros((block_q, cap), jnp.int32)] * n_tables
        for idx in range(n_fetch):            # static unroll over Bq*L
            slot = idx % 2
            if idx + 1 < n_fetch:
                for cp in copies(idx + 1, (idx + 1) % 2):
                    cp.start()
            for cp in copies(idx, slot):
                cp.wait()
            b, t = divmod(idx, n_tables)
            w = w_vmem[slot].astype(jnp.float32)              # [P, d]
            if quantized:
                # in-VMEM dequantize: same elementwise op as the
                # oracle's dequantize_int8_rows, so bit-identical
                w = w * _row_to_col(scale_vmem[slot])          # [P, 1]
            blk = jnp.matmul(q, w.T, precision=_HIGHEST,
                             preferred_element_type=jnp.float32)  # [Bq, P]
            here = row == b                                   # this query's
            logit_tiles[t] = jnp.where(here, blk, logit_tiles[t])
            id_tiles[t] = jnp.where(here, ids_vmem[slot], id_tiles[t])
        logits = jnp.concatenate(logit_tiles, axis=1)         # [Bq, C]
        cand = jnp.concatenate(id_tiles, axis=1)
        cand_ref[...] = cand

        # ---- stage 3+4: dedup + stable top-k --------------------------
        if dedup == "quadratic":
            rows = jax.lax.broadcasted_iota(jnp.int32, (block_q, top_k), 0)
            rows1 = jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
            tl_all = jnp.zeros((block_q, top_k), jnp.float32)
            ti_all = jnp.zeros((block_q, top_k), jnp.int32)
            s_all = jnp.zeros((block_q, 1), jnp.int32)
            for b in range(block_q):          # static unroll over the tile
                tl, ti, sample = _topk_quadratic_row(
                    cand[b:b + 1], logits[b:b + 1], top_k)
                tl_all = jnp.where(rows == b, tl, tl_all)
                ti_all = jnp.where(rows == b, ti, ti_all)
                s_all = jnp.where(rows1 == b, sample, s_all)
        else:
            tl_all, ti_all, s_all = _topk_bitonic_tile(cand, logits, top_k)
        top_l_ref[...] = tl_all
        top_i_ref[...] = ti_all
        sample_ref[...] = s_all

    return kernel


@functools.partial(jax.jit, static_argnames=("k_bits", "n_tables", "top_k",
                                             "block_q", "dedup",
                                             "vmem_limit_bytes",
                                             "interpret"))
def lss_topk_pallas(q_aug: jax.Array, theta: jax.Array, tids_flat: jax.Array,
                    w_flat: jax.Array, scales_flat: jax.Array | None = None,
                    *, k_bits: int, n_tables: int,
                    top_k: int, block_q: int = DEFAULT_BLOCK_Q,
                    dedup: str = "quadratic",
                    vmem_limit_bytes: int | None = None,
                    interpret: bool = False
                    ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused retrieve->score->top-k over ``[block_q, d]`` query tiles.

    Args:
      q_aug:     ``[B, d]`` augmented queries, B a multiple of
                 ``block_q`` (``ops.py`` pads B; pads d on TPU).
      theta:     ``[d, K*L]`` hyperplanes.
      tids_flat: int32 ``[S, 1, P]`` flattened bucket-major ids
                 (S = L*2^K; the unit axis makes one slab's ids a whole
                 tile for the DMA).
      w_flat:    ``[S, P, d]`` flattened bucket-major slabs
                 (fp32 | bf16 | int8 storage).
      scales_flat: fp32 ``[S, 1, P]`` per-neuron-row scales — required iff
                 ``w_flat`` is int8 (``lss_topk.slab_dtype = int8``).
      block_q:   query rows per grid step (``grid=(B/block_q,)``).
      dedup:     ``quadratic`` | ``bitonic`` (resolved by ``ops.py``).
      vmem_limit_bytes: scoped VMEM the kernel asks Mosaic for (None =
                 the chip's default; ``ops.py`` derives it from
                 ``lss_topk_vmem_bytes``).

    Returns:
      (top_logits [B,k], top_ids [B,k], sample [B,1], cand_ids [B, L*P]).
    """
    bsz, d = q_aug.shape
    n_slabs, cap, dw = w_flat.shape
    assert d == dw, (d, dw)
    assert n_slabs == n_tables * 2 ** k_bits, (n_slabs, n_tables, k_bits)
    assert bsz % block_q == 0, (bsz, block_q)
    kl = k_bits * n_tables
    assert theta.shape == (d, kl), (theta.shape, d, kl)
    n_cand = n_tables * cap
    assert top_k <= n_cand, (top_k, n_cand)
    assert dedup in ("quadratic", "bitonic"), dedup
    quantized = w_flat.dtype == jnp.int8
    assert quantized == (scales_flat is not None), \
        "int8 slabs require scales_flat (and only int8 slabs take one)"
    if quantized:
        assert scales_flat.shape == (n_slabs, 1, cap), scales_flat.shape
    assert tids_flat.shape == (n_slabs, 1, cap), tids_flat.shape

    # constant pack matrix: pack[t*K + j, t] = 2^j (exact in fp32)
    eye = jnp.eye(n_tables, dtype=jnp.float32)
    weights = 2.0 ** jnp.arange(k_bits, dtype=jnp.float32)
    pack = (eye[:, None, :] * weights[None, :, None]).reshape(kl, n_tables)

    in_specs = [
        pl.BlockSpec((block_q, d), lambda b: (b, 0)),
        pl.BlockSpec((d, kl), lambda b: (0, 0)),
        pl.BlockSpec((kl, n_tables), lambda b: (0, 0)),
        pl.BlockSpec(memory_space=pl.ANY),     # ids stay in HBM
        pl.BlockSpec(memory_space=pl.ANY),     # slabs stay in HBM
    ]
    scratch = [
        pltpu.VMEM((2, cap, d), w_flat.dtype),    # double-buffered
        pltpu.VMEM((2, 1, cap), jnp.int32),
    ]
    operands = [q_aug, theta, pack, tids_flat, w_flat]
    if quantized:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))  # scales too
        scratch.append(pltpu.VMEM((2, 1, cap), jnp.float32))
        operands.append(scales_flat)
    scratch += [pltpu.SemaphoreType.DMA((2,))] * (3 if quantized else 2)

    return pl.pallas_call(
        _make_kernel(k_bits, n_tables, top_k, cap, block_q, dedup,
                     quantized),
        grid=(bsz // block_q,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_q, top_k), lambda b: (b, 0)),
            pl.BlockSpec((block_q, top_k), lambda b: (b, 0)),
            pl.BlockSpec((block_q, 1), lambda b: (b, 0)),
            pl.BlockSpec((block_q, n_cand), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, top_k), jnp.float32),
            jax.ShapeDtypeStruct((bsz, top_k), jnp.int32),
            jax.ShapeDtypeStruct((bsz, 1), jnp.int32),
            jax.ShapeDtypeStruct((bsz, n_cand), jnp.int32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit_bytes),
        interpret=interpret,
    )(*operands)
