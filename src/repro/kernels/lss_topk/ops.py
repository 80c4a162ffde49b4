"""Public op: fused LSS retrieve->score->top-k, dispatched through the
kernel registry.

This is the serving hot path: ``core.lss.lss_forward`` routes every
bucket-major forward through this op, so whichever impl the registry
resolves (ref on CPU, pallas on TPU, pallas_interpret under test) is the
one that actually serves traffic.

Two registry knobs shape a call:

* ``impl`` — which implementation runs (``ref`` | ``pallas`` |
  ``pallas_interpret``), as for every op.
* ``dedup`` — which cross-table dedup algorithm every impl uses
  (``quadratic`` | ``bitonic``), resolved through the
  ``lss_topk.dedup`` strategy (auto-select on C = L*P, ``REPRO_LSS_DEDUP``
  env override; see ``kernels.lss_topk.dedup``).

A third knob, ``lss_topk.slab_dtype`` (``fp32`` | ``bf16`` | ``int8``,
see ``kernels.lss_topk.slabs``), is resolved at INDEX BUILD time rather
than per call: this op simply consumes whatever storage format
``w_bucketed`` arrives in, taking the per-neuron-row scale table via
``w_scale`` when the slabs are int8 and dequantizing on the fly inside
each impl.  The slab layout is the build's choice too: the ``pallas``
impl reads aligned storage as stored and lays out logical storage in
the call, and records which in the registry dispatch log as
``("lss_topk.slab_layout", "stored" | "padded_per_call")``.

There is no hardcoded candidate ceiling anymore: past the old ~2k
comfort limit the strategy auto-switches to the bitonic dedup, and a
warning fires only when the VMEM working set DERIVED from the actual
shape (:func:`lss_topk_vmem_bytes` over C, d, cap, Bq) exceeds the
budget a TPU core can stage.
"""

from __future__ import annotations

import functools
import os
import warnings

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.kernels.lss_topk import dedup as dedup_mod
from repro.kernels.lss_topk import slabs as slabs_mod
from repro.kernels.lss_topk.kernel import DEFAULT_BLOCK_Q, lss_topk_pallas
from repro.kernels.lss_topk.ref import lss_topk_ref
from repro.kernels.registry import kernel_op

lss_topk_op = kernel_op("lss_topk")
lss_topk_op.register_impl("ref", lss_topk_ref)

# Practical per-core VMEM budget for the kernel's working set (the full
# VMEM is ~16 MiB; leave headroom for the compiler's own staging).
VMEM_BUDGET_BYTES = 12 * 2 ** 20
# Scoped VMEM Mosaic gives a kernel unless it asks for more (16 MiB on
# v5e, whose cores hold 128 MiB).
SCOPED_VMEM_DEFAULT_BYTES = 16 * 2 ** 20

BLOCK_Q_ENV_VAR = "REPRO_LSS_BLOCK_Q"


def default_block_q() -> int:
    """Query-tile rows per grid step (env ``REPRO_LSS_BLOCK_Q``)."""
    env = os.environ.get(BLOCK_Q_ENV_VAR)
    return int(env) if env else DEFAULT_BLOCK_Q


def grid_steps(bsz: int, block_q: int | None = None) -> int:
    """Pallas grid size for a B-query call: ``ceil(B / Bq)`` query tiles
    (the pre-blocking kernel ran ``B`` steps).  Single source of truth —
    ``_pallas_impl`` sizes its grid and padding from this."""
    bq = effective_block_q(bsz, block_q)
    return -(-bsz // bq)


def effective_block_q(bsz: int, block_q: int | None = None) -> int:
    """Tile height actually used: never taller than the batch, so a
    bucket-1 decode step keeps its single-row grid instead of paying for
    seven padded rows of hash + slab traffic."""
    bq = block_q or default_block_q()
    return max(1, min(bq, bsz))


def lss_topk_vmem_bytes(n_candidates: int, d: int, cap: int, *,
                        block_q: int | None = None,
                        dedup: str = "bitonic", kl: int = 64,
                        slab_dtype: str = "fp32") -> int:
    """Estimated VMEM working set of one fused-kernel grid step.

    Counts the resident operands (theta ``[d, KL]``, pack, the query
    tile, double-buffered ``2x[P, d]`` slab + ``2x[P]`` id scratch — the
    slab scratch shrinking with the storage itemsize, plus ``2x[P]``
    fp32 scale scratch when the storage is int8), the ``[Bq, C]``
    logit/candidate tiles, and the dedup working set: ``~9*C^2`` bytes
    for the quadratic all-pairs compare (id/iota int32 pairs + the bool
    mask) vs ``~4 arrays x [Bq, pow2(C)] x 4`` bytes for the bitonic
    network (id, pos, logit, plus one merge temp).
    """
    bq = block_q or default_block_q()
    c = n_candidates
    item = slabs_mod.slab_itemsize(slab_dtype)
    fixed = 4 * (d * kl + kl * bq + bq * d)        # theta + pack + q tile
    slabs = 2 * cap * d * item + 2 * cap * 4       # double-buffered scratch
    if slab_dtype == "int8":
        slabs += 2 * cap * 4                       # fp32 scale-row scratch
    tiles = 2 * bq * c * 4                         # logits + cand
    if dedup == "quadratic":
        dedup_ws = 9 * c * c                       # eq bool + iota pair
    else:
        n_pad = 1 << max(c - 1, 1).bit_length()
        dedup_ws = 4 * bq * n_pad * 4 * 2          # 4 arrays + merge temp
    return fixed + slabs + tiles + dedup_ws


def lss_topk_vmem_limit(est_bytes: int) -> int:
    """The scoped VMEM limit the kernel requests for an estimated working
    set: the default, or the estimate plus a quarter for Mosaic's own
    staging when that is larger."""
    return max(SCOPED_VMEM_DEFAULT_BYTES, est_bytes + est_bytes // 4)


@functools.lru_cache(maxsize=None)
def _warn_vmem_exceeded(n_candidates: int, d: int, cap: int, block_q: int,
                        dedup: str, slab_dtype: str, est: float) -> None:
    """One-time (per shape) heads-up that even the selected dedup
    strategy cannot stage this shape's working set in VMEM."""
    warnings.warn(
        f"lss_topk: estimated VMEM working set {est / 2**20:.1f} MiB for "
        f"C={n_candidates}, d={d}, P={cap}, Bq={block_q}, dedup={dedup}, "
        f"slab_dtype={slab_dtype} exceeds the "
        f"~{VMEM_BUDGET_BYTES / 2**20:.0f} MiB budget; the "
        f"fused kernel will spill or fail to fit at this size. Reduce "
        f"table capacity / k_bits / block_q, quantize the slabs "
        f"(lss_topk.slab_dtype), or shard the vocabulary "
        f"(serve.heads.shard_index).", stacklevel=4)


def _check_vmem(n_candidates: int, d: int, cap: int, block_q: int,
                dedup: str, kl: int, slab_dtype: str) -> None:
    est = lss_topk_vmem_bytes(n_candidates, d, cap, block_q=block_q,
                              dedup=dedup, kl=kl, slab_dtype=slab_dtype)
    if est > VMEM_BUDGET_BYTES:
        _warn_vmem_exceeded(n_candidates, d, cap, block_q, dedup,
                            slab_dtype, est)


def _pallas_impl(q_aug: jax.Array, theta: jax.Array, table_ids: jax.Array,
                 w_bucketed: jax.Array, *, top_k: int, interpret: bool,
                 dedup: str | None = None, block_q: int | None = None,
                 w_scale: jax.Array | None = None,
                 slab_ids: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    n_tables, n_buckets, cap = table_ids.shape
    k_bits = n_buckets.bit_length() - 1
    assert 2 ** k_bits == n_buckets, n_buckets
    bsz, d = q_aug.shape
    # an explicit dedup= arrives pre-resolved from the dispatching
    # wrapper; only resolve (and log) when called directly
    choice = (dedup if dedup is not None
              else dedup_mod.resolve_dedup(None, n_candidates=n_tables * cap))
    bq = effective_block_q(bsz, block_q)
    stored = slabs_mod.is_aligned(w_bucketed, d)
    registry.record("lss_topk.slab_layout",
                    "stored" if stored else "padded_per_call")
    if stored:
        # built for this kernel (core.lss.build_index): pass it straight
        tids, w_flat, scales = slab_ids, w_bucketed, w_scale
    else:
        # logical storage: lay it out here, in every call.  TPU pads to
        # lane multiples; interpret mode runs unpadded so the fp32
        # reductions are bit-identical to the jnp oracle (see kernel.py).
        tids, w_flat, scales = slabs_mod.kernel_slabs(
            table_ids, w_bucketed, w_scale,
            lane=None if interpret else slabs_mod.SLAB_LANE)
    # Query-tile padding applies in BOTH modes (the grid is blocked
    # either way): zero rows hash to some bucket like any query, produce
    # ordinary per-row outputs, and are sliced off below — padding can
    # never reach a real query's top-k because every row's dedup + top-k
    # is row-local.  Query and theta columns pad to the slabs' width.
    cap_k, d_k = w_flat.shape[1:]        # the slab the kernel sees
    pad_b = (-bsz) % bq
    if pad_b or d_k > d:
        q_aug = jnp.pad(q_aug, ((0, pad_b), (0, d_k - d)))
    if d_k > d:
        theta = jnp.pad(theta, ((0, d_k - d), (0, 0)))
    est = lss_topk_vmem_bytes(
        n_tables * cap_k, d_k, cap_k, block_q=bq, dedup=choice,
        kl=theta.shape[1], slab_dtype=slabs_mod.slab_dtype_of(w_flat))
    top_logits, top_ids, sample, cand = lss_topk_pallas(
        q_aug, theta, tids, w_flat, scales, k_bits=k_bits,
        n_tables=n_tables, top_k=top_k, block_q=bq, dedup=choice,
        vmem_limit_bytes=lss_topk_vmem_limit(est), interpret=interpret)
    if pad_b:
        top_logits = top_logits[:bsz]
        top_ids = top_ids[:bsz]
        sample = sample[:bsz]
        cand = cand[:bsz]
    if cap_k > cap:
        cand = cand.reshape(bsz, n_tables, cap_k)[:, :, :cap]
        cand = cand.reshape(bsz, n_tables * cap)
    return top_logits, top_ids, sample[:, 0], cand


lss_topk_op.register_impl(
    "pallas", functools.partial(_pallas_impl, interpret=False))
lss_topk_op.register_impl(
    "pallas_interpret", functools.partial(_pallas_impl, interpret=True))


def lss_topk(q_aug: jax.Array, theta: jax.Array, table_ids: jax.Array,
             w_bucketed: jax.Array, *, top_k: int, impl: str | None = None,
             dedup: str | None = None, w_scale: jax.Array | None = None,
             slab_ids: jax.Array | None = None
             ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused Algorithm-2 forward over a bucket-major index.

    ``[B,d] x [d,KL] x [L,2^K,P] x [L,2^K,P,d] ->``
    ``(top_logits [B,k], top_ids [B,k], sample_size [B], cand_ids [B,L*P])``

    ``w_bucketed`` (with ``w_scale``) may instead be stored in the TPU
    kernel's aligned layout ``[L*2^K, P', d']`` with its ids in
    ``slab_ids`` ``[L*2^K, 1, P']`` (``kernels.lss_topk.slabs``); the
    ``pallas`` impl then reads it as stored, the others slice it back.
    ``table_ids`` stays the logical table either way.

    impl:    ``ref`` | ``pallas`` | ``pallas_interpret`` | None (registry
             auto-selection — see ``repro.kernels.registry``).
    dedup:   ``quadratic`` | ``bitonic`` | None (strategy auto-select on
             C = L*P — see ``repro.kernels.lss_topk.dedup``).
    w_scale: fp32 ``[L, 2^K, P]`` per-neuron-row scale table — required
             iff ``w_bucketed`` stores int8 slabs (the
             ``lss_topk.slab_dtype`` knob is resolved at index build
             time; see ``repro.kernels.lss_topk.slabs``).
    slab_ids: the aligned layout's ids; None for logical storage.
    """
    n_tables, _, capacity = table_ids.shape
    c = n_tables * capacity
    sdt = slabs_mod.slab_dtype_of(w_bucketed)
    if (sdt == "int8") != (w_scale is not None):
        raise ValueError(
            f"slab_dtype={sdt} storage and w_scale disagree: int8 slabs "
            f"require a per-neuron-row scale table, other formats forbid "
            f"one (got w_scale={'set' if w_scale is not None else 'None'})")
    choice = dedup_mod.resolve_dedup(dedup, n_candidates=c)
    bq = effective_block_q(q_aug.shape[0])
    _check_vmem(c, q_aug.shape[1], capacity, bq, choice, theta.shape[1],
                sdt)
    return lss_topk_op(q_aug, theta, table_ids, w_bucketed, top_k=top_k,
                       dedup=choice, w_scale=w_scale, slab_ids=slab_ids,
                       impl=impl)
