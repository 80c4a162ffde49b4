"""Pure-jnp oracle for the fused LSS top-k kernel.

Composes the registry ref impls of the two sub-ops (simhash_codes,
bucket_logits) with the dedup + top-k epilogue — so this oracle IS, op
for op, what ``lss_forward``'s ref path computes on a bucket-major
index.  Bit-identity between the fused kernel and ``lss_forward``
reduces to bit-identity against this function.

The dedup step honors the ``lss_topk.dedup`` strategy knob
(``quadratic`` | ``bitonic``, see ``kernels.lss_topk.dedup``): both
produce the identical first-occurrence boolean mask, so the oracle's
outputs are bit-identical across strategies — the knob only moves the
CPU cost from O(C^2) all-pairs compares to an O(C log^2 C) sorting
network, which is what keeps the ref path (the CPU-measurable serving
path) sub-quadratic in the paper's large-sample regimes.

Quantized slab storage (``lss_topk.slab_dtype``, see
``kernels.lss_topk.slabs``): when the index stores bf16/int8 slabs the
oracle widens the WHOLE slab tensor to fp32 up front
(``dequantize_slabs``) and then runs the identical pipeline.  Widening
is elementwise, so the kernel — which widens each fetched ``[P, d]``
slab in VMEM instead — sees bit-identical operand matrices and the
interpret-mode exact-equality contract holds per storage format.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bucket_logits.ref import bucket_logits_ref
from repro.kernels.lss_topk.dedup import (dedup_mask_bitonic,
                                          dedup_mask_quadratic,
                                          resolve_dedup)
from repro.kernels.lss_topk.slabs import dequantize_slabs, logical_slabs
from repro.kernels.simhash_codes.ref import simhash_codes_ref


def lss_topk_ref(q_aug: jax.Array, theta: jax.Array, table_ids: jax.Array,
                 w_bucketed: jax.Array, *, top_k: int,
                 dedup: str | None = None,
                 w_scale: jax.Array | None = None,
                 slab_ids: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Retrieve -> slab logits -> dedup mask -> top-k, all in jnp.

    Args:
      q_aug:      ``[B, d_aug]`` bias-augmented queries.
      theta:      ``[d_aug, K*L]`` hyperplanes.
      table_ids:  int32 ``[L, 2^K, P]`` bucket-major neuron ids, -1 padded.
      w_bucketed: ``[L, 2^K, P, d_aug]`` bucket-major WOL slabs
                  (fp32 | bf16 | int8 storage, see
                  ``kernels.lss_topk.slabs``).
      dedup:      ``quadratic`` | ``bitonic`` | None (strategy
                  auto-select on C = L*P).
      w_scale:    fp32 ``[L, 2^K, P]`` per-neuron-row scales (int8
                  storage only, else None).
      slab_ids:   unused: slabs stored in the TPU kernel's aligned
                  layout are sliced back to the logical one here.

    Returns:
      (top_logits [B,k] f32, top_ids [B,k] i32, sample_size [B] i32,
       cand_ids [B, L*P] i32) — the :class:`repro.core.lss.LSSForward`
      fields.
    """
    # Deferred: core.lss routes through repro.kernels at module scope, so
    # importing it here at module scope would be circular.
    from repro.core import simhash
    from repro.core.lss import NEG_INF

    n_tables, n_buckets, cap = table_ids.shape
    k_bits = n_buckets.bit_length() - 1
    bsz = q_aug.shape[0]
    # dequantize-on-the-fly, oracle form: widen once, elementwise — the
    # kernel widens per fetched slab, which is the same values
    w_bucketed = dequantize_slabs(*logical_slabs(
        w_bucketed, w_scale, table_ids.shape, q_aug.shape[1]))

    # sign(theta^T x) is scale-invariant; normalizing first matches the
    # hash definition in core.simhash (shared with the IUL relaxation).
    buckets = simhash_codes_ref(simhash.unit(q_aug), theta, k_bits,
                                n_tables)                       # [B, L]
    slab_ids = buckets + jnp.arange(
        n_tables, dtype=buckets.dtype)[None, :] * n_buckets     # [B, L]

    cand = table_ids.reshape(-1, cap)[slab_ids]                 # [B, L, P]
    cand = cand.reshape(bsz, -1)                                # [B, C]
    w_flat = w_bucketed.reshape(-1, cap, w_bucketed.shape[-1])
    logits = bucket_logits_ref(q_aug, w_flat, slab_ids)         # [B, L, P]
    logits = logits.reshape(bsz, -1)

    # an explicit dedup= arrives pre-resolved (and pre-logged) from the
    # dispatching wrapper; only resolve (and log) when called directly
    choice = (dedup if dedup is not None
              else resolve_dedup(None, n_candidates=cand.shape[-1]))
    assert choice in ("quadratic", "bitonic"), choice
    mask = (dedup_mask_quadratic(cand) if choice == "quadratic"
            else dedup_mask_bitonic(cand))
    logits = jnp.where(mask, logits, NEG_INF)
    top_logits, pos = jax.lax.top_k(logits, top_k)
    top_ids = jnp.take_along_axis(cand, pos, axis=-1)
    top_ids = jnp.where(top_logits > NEG_INF / 2, top_ids, -1)
    return top_logits, top_ids, jnp.sum(mask, axis=-1), cand
