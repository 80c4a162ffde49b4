"""Fused LSS retrieve->score->top-k: the serving hot path as ONE op.

Layout: ``kernel.py`` (the Pallas TPU pass), ``ref.py`` (the jnp
oracle), ``ops.py`` (registry dispatch + VMEM accounting), ``dedup.py``
(the ``lss_topk.dedup`` strategy), ``slabs.py`` (the
``lss_topk.slab_dtype`` storage strategy).

Invariants this package maintains — everything downstream (core.lss,
serve.heads, the engine's jitted steps) leans on them:

* **Oracle identity.** ``ref.lss_topk_ref`` composes the registered ref
  impls of the sub-ops, so it IS what ``lss_forward``'s ref path
  computes; pallas-interpret output is bit-identical to it for every
  (dedup, slab_dtype) combination, because interpret mode skips lane
  padding and both paths feed the same row-consistent CPU gemm the same
  fp32 operands (quantized storage dequantizes ELEMENTWISE before the
  gemm on both sides).
* **Static shapes.** Outputs are ``[B, k]`` / ``[B, L*P]`` with -1
  padding; duplicates are masked, never compacted.  Batch padding rows
  are row-local and sliced off, so they can never leak into a real
  query's top-k.
* **Storage is the index's choice.** ``slab_dtype`` resolves at index
  BUILD time (``core.lss.build_index``); this op consumes whatever
  format ``w_bucketed`` arrives in and requires ``w_scale`` iff it is
  int8.  DMA/VMEM cost helpers (``lss_topk_vmem_bytes``,
  ``lss_topk_slab_dma_bytes``) take the format so capacity planning
  reflects the real byte traffic.  The slab layout is chosen at build
  too, by the impl that will serve: aligned (the kernel's own operands)
  for ``pallas``, logical for the others (``slabs.slab_layout_for``).
"""

from repro.kernels.lss_topk.dedup import (dedup_auto_threshold,
                                          set_dedup_auto_threshold)
from repro.kernels.lss_topk.ops import (grid_steps, lss_topk,
                                        lss_topk_vmem_bytes)
from repro.kernels.lss_topk.slabs import (SLAB_DTYPE_CHOICES,
                                          lss_topk_slab_dma_bytes,
                                          quantize_slabs, dequantize_slabs,
                                          resolve_slab_dtype, slab_dtype_of)
__all__ = ["lss_topk", "grid_steps", "lss_topk_vmem_bytes",
           "dedup_auto_threshold", "set_dedup_auto_threshold",
           "SLAB_DTYPE_CHOICES", "lss_topk_slab_dma_bytes",
           "quantize_slabs", "dequantize_slabs", "resolve_slab_dtype",
           "slab_dtype_of"]
