"""Pallas TPU kernels (validated in interpret mode on CPU).

Each kernel ships as <name>/{kernel.py, ops.py, ref.py}: the pallas_call
with explicit BlockSpec tiling, the public wrapper, and the pure-jnp
oracle.  Implementations register on the dispatch registry
(``repro.kernels.registry``): selection is automatic by backend (pallas
on TPU, ref elsewhere), overridable per call (``impl=``), per process
(``registry.set_default_impl`` / ``use_impl``), or via the
``REPRO_KERNEL_IMPL`` environment variable.

Ops can also expose *strategy* knobs — algorithm choices every impl
honors, resolved the same way (explicit arg > ``use_strategy`` > env >
auto-select on shape): ``lss_topk.dedup`` picks the cross-table dedup
(``quadratic`` below the measured C crossover, ``bitonic`` above; see
``repro.kernels.lss_topk.dedup``); ``lss_topk.slab_dtype`` picks the
bucket-major slab storage format (``fp32`` | ``bf16`` | ``int8``,
resolved once at index build time; see ``repro.kernels.lss_topk.slabs``).
"""
from repro.kernels import registry
from repro.kernels.simhash_codes import simhash_codes
from repro.kernels.bucket_logits import bucket_logits
from repro.kernels.lss_topk import lss_topk
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.kv_write import kv_write
__all__ = ["registry", "simhash_codes", "bucket_logits", "lss_topk",
           "moe_gmm", "kv_write"]
