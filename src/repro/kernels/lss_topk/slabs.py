"""Quantized slab storage for the fused ``lss_topk`` path.

The bucket-major WOL slabs (``[L, 2^K, P, d]``) are the fused kernel's
dominant DMA traffic: every query streams ``L`` hit slabs from HBM to
VMEM, so at fp32 the per-query byte count is ``L * P * (4d + 4)`` and
slab bytes — not compute — bound the candidate ceiling (see
``ops.lss_topk_vmem_bytes`` / ``lss_topk_slab_dma_bytes``).  The paper's
own framing justifies compressing them aggressively: LSS is tuned for
*label recall*, not inner-product magnitude, so the slab representation
only has to preserve which labels survive the top-k (PAPER.md §4;
PAPERS.md: anisotropic/score-aware quantization à la ScaNN preserves
exactly this).

Three storage formats, selected through the registry strategy knob
``lss_topk.slab_dtype`` (resolved like ``lss_topk.dedup`` — explicit
argument > process override > ``$REPRO_LSS_SLAB_DTYPE`` > auto, which
defaults to ``fp32``; every resolution is recorded in the registry
dispatch log):

``fp32``
    The original layout.  Exact, 4 bytes/element.

``bf16``
    Slabs cast to bfloat16, no side table.  2 bytes/element; dequantize
    is a pure ``astype`` widening.

``int8``
    Symmetric per-NEURON-row int8 (``optim.compression.quantize_int8_rows``:
    one fp32 scale per ``[d]`` row, so a slab DMA becomes an int8
    ``[P, d]`` block plus a ``[P]`` scale row).  1 byte/element + 4/d
    for scales — ~3.6x fewer slab DMA bytes at d=64, and the index for a
    10M-class WOL shrinks from ~10 GB to ~2.7 GB.

Quantization happens ONCE, at :func:`repro.core.lss.build_index` time
(and again automatically on every IUL refit — ``fit_lss`` rebuilds the
index through the same constructor).  Both the jnp ref and the Pallas
kernel then dequantize on the fly: the ref widens the whole slab tensor
before its gemm, the kernel widens each fetched ``[P, d]`` slab in VMEM
right before its ``[Bq, d] @ [d, P]`` MXU matmul.  Because dequantize is
an elementwise fp32 op (``q * scale``), both paths feed bit-identical
operand matrices to the same row-consistent gemm, so the ref /
pallas-interpret exact-equality contract of the fp32 path carries over
unchanged to every storage format (tested in ``tests/test_slab_quant.py``).

The slab LAYOUT is decided at build time too, by the impl that will serve
the index (:func:`slab_layout_for`).  ``logical`` storage is the
bucket-major ``[L, 2^K, P, d]`` tensor (``ref`` / ``pallas_interpret``).
``aligned`` storage is exactly what the TPU kernel reads (``pallas``):
slabs ``[L*2^K, P', d']`` with ``P'`` and ``d'`` rounded up to the
128-lane tile and zero-filled, ids ``[L*2^K, 1, P']`` with -1 in the
added slots, int8 scales ``[L*2^K, 1, P']`` with 0 there
(:func:`kernel_slabs`).  Serving logical storage on the TPU lays it out
in every call, which rewrites the whole slab tensor each time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import registry
from repro.optim.compression import dequantize_int8_rows, quantize_int8_rows

__all__ = [
    "SLAB_DTYPE_CHOICES", "SLAB_DTYPE_ENV_VAR", "slab_dtype_strategy",
    "resolve_slab_dtype", "slab_dtype_of", "slab_itemsize",
    "quantize_slabs", "dequantize_slabs", "lss_topk_slab_dma_bytes",
    "SLAB_LANE", "slab_layout_for", "kernel_slabs", "is_aligned",
    "logical_slabs",
]

SLAB_DTYPE_CHOICES = ("fp32", "bf16", "int8")
SLAB_DTYPE_ENV_VAR = "REPRO_LSS_SLAB_DTYPE"

_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}
_NAMES = {jnp.dtype(v): k for k, v in _DTYPES.items()}
_ITEMSIZE = {"fp32": 4, "bf16": 2, "int8": 1}

SLAB_LANE = 128     # TPU lane width: aligned storage pads P and d to it


def _auto_slab_dtype(**_ctx) -> str:
    """Auto default: fp32 — storage compression is an opt-in accuracy
    trade (unlike the dedup knob, whose choices are bit-identical)."""
    return "fp32"


slab_dtype_strategy = registry.kernel_strategy(
    "lss_topk.slab_dtype", SLAB_DTYPE_CHOICES, env_var=SLAB_DTYPE_ENV_VAR,
    auto=_auto_slab_dtype)


def resolve_slab_dtype(requested: str | None = None, **ctx) -> str:
    """Resolve the slab storage format (logged in the registry dispatch
    log as ``("lss_topk.slab_dtype", choice)``).  Called at INDEX BUILD
    time — the serving-time kernel simply consumes whatever storage the
    index holds."""
    return slab_dtype_strategy.resolve(requested, **ctx)


def slab_dtype_of(w_bucketed: jax.Array) -> str:
    """The strategy name for a slab tensor's dtype (fp32|bf16|int8)."""
    name = _NAMES.get(jnp.dtype(w_bucketed.dtype))
    if name is None:
        raise ValueError(
            f"slab dtype {w_bucketed.dtype} is not one of the "
            f"lss_topk.slab_dtype storage formats {SLAB_DTYPE_CHOICES}")
    return name


def slab_itemsize(slab_dtype: str) -> int:
    """Bytes per slab element for a storage format name."""
    return _ITEMSIZE[slab_dtype]


def quantize_slabs(w_bucketed: jax.Array, slab_dtype: str
                   ) -> tuple[jax.Array, jax.Array | None]:
    """Encode fp32 bucket-major slabs into the requested storage format.

    ``[L, 2^K, P, d] -> (slabs, scales)`` where ``scales`` is the
    per-neuron-row fp32 ``[L, 2^K, P]`` table for int8 and ``None``
    otherwise.  Empty (-1) slots are zero rows; they quantize to zero
    codes and dequantize back to exactly 0, so the "padded slots score
    logit 0, masked by id" contract of ``bucketize_weights`` holds for
    every format.
    """
    if slab_dtype == "fp32":
        return w_bucketed.astype(jnp.float32), None
    if slab_dtype == "bf16":
        return w_bucketed.astype(jnp.bfloat16), None
    if slab_dtype == "int8":
        return quantize_int8_rows(w_bucketed)
    raise ValueError(f"slab_dtype must be one of {SLAB_DTYPE_CHOICES}, "
                     f"got {slab_dtype!r}")


def dequantize_slabs(w_bucketed: jax.Array, w_scale: jax.Array | None
                     ) -> jax.Array:
    """Widen stored slabs back to fp32 (the jnp-ref side of the
    dequantize-on-the-fly contract; the kernel applies the identical
    elementwise op per fetched slab)."""
    name = slab_dtype_of(w_bucketed)
    if name == "int8":
        assert w_scale is not None, "int8 slabs need their scale table"
        return dequantize_int8_rows(w_bucketed, w_scale)
    return w_bucketed.astype(jnp.float32)


def lss_topk_slab_dma_bytes(n_tables: int, cap: int, d: int,
                            slab_dtype: str = "fp32") -> int:
    """Slab-stream HBM->VMEM bytes PER QUERY for one fused-kernel pass:
    ``L`` slab fetches of ``[P, d]`` weights + ``[P]`` int32 ids, plus a
    ``[P]`` fp32 scale row per fetch when the storage is int8.  This is
    the kernel's real per-query bottleneck once C clears the dedup
    crossover (the quantity ``benchmarks.kernels_bench`` records per
    slab_dtype)."""
    per_slab = cap * d * slab_itemsize(slab_dtype) + cap * 4
    if slab_dtype == "int8":
        per_slab += cap * 4                      # the [P] scale row
    return n_tables * per_slab


def slab_layout_for(impl: str | None = None) -> str:
    """The slab layout an index served by ``impl`` stores: ``aligned``
    for the TPU kernel (``pallas``), ``logical`` for ``ref`` and
    ``pallas_interpret``.  ``impl`` resolves as the ``lss_topk`` op's
    does: explicit > process default > ``$REPRO_KERNEL_IMPL`` >
    backend."""
    impl = registry.resolve_impl("lss_topk", impl)
    return "aligned" if impl == "pallas" else "logical"


def kernel_slabs(table_ids: jax.Array, w_bucketed: jax.Array,
                 w_scale: jax.Array | None, lane: int | None = SLAB_LANE
                 ) -> tuple[jax.Array, jax.Array, jax.Array | None]:
    """Logical storage -> the kernel's operands ``(ids [S, 1, P'],
    slabs [S, P', d'], scales [S, 1, P'] | None)``, S = L*2^K.

    With ``lane``, P and d are padded up to its multiples: the added
    slots read as empty (id -1, zero row, zero scale), so they score
    logit 0 and are masked by id like any empty slot.  ``lane=None``
    only flattens (interpret mode, whose contraction lengths must match
    the ref's)."""
    n_tables, n_buckets, cap = table_ids.shape
    d = w_bucketed.shape[-1]
    n_slabs = n_tables * n_buckets
    tids = table_ids.reshape(n_slabs, 1, cap)
    w = w_bucketed.reshape(n_slabs, cap, d)
    scales = (None if w_scale is None
              else w_scale.reshape(n_slabs, 1, cap).astype(jnp.float32))
    if lane:
        pad_p, pad_d = (-cap) % lane, (-d) % lane
        w = jnp.pad(w, ((0, 0), (0, pad_p), (0, pad_d)))
        tids = jnp.pad(tids, ((0, 0), (0, 0), (0, pad_p)),
                       constant_values=-1)
        if scales is not None:
            scales = jnp.pad(scales, ((0, 0), (0, 0), (0, pad_p)))
    return tids, w, scales


def is_aligned(w_bucketed: jax.Array, d: int) -> bool:
    """Whether stored slabs are already in the kernel's aligned layout
    for ``d``-wide queries (read from the shape alone)."""
    if w_bucketed.ndim != 3:
        return False
    _, cap, dw = w_bucketed.shape
    return cap % SLAB_LANE == 0 and dw % SLAB_LANE == 0 and dw >= d


def logical_slabs(w_bucketed: jax.Array, w_scale: jax.Array | None,
                  table_shape: tuple[int, int, int], d: int
                  ) -> tuple[jax.Array, jax.Array | None]:
    """The logical ``[L, 2^K, P, d]`` slabs (and ``[L, 2^K, P]`` scales)
    of storage in either layout: aligned storage is sliced back to the
    table's P and the queries' d, logical storage passes through."""
    if w_bucketed.ndim == 4:
        return w_bucketed, w_scale
    n_tables, n_buckets, cap = table_shape
    w = w_bucketed[:, :cap, :d].reshape(n_tables, n_buckets, cap, d)
    ws = (None if w_scale is None
          else w_scale[:, 0, :cap].reshape(n_tables, n_buckets, cap))
    return w, ws
