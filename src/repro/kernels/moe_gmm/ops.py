"""Public op: grouped matrix product over group-sorted rows, dispatched
through the kernel registry (``ref`` | ``pallas`` | ``pallas_interpret``).

``moe_gmm(lhs [M, K], rhs [L, G, K, N], group_sizes [G], layer=l) ->
fp32 [M, N]``: row ``r`` of group ``g`` (the rows are sorted by group)
is ``lhs[r] @ rhs[l, g]``, accumulated in fp32 from the operands' dtype;
rows past ``sum(group_sizes)`` are zero.  ``rhs`` holds the matrices of
every layer and the call reads layer ``l``'s where they lie.  Every row
is computed alone, so a row's result does not depend on which other
rows share the call.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_gmm.kernel import moe_gmm_pallas
from repro.kernels.moe_gmm.ref import moe_gmm_ref
from repro.kernels.registry import kernel_op

moe_gmm_op = kernel_op("moe_gmm")
moe_gmm_op.register_impl("ref", moe_gmm_ref)

# largest m tile; bf16 rows pack 16 to a sublane tile
TM_MAX, TM_ALIGN = 128, 16
# a weight block of about this many bytes per grid step: large enough
# that the step's fixed cost hides behind its DMA
BLOCK_BYTES = 3 * 2 ** 20
LANE = 128


def _tiles(dim: int) -> list[int]:
    """Block sizes along one dimension: the multiples of 128 that divide
    it, and the whole dimension (a block may always span it)."""
    return sorted({t for t in range(LANE, dim, LANE) if dim % t == 0}
                  | {dim})


def moe_gmm_tiling(m: int, k: int, n: int, itemsize: int
                   ) -> tuple[int, int, int]:
    """``(tm, tk, tn)`` for an ``[m, k] x [G, k, n]`` call (m already a
    multiple of ``tm``): one m tile of up to 128 rows, so a decode step's
    few rows per expert read each expert's weights once; and the largest
    weight block within ``BLOCK_BYTES`` (the smallest one where none
    fits), the longer k block on a tie."""
    tm = min(TM_MAX, m)
    blocks = [(tk * tn, tk, tn) for tk in _tiles(k) for tn in _tiles(n)]
    fit = [b for b in blocks if b[0] * itemsize <= BLOCK_BYTES]
    _, tk, tn = max(fit) if fit else min(blocks)
    return tm, tk, tn


def vmem_bytes(tiling: tuple[int, int, int], itemsize: int) -> int:
    """The kernel's VMEM working set: double-buffered lhs and weight
    blocks and fp32 output block, and the fp32 accumulator."""
    tm, tk, tn = tiling
    return (2 * (tm * tk + tk * tn) * itemsize + 2 * tm * tn * 4
            + tm * tn * 4)


def _pallas_impl(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                 layer: jax.Array, *, interpret: bool) -> jax.Array:
    m, k = lhs.shape
    n = rhs.shape[3]
    align = TM_ALIGN if m <= TM_MAX else TM_MAX
    m_pad = -(-m // align) * align
    if m_pad > m:
        lhs = jnp.pad(lhs, ((0, m_pad - m), (0, 0)))
    item = jnp.dtype(rhs.dtype).itemsize
    tiling = moe_gmm_tiling(m_pad, k, n, item)
    est = vmem_bytes(tiling, item)
    out = moe_gmm_pallas(lhs, rhs, group_sizes, layer, tiling=tiling,
                         vmem_limit_bytes=max(16 * 2 ** 20, est + est // 2),
                         interpret=interpret)
    rows = jnp.arange(m)[:, None]
    return jnp.where(rows < jnp.sum(group_sizes), out[:m], 0.0)


moe_gmm_op.register_impl(
    "pallas", functools.partial(_pallas_impl, interpret=False))
moe_gmm_op.register_impl(
    "pallas_interpret", functools.partial(_pallas_impl, interpret=True))


def moe_gmm(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
            layer: jax.Array | int, impl: str | None = None) -> jax.Array:
    """``[M, K] x [L, G, K, N] x int32 [G] -> fp32 [M, N]`` over rows
    sorted by group, the groups using ``rhs[layer]`` (``layer`` an int32
    scalar, traced or not; see the module text).

    impl: ``ref`` | ``pallas`` | ``pallas_interpret`` | None (registry
    auto-selection — see ``repro.kernels.registry``).
    """
    if lhs.dtype != rhs.dtype:
        raise ValueError(f"lhs {lhs.dtype} and rhs {rhs.dtype} differ")
    return moe_gmm_op(lhs, rhs, group_sizes.astype(jnp.int32),
                      jnp.asarray(layer, jnp.int32).reshape(1), impl=impl)
