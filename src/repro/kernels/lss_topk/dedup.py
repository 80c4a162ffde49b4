"""Cross-table dedup strategies for the ``lss_topk`` candidate set.

The fused serving pass retrieves C = L*P candidate ids per query (one
``[P]`` slot row per table) and must keep exactly the FIRST occurrence of
every non-negative id before top-k — the paper's sample-size metric and
the exact-top-k contract both hang off that mask.  Two interchangeable
algorithms implement it, selected through the registry strategy knob
``lss_topk.dedup``:

``quadratic``
    The original ``[C, C]`` all-pairs compare: an id survives iff no
    EARLIER slot holds the same id.  O(C^2) work and O(C^2) memory per
    query — unbeatable VPU shape at small C, a VMEM wall past a few
    thousand candidates.

``bitonic``
    An O(C log^2 C) bitonic sorting network over (id, original-position)
    pairs, then a single neighbor compare marks the first occurrence of
    each id run.  Carrying the original position as the tie-break key
    makes the sort stable, so "first occurrence" means exactly what the
    quadratic mask means (lower index wins) and the two strategies are
    bit-identical end to end.  The network is expressed as static lane
    rotations and selects (power-of-two stage sizes, no gathers, no
    reshapes), so the same code path serves the jnp ref (``jnp.roll``)
    and the Pallas kernel body (``pltpu.roll``).

Auto-selection (``resolve_dedup``): ``quadratic`` up to
:func:`dedup_auto_threshold` candidates, ``bitonic`` beyond.  The
default threshold of 256 is the MEASURED CPU crossover of the full ref
path (quadratic beats both the bitonic network and the pre-strategy
argsort dedup below ~256 candidates, ties at 256, and collapses 5-10x
past 512; bitonic matches or beats the old argsort everywhere above —
re-measure with ``benchmarks.kernels_bench``, which records
``crossover_c`` in ``BENCH_kernels.json``), so the auto default is
never slower than the pre-strategy ref at any C.  The VMEM budget
alone would tolerate quadratic to C ~ 1024 (~9*C^2 bytes for the
``[C, C]`` compare plus its index iotas vs ~12 MiB) — a TPU host where
the compare's VPU shape wins can retune upward with the
``REPRO_LSS_DEDUP_AUTO_C`` env var or
:func:`set_dedup_auto_threshold`; per-call ``dedup=`` arguments,
``registry.use_strategy("lss_topk.dedup", ...)``, and the
``REPRO_LSS_DEDUP`` env var override the auto-select entirely,
mirroring how kernel impls resolve.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.kernels import registry

__all__ = [
    "DEDUP_CHOICES", "DEDUP_ENV_VAR", "AUTO_THRESHOLD_ENV_VAR",
    "INT32_MAX", "dedup_strategy", "resolve_dedup", "dedup_auto_threshold",
    "set_dedup_auto_threshold", "bitonic_sort_by_id_pos",
    "dedup_mask_quadratic", "dedup_mask_bitonic", "sorted_dedup",
]

DEDUP_CHOICES = ("quadratic", "bitonic")
DEDUP_ENV_VAR = "REPRO_LSS_DEDUP"
AUTO_THRESHOLD_ENV_VAR = "REPRO_LSS_DEDUP_AUTO_C"
DEFAULT_AUTO_THRESHOLD = 256

INT32_MAX = jnp.iinfo(jnp.int32).max   # sort sentinel for padded slots

_auto_threshold: int | None = None     # programmatic override


def dedup_auto_threshold() -> int:
    """Candidate count above which auto-select switches to bitonic."""
    if _auto_threshold is not None:
        return _auto_threshold
    env = os.environ.get(AUTO_THRESHOLD_ENV_VAR)
    return int(env) if env else DEFAULT_AUTO_THRESHOLD


def set_dedup_auto_threshold(c: int | None) -> None:
    """Pin the auto-select crossover (``None`` restores env/default) —
    e.g. from the measured crossover in ``benchmarks.kernels_bench``."""
    global _auto_threshold
    _auto_threshold = c


def _auto_dedup(n_candidates: int | None = None, **_ctx) -> str:
    if n_candidates is not None and n_candidates > dedup_auto_threshold():
        return "bitonic"
    return "quadratic"


dedup_strategy = registry.kernel_strategy(
    "lss_topk.dedup", DEDUP_CHOICES, env_var=DEDUP_ENV_VAR, auto=_auto_dedup)


def resolve_dedup(requested: str | None, n_candidates: int) -> str:
    """Resolve the dedup algorithm for a C-candidate call (logged in the
    registry dispatch log as ``("lss_topk.dedup", choice)``)."""
    return dedup_strategy.resolve(requested, n_candidates=n_candidates)


# ------------------------------------------------------------ quadratic --

def dedup_mask_quadratic(ids: jax.Array) -> jax.Array:
    """First-occurrence mask via the all-pairs compare.

    ``int32 [..., C] -> bool [..., C]``: True iff ``ids[i] >= 0`` and no
    ``j < i`` holds the same id.  Materialises ``[..., C, C]``.
    """
    c = ids.shape[-1]
    eq = ids[..., :, None] == ids[..., None, :]              # [..., C, C]
    earlier = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1) < \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)       # col < row
    n_earlier = jnp.sum((eq & earlier).astype(jnp.int32), axis=-1)
    return (n_earlier == 0) & (ids >= 0)


# -------------------------------------------------------------- bitonic --

def _ceil_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n & (n - 1) else max(n, 2)


def _neighbour(a: jax.Array, s: int, ahead: jax.Array, roll) -> jax.Array:
    """``a[..., i + s]`` where ``ahead`` holds and ``a[..., i - s]``
    elsewhere (lane indices mod n), by two static lane rotations.
    Callers fold :func:`_roll_is_jnp` into ``ahead``, so the network
    does not rest on the rotation's sign convention."""
    n = a.shape[-1]
    ax = a.ndim - 1
    return jnp.where(ahead, roll(a, n - s, ax), roll(a, s, ax))


def _roll_is_jnp(lane: jax.Array, roll) -> jax.Array:
    """True (everywhere) iff ``roll`` follows ``jnp.roll``:
    ``roll(a, 1)[i] == a[i - 1]``."""
    n = lane.shape[-1]
    return roll(lane, 1, lane.ndim - 1) == (lane + n - 1) % n


def _compare_exchange(arrays: tuple[jax.Array, ...], j: int, k: int,
                      lane: jax.Array, forward: jax.Array, roll
                      ) -> tuple[jax.Array, ...]:
    """One bitonic substage: compare-exchange elements at XOR-distance
    ``j`` inside stage ``k``, ordering by the lexicographic (id, pos) key
    held in ``arrays[0:2]``.

    Lane ``i``'s partner ``i ^ j`` is ``i + j`` when bit ``j`` of ``i``
    is clear and ``i - j`` when it is set, so two static lane rotations
    and a select expose it: no gathers and no rank-changing reshapes,
    which is what lets Mosaic lay the network out on the VPU.
    """
    low = (lane & j) == 0                     # i is the pair's lower lane
    ahead = (low & forward) | (~low & ~forward)

    def partner(a):
        return _neighbour(a, j, ahead, roll)

    keys, pos = arrays[0], arrays[1]
    pk, pp = partner(keys), partner(pos)
    self_less = (keys < pk) | ((keys == pk) & (pos < pp))
    # ascending iff (i & k) == 0: there the lower lane keeps the minimum;
    # descending blocks flip it.  Keys are distinct (pos is unique).
    asc = (lane & k) == 0
    want_min = (low & asc) | (~low & ~asc)
    keep = (self_less & want_min) | (~self_less & ~want_min)
    return tuple(jnp.where(keep, a, partner(a)) for a in arrays)


def bitonic_sort_by_id_pos(ids: jax.Array, pos: jax.Array,
                           *payload: jax.Array, roll=jnp.roll
                           ) -> tuple[jax.Array, ...]:
    """Sort ``(ids, pos, *payload)`` along the last axis ascending by the
    (id, pos) pair with a bitonic network.

    The last axis must be a power of two >= 2.  Because ``pos`` values
    are distinct, the sort is a deterministic permutation — equal ids
    come out in original-position order, which is exactly the stable
    lower-index-wins contract the dedup + top-k epilogue needs.
    O(log^2 n) substages, each O(n) work, statically unrolled.
    ``roll`` is ``jnp.roll`` or, inside a Pallas kernel, ``pltpu.roll``.
    """
    n = ids.shape[-1]
    assert n >= 2 and n & (n - 1) == 0, f"need pow2 length, got {n}"
    arrays = (ids, pos) + payload
    lane = jax.lax.broadcasted_iota(jnp.int32, ids.shape, ids.ndim - 1)
    forward = _roll_is_jnp(lane, roll)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            arrays = _compare_exchange(arrays, j, k, lane, forward, roll)
            j //= 2
        k *= 2
    return arrays


def sorted_dedup(ids: jax.Array, logits: jax.Array, roll=jnp.roll
                 ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Bitonic-sort (id, pos, logit) rows and mark first occurrences.

    ``ids, logits: [..., C]`` -> ``(sorted_ids, sorted_pos, sorted_logits,
    first)`` each ``[..., n]`` with n the next power of two: padded slots
    carry ``INT32_MAX`` ids / ``pos >= C`` and are never marked first;
    ``first`` is True exactly once per distinct non-negative id — at its
    lowest original position.  Everything downstream (sample size, top-k
    with position tie-breaks) can run in the sorted domain.
    """
    c = ids.shape[-1]
    n = _ceil_pow2(c)
    lead = ids.shape[:-1]
    pos = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, (1,) * len(lead) + (n,),
                                 len(lead)), lead + (n,))
    if n != c:
        pad = [(0, 0)] * len(lead) + [(0, n - c)]
        ids = jnp.pad(ids, pad, constant_values=INT32_MAX)
        logits = jnp.pad(logits, pad, constant_values=0.0)
    sids, spos, slog = bitonic_sort_by_id_pos(ids, pos, logits, roll=roll)
    lane = jax.lax.broadcasted_iota(jnp.int32, sids.shape, len(lead))
    prev = _neighbour(sids, 1, ~_roll_is_jnp(lane, roll), roll)  # [i - 1]
    new_run = (lane == 0) | (sids != prev)
    first = new_run & (sids >= 0) & (sids != INT32_MAX)
    return sids, spos, slog, first


def dedup_mask_bitonic(ids: jax.Array) -> jax.Array:
    """First-occurrence mask via the sorting network, scattered back to
    original positions — boolean-identical to ``dedup_mask_quadratic``.

    ``int32 [B, C] -> bool [B, C]``.
    """
    bsz, c = ids.shape
    _, spos, _, first = sorted_dedup(ids, jnp.zeros_like(ids, jnp.float32))
    n = spos.shape[-1]
    b = jnp.arange(bsz)[:, None]
    return jnp.zeros((bsz, n), bool).at[b, spos].set(first)[:, :c]
