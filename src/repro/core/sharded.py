"""Vocab-sharded LSS: the distributed serving form of the paper's index.

Each shard of the model axis owns m/TP contiguous WOL neurons and builds an
independent LSS index over them (theta is replicated — hyperplanes are tiny).
Per query:

    shard-local retrieve -> local sparse logits -> local top-k
    -> all-gather k candidates per shard (O(TP*k) per query, NOT O(m))
    -> global top-k

This replaces the paper's "embarrassingly parallel over CPU threads" claim
with "embarrassingly parallel over vocab shards" and makes the WOL head's
communication volume independent of vocabulary size.

Quantized slab storage composes transparently: ``LSSIndex.w_scale`` is
an ordinary pytree leaf, so per-shard int8 indexes stack, shard over the
model axis, and flow through shard_map exactly like the fp32 slabs —
nothing here is storage-format aware.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.lss import LSSConfig, LSSIndex, build_index, lss_forward
from repro.utils import compat

__all__ = ["build_local_index", "local_topk", "sharded_lss_predict",
           "sharded_lss_forward", "make_sharded_predict",
           "hierarchical_topk_merge", "multihost_lss_predict",
           "multihost_lss_forward", "make_multihost_predict"]


def build_local_index(w_aug_local: jax.Array, theta: jax.Array,
                      cfg: LSSConfig, *, impl: str | None = None
                      ) -> LSSIndex:
    """Build the index for this shard's rows (call inside shard_map or on
    pre-split host arrays). Neuron ids inside are LOCAL row indices;
    ``impl`` picks the slab layout (``core.lss.build_index``)."""
    return build_index(w_aug_local, theta, cfg, impl=impl)


def local_topk(q: jax.Array, index: LSSIndex, w_aug_local: jax.Array | None,
               k: int, with_aux: bool = False, impl: str | None = None,
               dedup: str | None = None):
    """Shard-local Algorithm 2 returning exactly-k (logits, local ids).

    Delegates to ``lss_forward`` (registry-dispatched; the fused Pallas
    pass on a bucket-major index), so shard-local slots fewer than k read
    -1 rather than an arbitrary duplicate id that would survive the
    global all-gather.  With ``with_aux`` also returns the per-query
    local sample size from the SAME retrieval pass.
    """
    out = lss_forward(q, index, w_aug_local, k, impl=impl, dedup=dedup)
    if with_aux:
        return out.top_logits, out.top_ids, out.sample_size
    return out.top_logits, out.top_ids


def sharded_lss_predict(q: jax.Array, index: LSSIndex,
                        w_aug_local: jax.Array | None, *, k: int,
                        axis_name: str, m_local: int,
                        impl: str | None = None, dedup: str | None = None
                        ) -> tuple[jax.Array, jax.Array]:
    """Body to run INSIDE shard_map: q replicated, index/w shard-local.

    Returns global (top-k logits, top-k GLOBAL neuron ids), replicated.
    """
    logits, ids = local_topk(q, index, w_aug_local, k,
                             impl=impl, dedup=dedup)            # [B, k]
    offset = jax.lax.axis_index(axis_name) * m_local
    gids = jnp.where(ids >= 0, ids + offset, -1)
    all_logits = jax.lax.all_gather(logits, axis_name, axis=1)  # [B, TP, k]
    all_ids = jax.lax.all_gather(gids, axis_name, axis=1)
    all_logits = all_logits.reshape(q.shape[0], -1)
    all_ids = all_ids.reshape(q.shape[0], -1)
    top_logits, pos = jax.lax.top_k(all_logits, k)
    top_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
    return top_logits, top_ids


def sharded_lss_forward(q: jax.Array, index: LSSIndex,
                        w_aug_local: jax.Array | None, *, k: int,
                        axis_name: str, m_local: int,
                        impl: str | None = None, dedup: str | None = None
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``sharded_lss_predict`` + per-query GLOBAL sample size (psum of the
    shard-local unique-candidate counts) from the single retrieval pass."""
    logits, ids, local_sample = local_topk(q, index, w_aug_local, k,
                                           with_aux=True, impl=impl,
                                           dedup=dedup)
    offset = jax.lax.axis_index(axis_name) * m_local
    gids = jnp.where(ids >= 0, ids + offset, -1)
    all_logits = jax.lax.all_gather(logits, axis_name, axis=1)  # [B, TP, k]
    all_ids = jax.lax.all_gather(gids, axis_name, axis=1)
    all_logits = all_logits.reshape(q.shape[0], -1)
    all_ids = all_ids.reshape(q.shape[0], -1)
    top_logits, pos = jax.lax.top_k(all_logits, k)
    top_ids = jnp.take_along_axis(all_ids, pos, axis=-1)
    sample = jax.lax.psum(local_sample, axis_name)              # [B]
    return top_logits, top_ids, sample


def hierarchical_topk_merge(logits: jax.Array, gids: jax.Array, k: int, *,
                            model_axis: str, host_axis: str, n_hosts: int
                            ) -> tuple[jax.Array, jax.Array]:
    """Two-stage top-k merge for a (host, model) mesh.

    Stage 1 all-gathers the k candidates per shard over the fast
    intra-host ``model_axis`` and reduces to k per host; stage 2
    all-gathers only those k per host over the slow ``host_axis``, so
    cross-host traffic is O(n_hosts * k) per query — independent of both
    m and the per-host shard count.

    Bit-identical to the flat single-stage merge: ``jax.lax.top_k`` is
    stable (ties resolve to the lowest position), shard blocks are
    host-contiguous in the gather order, and every sub-k shard slot
    carries (NEG_INF, -1), so any candidate the intra-host stage drops
    already had k better-or-equal-earlier candidates on its own host and
    could never enter the flat global top-k either.  With ``n_hosts == 1``
    stage 2 is skipped and this IS the flat merge.
    """
    b = logits.shape[0]
    all_logits = jax.lax.all_gather(logits, model_axis, axis=1)
    all_ids = jax.lax.all_gather(gids, model_axis, axis=1)
    host_logits, pos = jax.lax.top_k(all_logits.reshape(b, -1), k)
    host_ids = jnp.take_along_axis(all_ids.reshape(b, -1), pos, axis=-1)
    if n_hosts == 1:
        return host_logits, host_ids
    x_logits = jax.lax.all_gather(host_logits, host_axis, axis=1)
    x_ids = jax.lax.all_gather(host_ids, host_axis, axis=1)
    top_logits, pos = jax.lax.top_k(x_logits.reshape(b, -1), k)
    top_ids = jnp.take_along_axis(x_ids.reshape(b, -1), pos, axis=-1)
    return top_logits, top_ids


def _global_shard_ids(ids: jax.Array, *, model_axis: str, host_axis: str,
                      shards_per_host: int, m_local: int) -> jax.Array:
    shard = (jax.lax.axis_index(host_axis) * shards_per_host
             + jax.lax.axis_index(model_axis))
    return jnp.where(ids >= 0, ids + shard * m_local, -1)


def multihost_lss_predict(q: jax.Array, index: LSSIndex,
                          w_aug_local: jax.Array | None, *, k: int,
                          model_axis: str, host_axis: str, n_hosts: int,
                          shards_per_host: int, m_local: int,
                          impl: str | None = None, dedup: str | None = None
                          ) -> tuple[jax.Array, jax.Array]:
    """``sharded_lss_predict`` for a (host, model) mesh: shard-local
    retrieve + top-k, then the hierarchical merge.  Global neuron id =
    (host * shards_per_host + model) * m_local + local id."""
    logits, ids = local_topk(q, index, w_aug_local, k,
                             impl=impl, dedup=dedup)
    gids = _global_shard_ids(ids, model_axis=model_axis,
                             host_axis=host_axis,
                             shards_per_host=shards_per_host,
                             m_local=m_local)
    return hierarchical_topk_merge(logits, gids, k, model_axis=model_axis,
                                   host_axis=host_axis, n_hosts=n_hosts)


def multihost_lss_forward(q: jax.Array, index: LSSIndex,
                          w_aug_local: jax.Array | None, *, k: int,
                          model_axis: str, host_axis: str, n_hosts: int,
                          shards_per_host: int, m_local: int,
                          impl: str | None = None, dedup: str | None = None
                          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``multihost_lss_predict`` + global per-query sample size (psum
    over BOTH mesh axes) from the single retrieval pass."""
    logits, ids, local_sample = local_topk(q, index, w_aug_local, k,
                                           with_aux=True, impl=impl,
                                           dedup=dedup)
    gids = _global_shard_ids(ids, model_axis=model_axis,
                             host_axis=host_axis,
                             shards_per_host=shards_per_host,
                             m_local=m_local)
    top_logits, top_ids = hierarchical_topk_merge(
        logits, gids, k, model_axis=model_axis, host_axis=host_axis,
        n_hosts=n_hosts)
    sample = jax.lax.psum(local_sample, (host_axis, model_axis))
    return top_logits, top_ids, sample


def make_multihost_predict(mesh: jax.sharding.Mesh, host_axis: str,
                           model_axis: str, cfg: LSSConfig, m_local: int,
                           k: int, with_aux: bool = False,
                           impl: str | None = None,
                           dedup: str | None = None):
    """:func:`make_sharded_predict` for a 2-axis (host, model) mesh.

    Stacked per-shard pytrees carry a leading [n_shards] dim sharded
    over BOTH axes (``P((host_axis, model_axis))``); shard s lives on
    host ``s // shards_per_host`` — build the stack with
    ``serve.heads.shard_index(..., shard_range=...)`` plus
    ``jax.make_array_from_process_local_data`` so no process materializes remote
    shards.  q and the outputs are replicated.  On a mesh whose host
    axis is 1 the merge reduces to the flat single-stage path
    bit-identically.
    """
    n_hosts = mesh.shape[host_axis]
    shards_per_host = mesh.shape[model_axis]
    body = partial(
        multihost_lss_forward if with_aux else multihost_lss_predict,
        k=k, model_axis=model_axis, host_axis=host_axis, n_hosts=n_hosts,
        shards_per_host=shards_per_host, m_local=m_local, impl=impl,
        dedup=dedup)
    stack_spec = P((host_axis, model_axis))

    def unstacked_body(q, index_stack, w_stack):
        index = jax.tree.map(lambda x: x[0], index_stack)
        w = None if w_stack is None else w_stack[0]
        return body(q, index, w)

    out_specs = (P(), P(), P()) if with_aux else (P(), P())

    def fn(q, index_stack, w_stack=None):
        in_specs = (
            P(),
            jax.tree.map(lambda _: stack_spec, index_stack),
            None if w_stack is None
            else jax.tree.map(lambda _: stack_spec, w_stack),
        )
        mapped = compat.shard_map(
            unstacked_body, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs)
        return mapped(q, index_stack, w_stack)

    return fn


def make_sharded_predict(mesh: jax.sharding.Mesh, model_axis: str,
                         cfg: LSSConfig, m_local: int, k: int,
                         batch_axis: str | None = None,
                         with_aux: bool = False,
                         impl: str | None = None,
                         dedup: str | None = None):
    """Wrap the sharded predictor in shard_map for the given mesh.

    Expects stacked per-shard pytrees: index leaves with a leading [TP] dim
    sharded over ``model_axis``; q sharded over ``batch_axis`` (or
    replicated).  Returns a function (q, stacked_index, w_local_stack|None)
    -> (logits [B,k], ids [B,k]) — plus sample size [B] if ``with_aux``.
    ``impl`` pins the registry kernel impl for the shard-local retrieval;
    ``dedup`` its cross-table dedup strategy (quadratic | bitonic).
    """
    qspec = P(batch_axis) if batch_axis else P()
    body = partial(sharded_lss_forward if with_aux else sharded_lss_predict,
                   k=k, axis_name=model_axis, m_local=m_local, impl=impl,
                   dedup=dedup)

    def unstacked_body(q, index_stack, w_stack):
        index = jax.tree.map(lambda x: x[0], index_stack)
        w = None if w_stack is None else w_stack[0]
        return body(q, index, w)

    out_specs = (qspec, qspec, qspec) if with_aux else (qspec, qspec)

    def fn(q, index_stack, w_stack=None):
        in_specs = (
            qspec,
            jax.tree.map(lambda _: P(model_axis), index_stack),
            None if w_stack is None
            else jax.tree.map(lambda _: P(model_axis), w_stack),
        )
        mapped = compat.shard_map(
            unstacked_body, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs)
        return mapped(q, index_stack, w_stack)

    return fn
