"""Unified batched serving engine for WOL inference.

One :class:`Engine` owns:

  * the frozen model body (``embed_fn``) and WOL parameters ``w, b``,
  * a fitted :class:`LSSIndex` (plus its vocab-sharded form, built lazily),
  * a pluggable head per request — ``full`` | ``lss`` | ``lss-sharded`` —
    shared by the score path (XC / recsys top-k) and the decode path
    (LM next-token), see ``serve.heads``,
  * a continuous micro-batcher that coalesces submitted requests into
    fixed bucketed batch shapes (``serve.batcher``) so arrival patterns
    never retrigger compilation: exactly one jitted step per
    (head, bucket) pair, trace counts exposed via ``compile_counts``,
  * first-class serving metrics — p50/p95/p99 latency, throughput, avg
    sample size, label recall — computed from the SAME retrieval pass
    that produced the ranking (no second ``retrieve`` call).

Request flow::

    engine.submit(x, labels=...)   # enqueue one example
    engine.flush()                 # coalesce -> bucketed jitted steps
    engine.metrics()               # ServeMetrics snapshot

``WOLServer`` and ``LMDecoder`` remain as thin compatibility wrappers.
"""

from __future__ import annotations

import contextlib
import math
import threading
import time
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import simhash
from repro.core.iul import fit_lss
from repro.core.lss import LSSConfig, LSSIndex, build_index
from repro.kernels import registry
from repro.serve.batcher import DEFAULT_BUCKETS, MicroBatcher
from repro.serve.heads import (HEAD_KINDS, HeadOutput, make_full_head,
                               make_lss_head, make_sharded_lss_head,
                               shard_index)
from repro.utils import compat

__all__ = ["Engine", "ServeMetrics", "RankResult", "WOLServer", "LMDecoder"]


class ServeMetrics(NamedTuple):
    """Serving metrics window.  The first three fields keep the legacy
    (n_requests, wall_s, avg_sample_size) positional layout."""

    n_requests: int
    wall_s: float
    avg_sample_size: float
    throughput_rps: float
    latency_p50_ms: float
    latency_p95_ms: float
    latency_p99_ms: float
    label_recall: float          # nan until labels are supplied
    n_compiles: int


class RankResult(NamedTuple):
    """Per-request result handed back by ``flush``."""

    rid: int
    logits: np.ndarray           # [k]
    ids: np.ndarray              # [k]


class _Pending(NamedTuple):
    rid: int
    x: Any                       # example pytree (no batch dim)
    labels: np.ndarray | None    # [NL] int, -1 padded
    t_submit: float


class _IndexEpoch:
    """One fitted-index generation and everything derived from it.

    The Engine keeps a versioned table of these (``Engine._epochs``) so
    an online refresh can PREPARE a new generation — index, heads,
    sharded stacks, jitted LSS steps — entirely off the serving path,
    then flip ``Engine.index_epoch`` in O(1) under the lock.  Old
    generations stay resident while decode sessions that prefilled
    under them are still draining (``pins``) and are dropped at unpin
    or at the next swap once unpinned — "old steps drain while new
    ones warm"."""

    __slots__ = ("epoch", "index", "heads", "sharded", "steps", "pins")

    def __init__(self, epoch: int, index: LSSIndex):
        self.epoch = epoch
        self.index = index
        self.heads: dict[str, Callable] = {}     # lss kinds only
        self.sharded = None       # (index_stack, w_stack, m_local)
        self.steps: dict[tuple[str, Any], Callable] = {}
        self.pins = 0             # decode generations holding this epoch


def _as_label_row(labels) -> np.ndarray | None:
    if labels is None:
        return None
    arr = np.atleast_1d(np.asarray(labels, np.int32))
    return arr


class Engine:
    """Batched WOL serving with a pluggable head.

    ``embed_fn(batch) -> [B, d]`` maps a request batch to query
    embeddings; pass None when requests already ARE embeddings (the LM
    decode path).  ``w [m, d]``, ``b [m]`` are the WOL parameters.
    ``impl`` pins the kernel-registry implementation the LSS heads serve
    with (``ref`` | ``pallas`` | ``pallas_interpret``); None lets the
    registry auto-select by backend (pallas on TPU, ref elsewhere).
    ``dedup`` pins the ``lss_topk`` cross-table dedup strategy
    (``quadratic`` | ``bitonic``); None lets the registry auto-select on
    the candidate count C = L*P.  ``slab_dtype`` pins the bucket-major
    slab storage format (``fp32`` | ``bf16`` | ``int8``) by overriding
    ``lss_cfg.slab_dtype`` — it takes effect at every index (re)build,
    so ``fit`` and each IUL refit (re)quantize through the same knob;
    None defers to the ``lss_topk.slab_dtype`` registry strategy.

    ``spmd`` (a ``serve.multihost.MultihostContext``) runs the
    lss-sharded head over the multi-process (host, model) mesh: the
    index stack is built from ONLY this process's shards and stitched
    into global arrays, and — on the leader — every score step is
    wrapped to broadcast its opcode + batch first, so followers sitting
    in ``multihost.follower_loop`` enter the same collective program.
    Admission (``submit``/``rank``/the AsyncRuntime) happens on the
    leader only; the wrapped seam is ``_step``, which both the sync
    paths and the runtime dispatcher fetch from.

    Thread safety: every mutation of engine state — the pending request
    queue, finished results, the metrics window, and the jitted step
    cache — happens under ``self.lock`` (an RLock), so one Engine can be
    shared by the AsyncRuntime's worker threads and any number of user
    threads without racing ``_pending``/metrics state.  Device execution
    of an already-built step is jax's concern and needs no lock.
    """

    def __init__(self, embed_fn: Callable | None, w: jax.Array,
                 b: jax.Array | None = None,
                 lss_cfg: LSSConfig = LSSConfig(), *,
                 top_k: int = 5, head: str = "lss",
                 buckets=DEFAULT_BUCKETS,
                 mesh: jax.sharding.Mesh | None = None,
                 model_axis: str = "model",
                 impl: str | None = None,
                 dedup: str | None = None,
                 slab_dtype: str | None = None,
                 audit_rate: float | None = None,
                 spmd=None):
        if head not in HEAD_KINDS:
            raise ValueError(f"head must be one of {HEAD_KINDS}, got {head}")
        if spmd is not None and embed_fn is not None:
            # fail here, not inside the hot step: the opcode channel
            # broadcasts raw [B, d] float32 embedding batches, and an
            # embed_fn engine's [B, T] int token batch is also 2-D — it
            # would be silently cast to float and fed to embed().  A
            # mid-stream raise would also leave followers parked.
            raise ValueError(
                "multihost serving (spmd=...) requires embed_fn=None: "
                "requests must already be [B, d] embeddings; run the "
                "model body before submission")
        if impl is not None and impl not in registry.IMPLS:
            raise ValueError(
                f"impl must be one of {registry.IMPLS} or None, got {impl}")
        if dedup is not None:
            registry.get_strategy("lss_topk.dedup")._validate(
                dedup, "Engine(dedup=...)")
        if slab_dtype is not None:
            registry.get_strategy("lss_topk.slab_dtype")._validate(
                slab_dtype, "Engine(slab_dtype=...)")
            lss_cfg = lss_cfg._replace(slab_dtype=slab_dtype)
        self.impl = impl
        self.dedup = dedup
        self.embed_fn = embed_fn
        self.w = w.astype(jnp.float32)
        self.b = (jnp.zeros((w.shape[0],), jnp.float32) if b is None
                  else b.astype(jnp.float32))
        self.lss_cfg = lss_cfg
        self.top_k = top_k
        self.default_head = head
        self.batcher = MicroBatcher(buckets)
        self.mesh = mesh
        self.model_axis = model_axis
        self.spmd = spmd
        self._w_aug_cache: jax.Array | None = None
        # versioned double-buffered index slot: epoch id -> _IndexEpoch.
        # index_epoch names the SERVING generation; prepared-but-unswapped
        # and pinned-but-draining generations coexist in the table.
        self._epochs: dict[int, _IndexEpoch] = {}
        self.index_epoch: int = 0     # 0 = no fitted index yet
        self._epoch_seq: int = 0
        self._full_head: Callable | None = None
        # jitted steps: (head, bucket) score steps and (head, "decode[...]")
        # fused decode steps.  This table holds the INDEX-FREE full-head
        # programs only; LSS steps live in their _IndexEpoch so a refit
        # is an O(1) pointer flip, not an invalidation sweep.  One
        # compile-count table spans all epochs (a refit that retraces a
        # shape increments the same key — the observable tests pin).
        self._steps: dict[tuple[str, Any], Callable] = {}
        self.compile_counts: dict[tuple[str, Any], int] = {}
        self.calib: tuple | None = None   # (q, labels) refs from last fit
        self._queue: list[_Pending] = []
        self._results: list[RankResult] = []
        self._next_rid = 0
        self.lock = threading.RLock()
        # bounded latency telemetry (was: unbounded self._lat list)
        self.obs = obs.MetricsRegistry(scope_prefix="engine")
        self._h_lat = self.obs.histogram(
            "engine_request_latency_seconds",
            "submit -> result per ranked request")
        self.obs.collect(self._collect_gauges)
        # online label-recall auditor (ISSUE: the paper's LSS-recall
        # claim as a live gauge); rate 0 = off, env-tunable
        if audit_rate is None:
            audit_rate = obs.audit_rate_from_env(0.0)
        self.auditor = None
        if audit_rate > 0:
            # offers are gated per request group on kind != "full" (an
            # exact head needs no audit), so the default head doesn't
            # matter here — LSS traffic through any engine gets sampled
            from repro.obs.audit import RecallAuditor
            self.auditor = RecallAuditor(self, audit_rate)
        self.reset_metrics()

    @property
    def _w_aug(self) -> jax.Array:
        """Bias-augmented neurons, built on first LSS use — a full-head-only
        engine (e.g. LMDecoder without fit_lss) never pays the O(m*d)
        augment or holds the second copy of W."""
        if self._w_aug_cache is None:
            self._w_aug_cache = simhash.augment_neurons(self.w, self.b)
        return self._w_aug_cache

    # ------------------------------------------------- offline fitting --
    def fit(self, key: jax.Array, calib_batches: list, labels: jax.Array,
            verbose: bool = False) -> dict:
        """Paper Algorithm 1: embed the calibration batches through the
        frozen model body, then IUL-train the hyperplanes."""
        assert self.embed_fn is not None, "fit() needs an embed_fn; " \
            "use fit_from_queries() when requests are raw embeddings"
        q = jnp.concatenate([self.embed_fn(bb) for bb in calib_batches])
        return self.fit_from_queries(key, q, labels, verbose=verbose)

    def fit_from_queries(self, key: jax.Array, q: jax.Array,
                         labels: jax.Array, verbose: bool = False) -> dict:
        index, hist = fit_lss(key, q, labels, self.w, self.b, self.lss_cfg,
                              verbose=verbose)
        # keep references (not copies) to the calibration set: an
        # IndexRefresher snapshots them once to re-learn the hash online
        self.calib = (q, labels)
        self._set_index(index)
        return hist

    def fit_random(self, key: jax.Array) -> None:
        """SimHash init without IUL (the SLIDE-style baseline; also what
        the speed benchmarks use — timing is learning-independent)."""
        theta = simhash.init_hyperplanes(key, self._w_aug.shape[1],
                                         self.lss_cfg.k_bits,
                                         self.lss_cfg.n_tables)
        self._set_index(build_index(self._w_aug, theta, self.lss_cfg))

    # --------------------------------------------------- index lifecycle --
    @property
    def index(self) -> LSSIndex | None:
        """The SERVING epoch's index (None before any fit)."""
        st = self._epochs.get(self.index_epoch)
        return None if st is None else st.index

    def index_for(self, epoch: int) -> LSSIndex:
        """The index a specific (e.g. pinned) epoch serves."""
        return self._epoch_state(epoch).index

    def _epoch_state(self, epoch: int | None = None) -> _IndexEpoch:
        e = self.index_epoch if epoch is None else epoch
        st = self._epochs.get(e)
        if st is None:
            if e == 0:
                raise AssertionError(
                    "LSS head needs a fitted index: call fit()/"
                    "fit_random()")
            raise KeyError(f"index epoch {e} is gone (unpinned epochs "
                           f"are dropped at swap)")
        return st

    def _set_index(self, index: LSSIndex) -> None:
        """Install ``index`` as the serving epoch immediately (the
        offline fit path; mirrored identically on every multihost
        process, so no broadcast).  Online refresh goes through
        :meth:`swap_index` instead — prepare + warm + guarded flip."""
        self._swap_prepared(self.prepare_epoch(index))

    def prepare_epoch(self, index: LSSIndex) -> int:
        """Register ``index`` as a new, NOT-yet-serving epoch.  Heavy
        derived state (heads, sharded stacks, jitted steps) is built
        against it lazily or via :meth:`warm_epoch` — none of it on the
        serving path, none of it under a lock held across device work."""
        with self.lock:
            self._epoch_seq += 1
            e = self._epoch_seq
            self._epochs[e] = _IndexEpoch(e, index)
            return e

    def warm_epoch(self, epoch: int, shapes=None) -> None:
        """Trace the prepared epoch's LSS score steps for the bucket
        shapes the serving epoch already compiled (or explicit
        ``shapes``), so post-swap traffic hits warm programs instead of
        paying a trace on its first chunk.  Runs OFF the serving path:
        traces never hold ``self.lock``.  Decode steps are not warmed
        here — a scheduler generation traces its fused step when it
        first dispatches under the new epoch, also lock-free.  No-op on
        multihost engines (a leader-side dry run would broadcast; the
        fleet warms in lockstep through its first post-swap chunks) and
        on embed_fn engines (request shapes are not fabricable here)."""
        if self.spmd is not None or self.embed_fn is not None:
            return
        if shapes is None:
            cur = self._epochs.get(self.index_epoch)
            shapes = [] if cur is None else \
                [k for k in list(cur.steps) if isinstance(k[1], int)]
        d = int(self.w.shape[1])
        for kind, bucket in shapes:
            step = self._step(kind, bucket, epoch=epoch)
            out = step(np.zeros((bucket, d), np.float32))
            jax.block_until_ready(out.logits)

    def _swap_prepared(self, epoch: int) -> int:
        """Flip the serving epoch to ``epoch`` — the ONLY mutation on
        the swap path, O(1) under the channel->engine lock order (the
        same order submit/flush use), so it lands between runtime
        ticks: every chunk/step fetched before the flip runs the old
        generation to completion, every fetch after runs the new."""
        from repro.testing import faults
        with self._channel_lock(), self.lock:
            st = self._epoch_state(epoch)       # raises if dropped
            faults.fire(faults.ENGINE_SWAP, epoch=epoch)
            old = self.index_epoch
            self.index_epoch = st.epoch
            for k in [k for k, s in self._epochs.items()
                      if k != st.epoch and s.pins <= 0]:
                del self._epochs[k]
        obs.event("index_swap", epoch=epoch, prev=old)
        return epoch

    def swap_index(self, index: LSSIndex, *, warm: bool = True) -> int:
        """Online refresh entry: register ``index`` as a new epoch,
        warm its score steps off the serving path, then flip.  On a
        multihost leader the flip rides an ``OP_SWAP_INDEX`` broadcast
        so followers rebuild and flip in lockstep; followers themselves
        swap only via that channel (``follower_loop``), never directly.
        Returns the new epoch id."""
        if self.spmd is not None:
            if not self.spmd.is_leader:
                raise RuntimeError(
                    "followers swap via the OP_SWAP_INDEX broadcast in "
                    "follower_loop, not swap_index()")
            from repro.serve.multihost import leader_swap_index
            return leader_swap_index(self.spmd, self, index)
        e = self.prepare_epoch(index)
        if warm:
            self.warm_epoch(e)
        return self._swap_prepared(e)

    def swap_from_theta(self, theta) -> int:
        """Follower-side swap: rebuild the index deterministically from
        broadcast hyperplanes against this process's own ``_w_aug`` and
        flip.  ``build_index`` is value-deterministic, so every process
        lands on a bit-identical index without shipping buckets."""
        theta = jnp.asarray(theta, jnp.float32)
        index = build_index(self._w_aug, theta, self.lss_cfg)
        return self._swap_prepared(self.prepare_epoch(index))

    def pin_epoch(self, epoch: int | None = None) -> int:
        """Pin an epoch (default: the serving one) so a swap cannot drop
        it — decode sessions rank through the generation they prefilled
        under until they leave.  Returns the pinned epoch id."""
        with self.lock:
            st = self._epoch_state(epoch)
            st.pins += 1
            return st.epoch

    def unpin_epoch(self, epoch: int) -> None:
        """Release a pin; a non-serving epoch with no pins left is
        dropped (its index, heads, and jitted steps become collectable
        — the drained half of the double buffer)."""
        with self.lock:
            st = self._epochs.get(epoch)
            if st is None:
                return
            st.pins -= 1
            if st.pins <= 0 and epoch != self.index_epoch:
                del self._epochs[epoch]

    def drop_step(self, kind: str, tag) -> None:
        """Remove one cached jitted step (every epoch's copy included) —
        the scheduler-replacement path uses this so an outgrown fused
        program cannot collide with its successor's tag."""
        with self.lock:
            self._steps.pop((kind, tag), None)
            for st in self._epochs.values():
                st.steps.pop((kind, tag), None)

    # ------------------------------------------------------ head lookup --
    def _get_mesh(self):
        if self.spmd is not None:
            return self.spmd.mesh
        if self.mesh is None:
            self.mesh = jax.make_mesh(
                (len(jax.devices()),), (self.model_axis,),
                axis_types=compat.auto_axis_types(1))
        return self.mesh

    def _head(self, kind: str, st: _IndexEpoch | None = None) -> Callable:
        if kind not in HEAD_KINDS:
            raise ValueError(f"unknown head {kind!r}")
        if kind == "full":
            # index-free: one head for every epoch
            if self._full_head is None:
                self._full_head = make_full_head(self.w, self.b,
                                                 self.top_k)
            return self._full_head
        st = st if st is not None else self._epoch_state()
        if kind in st.heads:
            return st.heads[kind]
        if kind == "lss":
            w_aug = None if st.index.w_bucketed is not None \
                else self._w_aug
            head = make_lss_head(st.index, w_aug, self.top_k,
                                 impl=self.impl, dedup=self.dedup)
        elif self.spmd is not None:
            head = self._multihost_head(st)
        else:
            mesh = self._get_mesh()
            tp = mesh.shape[self.model_axis]
            if st.sharded is None:
                stack, w_stack, m_local = shard_index(
                    self._w_aug, st.index.theta, self.lss_cfg, tp)
                # lay shard i on device i of the model axis once, here:
                # the stacks are step arguments, not program constants
                on_axis = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(self.model_axis))
                st.sharded = jax.device_put((stack, w_stack), on_axis) + (
                    m_local,)
            stack, w_stack, m_local = st.sharded
            head = make_sharded_lss_head(stack, w_stack, mesh,
                                         self.lss_cfg, m_local,
                                         self.top_k, self.model_axis,
                                         impl=self.impl,
                                         dedup=self.dedup)
        st.heads[kind] = head
        return head

    def _multihost_head(self, st: _IndexEpoch) -> Callable:
        """lss-sharded over the multi-process mesh: build ONLY the
        shards this process addresses (its ``row_range`` slice of W —
        the only place the full weight is even indexed), stitch the
        local stacks into global (host, model)-sharded arrays, and rank
        through the hierarchical O(hosts*k) merge."""
        from repro.serve.heads import make_multihost_lss_head
        from repro.serve.multihost import assemble_global_stack
        ctx = self.spmd
        if st.sharded is None:
            m = self.w.shape[0]
            lo, hi = ctx.shard_range()
            r0, r1 = ctx.row_range(m)
            w_aug_local = simhash.augment_neurons(self.w[r0:r1],
                                                  self.b[r0:r1])
            local_stack, local_w, m_local = shard_index(
                w_aug_local, st.index.theta, self.lss_cfg,
                ctx.n_shards, shard_range=(lo, hi), m_total=m)
            stack = assemble_global_stack(ctx, local_stack, ctx.n_shards)
            w_stack = (None if local_w is None else
                       assemble_global_stack(ctx, local_w, ctx.n_shards))
            st.sharded = (stack, w_stack, m_local)
        stack, w_stack, m_local = st.sharded
        return make_multihost_lss_head(
            stack, w_stack, ctx.mesh, self.lss_cfg, m_local, self.top_k,
            ctx.host_axis, ctx.model_axis, impl=self.impl,
            dedup=self.dedup)

    # ------------------------------------------------------ jitted steps --
    def _step(self, kind: str, bucket: int,
              epoch: int | None = None) -> Callable:
        """One jitted step per (head, bucket) per index epoch: compile
        count is observable because the Python body runs exactly once
        per trace.  ``epoch`` selects a pinned generation's table (the
        decode path); None serves the current epoch."""
        key = (kind, bucket)
        # Lock-free hot path: a GIL-atomic dict read, so the runtime's
        # dispatcher never stalls behind a user thread's flush() (which
        # holds the lock across device execution).  Swapping while
        # serving can hand one in-flight chunk the pre-swap step, which
        # is inherent to concurrent refresh and no worse than the locked
        # path (the fetch could equally precede the flip) — the old
        # epoch's program stays valid until its state is dropped.
        table = (self._steps if kind == "full"
                 else self._epoch_state(epoch).steps)
        step = table.get(key)
        if step is not None:
            return step
        with self.lock:
            if key not in table:
                head = self._head(
                    kind, None if kind == "full"
                    else self._epoch_state(epoch))
                embed = self.embed_fn

                def raw_step(x, *ops):
                    self.compile_counts[key] = \
                        self.compile_counts.get(key, 0) + 1
                    q = embed(x) if embed is not None else x
                    return head.with_operands(q, *ops)

                # the head's arrays ride as jit arguments, never as
                # closure constants (see serve.heads)
                def step(x, _j=jax.jit(raw_step), _ops=head.operands):
                    return _j(x, *_ops)

                if self.spmd is not None and self.spmd.is_leader:
                    # the SPMD seam: sync rank/flush AND the runtime
                    # dispatcher all fetch from here, so wrapping the
                    # leader's step makes every admission path broadcast
                    # to the follower_loop processes first
                    from repro.serve.multihost import make_leader_step
                    step = make_leader_step(self.spmd, step, kind, bucket)
                table[key] = step
            return table[key]

    def decode_logits(self, kind: str, tag: str, body: Callable,
                      epoch: int | None = None) -> Callable:
        """The batched decode head entry: one fused jitted program per
        (head kind, ``tag``) running ``body`` (the model's pooled decode
        step) straight into this engine's head — registry-dispatched for
        the LSS kinds, so the WOL ranking inside the token loop is the
        same kernel path the score buckets use.

        ``body(params, tok, *state) -> (hidden [B, d], k_new, v_new)``
        where ``state`` is the pool layout's cache operands — dense
        ``(k, v, lengths)``, paged ``(k, v, page_table, lengths)``; the
        returned step maps the same signature to ``(tok_next [B] int32,
        HeadOutput, k_new, v_new)`` with the next-token feedback computed
        IN-program, so a decode loop can chain steps device-to-device
        without a host round trip.  ``tag`` names the compile shape (the
        scheduler uses "decode[SxW]", paged "decode[SxW,pagedP]") and
        keys the shared jitted-step cache — compile counts land in
        ``compile_counts[(kind, tag)]`` next to the score buckets.  LSS
        decode steps live in their index epoch's table (``epoch`` pins a
        draining generation, None serves the current one), so a swap
        never invalidates a program a pinned decode generation is still
        running — it just stops being the default.

        The k/v slabs sit at argument positions 2 and 3 in EVERY layout,
        and on TPU the step donates them for in-place cache update
        (halving peak KV memory across a step); XLA:CPU does not support
        buffer donation, so donation is skipped there (the standing
        constraint) and the functional k-in/k-out flow stands alone.
        """
        key = (kind, tag)
        table = (self._steps if kind == "full"
                 else self._epoch_state(epoch).steps)
        step = table.get(key)             # lock-free hot path, like _step
        if step is not None:
            return step
        with self.lock:
            if key not in table:
                head = self._head(
                    kind, None if kind == "full"
                    else self._epoch_state(epoch))
                n_ops = len(head.operands)
                # a fleet's lss-sharded stacks are global arrays: its
                # step replicates every local operand onto the mesh
                fleet = self.spmd is not None and kind == "lss-sharded"

                def raw_step(params, tok, *rest):
                    self.compile_counts[key] = \
                        self.compile_counts.get(key, 0) + 1
                    state, ops = rest[:-n_ops], rest[-n_ops:]
                    hidden, k_new, v_new = body(params, tok, *state)
                    ho = head.with_operands(hidden.astype(jnp.float32),
                                            *ops)
                    tok_next = jnp.maximum(ho.ids[:, 0], 0).astype(jnp.int32)
                    return tok_next, ho, k_new, v_new

                donate = ((2, 3) if jax.default_backend() == "tpu"
                          and not fleet else ())
                jitted = jax.jit(raw_step, donate_argnums=donate)
                if not fleet:
                    def step(params, tok, *state, _j=jitted,
                             _ops=head.operands):
                        return _j(params, tok, *state, *_ops)

                    table[key] = step
                else:
                    # every local operand is promoted to a
                    # mesh-replicated global array (metadata-only: each
                    # process holds the same mirrored value) so the
                    # fused decode program runs SPMD across the fleet
                    from repro.utils import compat
                    mesh = self.spmd.mesh
                    # params replicate ONCE per weight tree, not per
                    # token: re-stamping every fully-addressable weight
                    # leaf each fused step is a host->device device_put
                    # of the whole model per token.  The cache pins the
                    # source tree so its id can't be recycled; the k/v
                    # state leaves come back from the previous step as
                    # global arrays and pass through replicate_global
                    # untouched, so only tok (and the first step's
                    # state) get stamped per call.
                    params_cache: dict = {}

                    def step(params, tok, *state, _j=jitted,
                             _ops=head.operands):
                        cached = params_cache.get(id(params))
                        if cached is None or cached[0] is not params:
                            params_cache.clear()
                            params_cache[id(params)] = (
                                params,
                                compat.replicate_global(params, mesh))
                        params_g = params_cache[id(params)][1]
                        tok, state = compat.replicate_global(
                            (tok, state), mesh)
                        return _j(params_g, tok, *state, *_ops)

                    table[key] = step
            return table[key]

    def _pad_to_bucket(self, x, bucket: int):
        """Device-side row padding (no host round-trip for jax inputs)."""
        def pad(leaf):
            n = leaf.shape[0]
            if n == bucket:
                return leaf
            fill = jnp.zeros((bucket - n,) + leaf.shape[1:], leaf.dtype)
            return jnp.concatenate([leaf, fill], axis=0)
        if isinstance(x, dict):
            return {k: pad(jnp.asarray(v)) for k, v in x.items()}
        return pad(jnp.asarray(x))

    # ------------------------------------------------------- score path --
    def rank(self, x, head: str | None = None, labels=None,
             record: bool = True, epoch: int | None = None) -> HeadOutput:
        """Rank one already-batched request group (rows = requests).

        Pads to the bucket, runs the (head, bucket) jitted step, slices
        back to the true row count.  ``labels`` (int [B, NL], -1 padded)
        feed the recall metric.  The decode loop calls this with
        ``record=False`` to keep the token loop free of host syncs, and
        with ``epoch`` set to its pinned index generation so prefill
        first-tokens stay consistent with its fused decode steps across
        an online swap.
        """
        kind = head or self.default_head
        leaves = jax.tree.leaves(x)
        n = leaves[0].shape[0]
        t0 = time.perf_counter()
        outs = []
        for chunk in self.batcher.plan(n):
            part = jax.tree.map(
                lambda l: l[chunk.start:chunk.start + chunk.size], x)
            padded = self._pad_to_bucket(part, chunk.bucket)
            o = self._step(kind, chunk.bucket, epoch)(padded)
            outs.append(jax.tree.map(lambda l: l[:chunk.size], o))
        out = outs[0] if len(outs) == 1 else HeadOutput(
            *(None if any(l is None for l in ls) else jnp.concatenate(ls)
              for ls in zip(*outs)))
        if record:
            jax.block_until_ready(out.logits)
            wall = time.perf_counter() - t0
            self._record(out, n, wall, [wall] * n, labels)
            if self.auditor is not None and kind != "full":
                self.auditor.offer(x, np.asarray(out.ids))
        return out

    # --------------------------------------------------- request queue --
    def _channel_lock(self):
        """The multihost opcode-channel lock when this process is the
        leader (a no-op context otherwise).  Entry points that hold
        ``self.lock`` across a leader-wrapped step (submit/flush) take
        it FIRST, so lock order is always channel -> engine — the same
        order ``multihost.leader_generate`` (channel) -> decode-step
        build (engine) uses.  Both locks are reentrant."""
        if self.spmd is not None and self.spmd.is_leader:
            return self.spmd.lock
        return contextlib.nullcontext()

    def submit(self, x, labels=None) -> int:
        """Enqueue one example (leaves WITHOUT the batch dim).  Returns a
        request id; auto-flushes once a full max bucket is waiting."""
        with self._channel_lock(), self.lock:
            rid = self._next_rid
            self._next_rid += 1
            self._queue.append(_Pending(rid, x, _as_label_row(labels),
                                        time.perf_counter()))
            if len(self._queue) >= self.batcher.max_bucket:
                self._flush_ready()
            return rid

    def submit_batch(self, xb, labels=None) -> list[int]:
        """Enqueue every row of a batched pytree."""
        xb_np = jax.tree.map(np.asarray, xb)     # one device->host copy
        n = jax.tree.leaves(xb_np)[0].shape[0]
        lab = None if labels is None else np.asarray(labels)
        with self._channel_lock(), self.lock:    # rids stay contiguous
            return [self.submit(jax.tree.map(lambda l: l[i], xb_np),
                                None if lab is None else lab[i])
                    for i in range(n)]

    def _flush_ready(self) -> None:
        while len(self._queue) >= self.batcher.max_bucket:
            group = self._queue[:self.batcher.max_bucket]
            del self._queue[:self.batcher.max_bucket]
            self._results.extend(self._run_group(group))

    def flush(self, head: str | None = None) -> list[RankResult]:
        """Drain the queue through bucketed steps; return all finished
        results (including auto-flushed ones) in submit order."""
        with self._channel_lock(), self.lock:
            while self._queue:
                take = min(len(self._queue), self.batcher.max_bucket)
                group = self._queue[:take]
                del self._queue[:take]
                self._results.extend(self._run_group(group, head))
            out = sorted(self._results, key=lambda r: r.rid)
            self._results = []
            return out

    def _run_group(self, group: list[_Pending],
                   head: str | None = None) -> list[RankResult]:
        kind = head or self.default_head
        bucket = self.batcher.bucket_for(len(group))
        x = jax.tree.map(lambda *rows: np.stack(rows),
                         *[g.x for g in group])
        padded = self._pad_to_bucket(x, bucket)
        t0 = time.perf_counter()
        out = self._step(kind, bucket)(padded)
        jax.block_until_ready(out.logits)
        t1 = time.perf_counter()
        n = len(group)
        out = jax.tree.map(lambda l: l[:n], out)
        lats = [t1 - g.t_submit for g in group]
        labels = self._stack_labels([g.labels for g in group])
        self._record(out, n, t1 - t0, lats, labels)
        logits = np.asarray(out.logits)
        ids = np.asarray(out.ids)
        if self.auditor is not None and kind != "full":
            self.auditor.offer(x, ids)
        return [RankResult(g.rid, logits[i], ids[i])
                for i, g in enumerate(group)]

    @staticmethod
    def _stack_labels(rows) -> np.ndarray | None:
        if all(r is None for r in rows):
            return None
        width = max(1 if r is None else r.shape[0] for r in rows)
        out = np.full((len(rows), width), -1, np.int32)
        for i, r in enumerate(rows):
            if r is not None:
                out[i, :r.shape[0]] = r
        return out

    # ----------------------------------------------------------- metrics --
    def reset_metrics(self) -> None:
        """Start a fresh metrics window.  Pending request results are NOT
        metrics and survive (they belong to the next ``flush``)."""
        with self.lock:
            self._n = 0
            self._wall = 0.0
            self._h_lat.reset()
            self._sample_sum = 0.0
            self._recall_hit = 0
            self._recall_tot = 0

    def _record(self, out: HeadOutput, n: int, wall: float,
                lats: list[float], labels) -> None:
        with self.lock:
            self._record_locked(out, n, wall, lats, labels)

    def _record_locked(self, out: HeadOutput, n: int, wall: float,
                       lats: list[float], labels) -> None:
        self._n += n
        self._wall += wall
        for v in lats:
            self._h_lat.record(v)
        self._sample_sum += float(jnp.sum(out.sample_size[:n]))
        if labels is not None:
            lab = jnp.asarray(labels)[:n]
            if lab.ndim == 1:                 # one label per request
                lab = lab[:, None]
            pool = out.cand_ids if out.cand_ids is not None else out.ids
            hit = (lab[:, :, None] == pool[:n, None, :]).any(-1)
            valid = lab >= 0
            self._recall_hit += int(jnp.sum(hit & valid))
            self._recall_tot += int(jnp.sum(valid))

    def _collect_gauges(self, reg) -> None:
        """Exporter hook: surface the ServeMetrics window as gauges at
        snapshot time (no double bookkeeping on the record path)."""
        m = self.metrics()
        reg.gauge("engine_requests_total").set(m.n_requests)
        reg.gauge("engine_throughput_rps").set(m.throughput_rps)
        reg.gauge("engine_avg_sample_size").set(m.avg_sample_size)
        reg.gauge("engine_label_recall").set(m.label_recall)
        reg.gauge("engine_compiles_total").set(m.n_compiles)

    def metrics(self) -> ServeMetrics:
        # quantiles come off the histogram's own bounded reservoir, not
        # under self.lock — a metrics() poll never stalls flush()
        p50, p95, p99 = self._h_lat.quantile((50, 95, 99))
        p50, p95, p99 = p50 * 1e3, p95 * 1e3, p99 * 1e3
        with self.lock:
            return self._metrics_locked(p50, p95, p99)

    def _metrics_locked(self, p50: float, p95: float,
                        p99: float) -> ServeMetrics:
        return ServeMetrics(
            n_requests=self._n,
            wall_s=self._wall,
            avg_sample_size=self._sample_sum / max(self._n, 1),
            throughput_rps=self._n / self._wall if self._wall else 0.0,
            latency_p50_ms=float(p50),
            latency_p95_ms=float(p95),
            latency_p99_ms=float(p99),
            label_recall=(self._recall_hit / self._recall_tot
                          if self._recall_tot else math.nan),
            n_compiles=sum(self.compile_counts.values()),
        )


# ================================================= compatibility wrappers ==

class WOLServer:
    """Legacy facade: one wide output layer, full or LSS head.

    Kept API-stable for existing callers/tests; all work happens in the
    unified :class:`Engine`.
    """

    def __init__(self, embed_fn: Callable, w: jax.Array,
                 b: jax.Array | None, cfg: LSSConfig, top_k: int = 5):
        self.engine = Engine(embed_fn, w, b, cfg, top_k=top_k)

    @property
    def index(self):
        return self.engine.index

    def fit(self, key: jax.Array, calib_batches: list[dict],
            labels: jax.Array, verbose: bool = False) -> dict:
        return self.engine.fit(key, calib_batches, labels, verbose=verbose)

    def serve(self, batches: list[dict], use_lss: bool = True
              ) -> tuple[list, ServeMetrics]:
        assert not use_lss or self.engine.index is not None, "fit() first"
        self.engine.reset_metrics()
        kind = "lss" if use_lss else "full"
        out = []
        for b in batches:
            ho = self.engine.rank(b, head=kind)
            out.append((ho.logits, ho.ids))
        return out, self.engine.metrics()


class LMDecoder:
    """Session-based LM decode; the per-token head is the Engine's.

    Since the streaming-decode refactor this is a thin facade over a
    :class:`repro.serve.decode.DecodeScheduler`: ``generate`` submits one
    session per prompt row into a fixed-slot scheduler and blocks for the
    streams, so the blocking API and the AsyncRuntime's streaming path
    run the SAME fused ``decode_step_pooled -> head`` program — one
    compile per (head, pool shape) across all ``generate`` calls and all
    sessions, and blocking results are bit-identical to interleaved ones.

    ``max_streams`` fixes the slot count (the fused step's row shape);
    ``max_len`` fixes the pool cache width.  Both are compile shapes AND
    numeric shapes (XLA reductions differ across shapes at the ulp
    level), so pin them when comparing runs.  ``max_len=None`` sizes the
    pool lazily from the first ``generate`` call (growing later
    recompiles).
    """

    def __init__(self, params: dict, cfg, lss_cfg: LSSConfig | None = None,
                 impl: str | None = None, *, max_streams: int = 8,
                 max_len: int | None = None, dedup: str | None = None,
                 slab_dtype: str | None = None, kv_layout: str | None = None,
                 kv_page_tokens: int | None = None,
                 kv_pages: int | None = None, spmd=None):
        from repro.models import transformer as T
        self.T = T
        self.params = params
        self.cfg = cfg
        self.lss_cfg = lss_cfg
        self.max_streams = max_streams
        self._max_len = max_len
        # KV storage layout knobs, handed to each scheduler's pool:
        # layout dense|paged (None -> kv_pool.layout strategy /
        # $REPRO_KV_LAYOUT), page size, and an optional arena page cap
        self.kv_layout = kv_layout
        self.kv_page_tokens = kv_page_tokens
        self.kv_pages = kv_pages
        self._scheds: dict[str, Any] = {}
        self.engine = Engine(None, self.head_weights().astype(jnp.float32),
                             None, lss_cfg or LSSConfig(), top_k=1,
                             head="full", impl=impl, dedup=dedup,
                             slab_dtype=slab_dtype, spmd=spmd)

    @property
    def index(self):
        return self.engine.index

    def head_weights(self) -> jax.Array:
        return (self.params["embed"] if self.cfg.tie_embeddings
                else self.params["lm_head"])

    def fit_lss(self, key: jax.Array, calib_tokens: jax.Array,
                verbose: bool = False) -> dict:
        """Calibrate the LSS index from prefill hidden states; labels are
        the observed next tokens (teacher forcing — exactly the paper's
        'training data through the trained model' recipe)."""
        hidden, _, _ = self.T.forward(self.params, calib_tokens, self.cfg,
                                      mode="train")
        q = hidden[:, :-1].reshape(-1, hidden.shape[-1]).astype(jnp.float32)
        labels = calib_tokens[:, 1:].reshape(-1, 1)
        return self.engine.fit_from_queries(key, q, labels, verbose=verbose)

    def scheduler(self, head: str | None = None, min_len: int | None = None):
        """The per-head-kind DecodeScheduler (built lazily, reused across
        ``generate`` calls and by the AsyncRuntime's decode path).

        A ``min_len`` beyond the current pool width rebuilds the
        scheduler (a new compile shape) ONLY when the old one is idle
        and unattached; a scheduler an AsyncRuntime owns (or one with
        sessions in flight) must not be silently swapped out from under
        it — that raises instead, so callers size ``max_len`` up front.
        """
        from repro.serve.decode import DecodeScheduler
        kind = head or self.engine.default_head
        if kind != "full":
            assert self.engine.index is not None, "fit_lss() first"
        need = max(min_len or 0, self._max_len or 0)
        sched = self._scheds.get(kind)
        if sched is not None and sched.max_len >= need:
            return sched
        if sched is not None:
            if sched.on_session_done is not None or not sched.idle:
                raise ValueError(
                    f"head {kind!r} scheduler has pool width "
                    f"{sched.max_len} < required {need} but is busy or "
                    f"runtime-attached; construct the LMDecoder with "
                    f"max_len >= {need} instead of growing it mid-flight")
            # outgrown and safely replaceable: drop its fused step from
            # the engine's cache (every index epoch's copy) so the old
            # program (and its trace closure) cannot be pinned or
            # collide with the new shape
            self.engine.drop_step(kind, sched._tag)
        self._max_len = (max(need, 64) if self._max_len is None
                         else max(self._max_len, need))
        sched = DecodeScheduler(self.engine, self.params, self.cfg,
                                max_streams=self.max_streams,
                                max_len=self._max_len, head=kind,
                                kv_layout=self.kv_layout,
                                kv_page_tokens=self.kv_page_tokens,
                                kv_pages=self.kv_pages)
        self._scheds[kind] = sched
        return sched

    def generate(self, prompt: jax.Array, steps: int, use_lss: bool = False,
                 head: str | None = None) -> jax.Array:
        """Greedy decode.  prompt [B, S] -> tokens [B, steps].

        ``head`` overrides the full/LSS switch (e.g. "lss-sharded").
        Rows run as sessions through the slot pool: ``B > max_streams``
        decodes in waves of ``max_streams`` (construct the decoder with
        ``max_streams >= B`` for full batch parallelism).  Safe while an
        AsyncRuntime serves the same scheduler — ticks serialize, and
        this call returns once ITS streams finish, leaving other
        producers' sessions in flight."""
        kind = head or ("lss" if use_lss else "full")
        sched = self.scheduler(head=kind,
                               min_len=prompt.shape[1] + steps)
        rows = np.asarray(prompt, np.int32)
        streams = [sched.submit(rows[i], max_new_tokens=steps)
                   for i in range(rows.shape[0])]
        sched.run(until=lambda: all(s.done() for s in streams))
        return jnp.stack([jnp.asarray(s.result()) for s in streams], 0)
