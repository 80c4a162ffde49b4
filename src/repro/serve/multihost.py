"""Multi-host SPMD serving: process 0 owns admission, followers mirror.

Topology (see ``compat.make_global_mesh``): one global (host, model)
mesh — rows are processes, columns each process's local devices — so a
stacked shard pytree sharded ``P(("host", "model"))`` puts contiguous
vocab-shard blocks on each host, exactly what the hierarchical top-k
merge's global-id offset math assumes.  Every process builds ONLY the
shards it addresses (``heads.shard_index(..., shard_range=...)``) and
:func:`assemble_global_stack` stitches the local stacks into global
arrays without any process materializing remote shards.

Control plane: the AsyncRuntime, the admission queue, deadlines, and
result futures live on process 0 only.  The jitted score steps are SPMD
collective programs, so before the leader runs one, every follower must
enter the same program with the same replicated batch.  The seam is
``Engine._step`` — the ONE choke point both ``Engine.rank``/``flush``
and the AsyncRuntime dispatcher fetch steps from — which on the leader
returns a :func:`make_leader_step` wrapper that first ships one opcode
message — an [4]-int32 header ``(opcode, head, rows, dim)`` plus the
padded batch — over :class:`_OpChannel` and then runs the step;
followers sit in :func:`follower_loop` replaying the opcode stream
until ``OP_STOP``.  The follower side of the channel is a single
thread, so every leader-side send sequence holds
``MultihostContext.lock`` end to end (message + step) — without it two
leader threads (the AsyncRuntime dispatcher and, say, the
RecallAuditor's background ``rank(head="full")``) could interleave
their messages and desync the whole fleet.

The channel rides the ``jax.distributed`` coordination service (a grpc
key-value store), NOT gloo collectives.  It used to be a stream of
tiny ``broadcast_one_to_all`` calls, but each of those is its own
jitted psum whose result is materialized from ``addressable_data(0)``
only — the OTHER local device's collective ops can still be in flight
when the caller issues the next, differently-shaped broadcast, and
under CPU contention two adjacent channel programs would overlap
across processes and collide on a gloo slot (the symptom is a fatal
``gloo ... op.preamble.length <= op.nbytes. 128 vs 4`` abort: a
4-byte scalar recv matched against a 128-byte segment of the batch
psum).  With the control plane on grpc, the only gloo traffic left is
INSIDE the SPMD step programs, which the channel strictly serializes.

Decode rides the same opcode channel at session granularity:
``OP_DECODE`` broadcasts the prompt block once, then EVERY process runs
the same deterministic blocking ``LMDecoder.generate`` — the fused
decode steps (which embed the multihost head's collectives) execute in
lockstep without per-token broadcasts, because blocking generate has no
wall-clock-dependent control flow.
"""

from __future__ import annotations

import contextlib
import dataclasses
import io
import threading

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.utils import compat

__all__ = ["MultihostContext", "init_multihost", "assemble_global_stack",
           "make_leader_step", "leader_generate", "leader_swap_index",
           "follower_loop", "stop_followers", "mirrored_region",
           "in_mirrored_region", "OP_STOP", "OP_SCORE", "OP_DECODE",
           "OP_SWAP_INDEX"]

OP_STOP, OP_SCORE, OP_DECODE, OP_SWAP_INDEX = 0, 1, 2, 3
_HEADER_LEN = 4
_HEAD_IDS = {"full": 0, "lss": 1, "lss-sharded": 2}
_ID_HEADS = {v: k for k, v in _HEAD_IDS.items()}


def _pack(arrays) -> bytes:
    """Serialize a tuple of arrays (dtype/shape/bytes verbatim)."""
    bio = io.BytesIO()
    np.savez(bio, **{f"a{i}": np.asarray(a) for i, a in enumerate(arrays)})
    return bio.getvalue()


def _unpack(blob: bytes) -> list[np.ndarray]:
    with np.load(io.BytesIO(blob)) as z:
        return [z[f"a{i}"] for i in range(len(z.files))]


class _OpChannel:
    """Leader -> followers opcode messaging over the ``jax.distributed``
    coordination service (grpc KV store; see the module docstring for
    why this must NOT be gloo collectives).

    One message per opcode: a monotonically increasing sequence number
    keys each blob, the leader's sends and every follower's recvs
    advance their local counters in lockstep (a follower consumes
    exactly one message per leader send), and payload bytes travel
    verbatim — followers see the leader's batch bit-identically, not a
    ``+ 0.0`` psum of it.  The leader lazily deletes keys ``_GC_WINDOW``
    sends behind, so a long-lived serving fleet cannot grow the
    coordinator's store without bound (a follower lagging 4096 whole
    opcodes is a broken fleet, not a slow one)."""

    _PREFIX = "repro/opch"
    _GC_WINDOW = 4096

    def __init__(self):
        self._seq = 0

    @property
    def _client(self):
        from jax._src import distributed
        client = distributed.global_state.client
        if client is None:
            raise RuntimeError("opcode channel requires an initialized "
                               "jax.distributed runtime (init_multihost)")
        return client

    def send(self, *arrays) -> None:
        self._seq += 1
        self._client.key_value_set_bytes(
            f"{self._PREFIX}/{self._seq}", _pack(arrays))
        old = self._seq - self._GC_WINDOW
        if old > 0:
            self._client.key_value_delete(f"{self._PREFIX}/{old}")

    def recv(self, timeout_s: float | None = 600.0) -> list[np.ndarray]:
        """Block for the next message.  ``None`` waits forever (an idle
        follower between requests), polling in bounded chunks so the
        grpc deadline never fires spuriously on a quiet channel."""
        self._seq += 1
        key = f"{self._PREFIX}/{self._seq}"
        chunk_ms = 60_000 if timeout_s is None \
            else max(1, int(timeout_s * 1000))
        while True:
            try:
                blob = self._client.blocking_key_value_get_bytes(
                    key, chunk_ms)
                return _unpack(blob)
            except Exception as exc:  # retry only grpc deadline expiry
                if timeout_s is None and "DEADLINE_EXCEEDED" in repr(exc):
                    continue
                raise


@dataclasses.dataclass(frozen=True)
class MultihostContext:
    """The fleet's shape, shared by engine, launcher, and bench.

    ``lock`` serializes the leader's opcode channel: followers replay
    opcodes strictly in sequence order, entering each SPMD program as
    they go, so a leader thread's send+step sequence must never
    interleave with another thread's (the swap's message pair and the
    collectives inside each step would cross).  Reentrant, because a
    mirrored decode holds it across ``generate`` while the inner
    prefill re-enters the step wrapper on the same thread."""

    mesh: jax.sharding.Mesh
    host_axis: str = "host"
    model_axis: str = "model"
    lock: threading.RLock = dataclasses.field(
        default_factory=threading.RLock, repr=False, compare=False)
    channel: _OpChannel = dataclasses.field(
        default_factory=_OpChannel, repr=False, compare=False)

    @property
    def process_id(self) -> int:
        return jax.process_index()

    @property
    def n_processes(self) -> int:
        return int(self.mesh.shape[self.host_axis])

    @property
    def shards_per_host(self) -> int:
        return int(self.mesh.shape[self.model_axis])

    @property
    def n_shards(self) -> int:
        return self.n_processes * self.shards_per_host

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    def shard_range(self) -> tuple[int, int]:
        """[lo, hi) shard ids this process addresses (host-contiguous)."""
        tpl = self.shards_per_host
        return self.process_id * tpl, (self.process_id + 1) * tpl

    def row_range(self, m: int) -> tuple[int, int]:
        """Global weight rows [r0, r1) this process's shards cover for a
        vocab of m — the ONLY rows it needs to hold."""
        m_local = -(-m // self.n_shards)
        lo, hi = self.shard_range()
        return lo * m_local, min(hi * m_local, m)

    def stack_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh,
                             P((self.host_axis, self.model_axis)))


def init_multihost(coordinator: str | None = None,
                   num_processes: int | None = None,
                   process_id: int | None = None, *,
                   host_axis: str = "host", model_axis: str = "model"
                   ) -> MultihostContext | None:
    """Initialize ``jax.distributed`` (CPU collectives included; see
    ``compat.distributed_initialize`` — args default to the
    ``REPRO_DIST_COORDINATOR``-family env vars) and build the global
    serving mesh.
    Returns None in the single-process case: callers branch once and the
    whole single-host path stays untouched."""
    if not compat.distributed_initialize(coordinator, num_processes,
                                         process_id):
        return None
    mesh = compat.make_global_mesh((host_axis, model_axis))
    return MultihostContext(mesh, host_axis, model_axis)


def assemble_global_stack(ctx: MultihostContext, local_tree, n_shards: int):
    """Stitch each process's locally built shard stack (leading dim =
    shards_per_host) into global [n_shards, ...] arrays sharded over
    (host, model) — metadata only, no cross-process copies."""
    sharding = ctx.stack_sharding()

    def leaf(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sharding, x, (n_shards,) + x.shape[1:])

    return jax.tree.map(leaf, local_tree)


# ------------------------------------------------------ opcode channel --
_MIRROR = threading.local()


def in_mirrored_region() -> bool:
    return getattr(_MIRROR, "depth", 0) > 0


@contextlib.contextmanager
def mirrored_region():
    """Marks a region EVERY process executes in lockstep (mirrored
    decode): inside it the leader's step wrapper stands down — nobody
    is waiting on the opcode channel, because the followers are running
    this very region themselves.  Without this, the decode prefill's
    ``engine.rank`` on the leader would send OP_SCORE at a follower
    that is inside its own mirrored ``generate`` — a deadlock."""
    _MIRROR.depth = getattr(_MIRROR, "depth", 0) + 1
    try:
        yield
    finally:
        _MIRROR.depth -= 1


def _header(op: int, kind_id: int, rows: int, dim: int) -> np.ndarray:
    return np.asarray([op, kind_id, rows, dim], np.int32)


def make_leader_step(ctx: MultihostContext, jitted, kind: str,
                     bucket: int):
    """Wrap a jitted score step for the leader: ship the opcode +
    replicated batch so every follower enters the same collective
    program, run it, and hand back HOST results (numpy) — the engine's
    slicing/metrics must not launch new device programs on global
    arrays outside the SPMD seam.  The whole message+step sequence runs
    under ``ctx.lock`` so concurrent leader threads (the AsyncRuntime
    dispatcher, the RecallAuditor, user threads) can never interleave
    opcodes on the single-threaded follower channel."""
    kind_id = _HEAD_IDS[kind]

    def step(padded):
        if in_mirrored_region():
            # every process is already running this same code in
            # lockstep — no message, the batch is identical everywhere
            # (uncommitted/local inputs are treated as replicated); on
            # the leader, ctx.lock is already held by leader_generate
            return jax.tree.map(lambda l: np.asarray(l), jitted(padded))
        x = np.asarray(padded, np.float32)
        if x.ndim != 2:
            raise ValueError(
                "multihost serving scores raw [B, d] embedding batches "
                f"(embed_fn=None engines); got shape {x.shape}")
        with ctx.lock:
            ctx.channel.send(
                _header(OP_SCORE, kind_id, x.shape[0], x.shape[1]), x)
            out = jitted(x)
            # materialize INSIDE the lock: the next opcode must not be
            # sent until this SPMD program has fully dispatched
            return jax.tree.map(lambda l: np.asarray(l), out)

    return step


def leader_generate(ctx: MultihostContext, decoder, prompt, steps: int,
                    head: str):
    """Blocking decode on the whole fleet: ship the session block, then
    run the same deterministic ``generate`` everywhere (followers pick
    it up via OP_DECODE in :func:`follower_loop`)."""
    prompt = np.asarray(prompt, np.int32)
    with ctx.lock:
        ctx.channel.send(
            _header(OP_DECODE, _HEAD_IDS[head], prompt.shape[0],
                    prompt.shape[1]),
            np.asarray([steps], np.int32), prompt)
        # hold the lock across the mirrored generate too: its fused
        # decode steps run fleet-wide collectives, so another leader
        # thread sending OP_SCORE mid-decode would interleave
        # collective programs across processes
        with mirrored_region():
            return decoder.generate(prompt, steps=steps, head=head)


def leader_swap_index(ctx: MultihostContext, engine, index) -> int:
    """Fleet-wide online index swap (``Engine.swap_index`` routes here
    on the leader).  Two-phase over the opcode channel: ship the
    hyperplanes, then a commit flag — followers rebuild the index
    deterministically from theta against their own weights (bit-identical
    by ``build_index`` determinism, no bucket arrays shipped) and flip
    only on commit=1.  If the leader dies between payload and commit
    (the ``multihost.swap_commit`` fault window), it sends commit=0 on
    the way out and EVERY process stays on the serving epoch — a swap
    is all-or-nothing, never split-brain.

    Holding ``ctx.lock`` across the whole sequence keeps the swap's
    message pair from interleaving with a score/decode opcode, which
    also means no score step can run BETWEEN a follower's flip and the
    leader's — the fleet is epoch-consistent at every opcode boundary."""
    from repro.testing import faults
    theta = np.asarray(index.theta, np.float32)
    with ctx.lock:
        ctx.channel.send(
            _header(OP_SWAP_INDEX, 0, theta.shape[0], theta.shape[1]),
            theta)
        try:
            faults.fire(faults.MULTIHOST_SWAP_COMMIT)
            ctx.channel.send(np.asarray([1], np.int32))
        except BaseException:
            # abort: tell the fleet to discard the payload and stay on
            # the old epoch, then surface the failure to the refresher
            ctx.channel.send(np.asarray([0], np.int32))
            raise
        # leader flips INSIDE the lock (the channel lock is the outer
        # half of the swap's channel->engine order anyway): the next
        # opcode can only be sent after both sides flipped
        return engine._swap_prepared(engine.prepare_epoch(index))


def stop_followers(ctx: MultihostContext) -> None:
    """Leader: release every follower_loop (call once, when done)."""
    with ctx.lock:
        ctx.channel.send(_header(OP_STOP, 0, 0, 0))


def follower_loop(engine, ctx: MultihostContext, decoder=None,
                  max_ops: int | None = None) -> int:
    """Run on every non-leader process: replay the leader's opcode
    stream — entering the same jitted steps with the same replicated
    payloads — until OP_STOP (or ``max_ops``).  Returns ops executed.

    The engine (and decoder, when decode traffic is expected) must be
    constructed identically to the leader's — same weights, same fitted
    index — which deterministic seeds give for free; the index stack
    itself is assembled from LOCAL shards, so "identical" never means
    shipping the full [m, d] weight anywhere.
    """
    if ctx.is_leader:
        raise RuntimeError("follower_loop on the leader would deadlock "
                           "waiting for its own opcode")
    n_ops = 0
    while max_ops is None or n_ops < max_ops:
        msg = ctx.channel.recv(timeout_s=None)
        op, kind_id, rows, dim = (int(v) for v in msg[0])
        if op == OP_STOP:
            break
        n_ops += 1
        kind = _ID_HEADS[kind_id]
        if op == OP_SCORE:
            out = engine._step(kind, rows)(msg[1])
            jax.block_until_ready(out.logits)
        elif op == OP_DECODE:
            steps, prompt = int(msg[1][0]), msg[2]
            if decoder is None:
                raise RuntimeError("OP_DECODE received but follower has "
                                   "no decoder to mirror generate on")
            with mirrored_region():
                decoder.generate(prompt, steps=steps, head=kind)
        elif op == OP_SWAP_INDEX:
            theta = msg[1]
            commit = int(ctx.channel.recv(timeout_s=None)[0][0])
            if commit:
                engine.swap_from_theta(theta)
            # commit=0: leader aborted mid-swap — drop theta, keep
            # serving the current epoch (graceful degradation)
        else:
            raise RuntimeError(f"unknown multihost opcode {op}")
    return n_ops
