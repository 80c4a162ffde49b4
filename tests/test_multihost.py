"""Multi-host SPMD serving (subprocess fleets: REAL ``jax.distributed``
over gloo CPU collectives, 2 processes x 2 fake devices = 4 shards).

Covers the three multihost contracts:

  * the hierarchical multi-process predict is BIT-IDENTICAL (exact
    array equality, logits AND ids AND sample sizes) to the
    single-process ``make_sharded_predict`` flat merge at equal total m
    — with each process building ONLY its own shard_range, int8 slab +
    padded tail included;
  * the ``Engine._step`` SPMD seam: the leader's ``rank`` broadcasts
    through ``make_leader_step`` while followers replay in
    ``follower_loop``, and the leader's results equal the
    single-process Engine's exactly;
  * mirrored decode: ``leader_generate`` + OP_DECODE followers produce
    the same tokens as a single-process ``LMDecoder.generate``.

The single-process reference runs FIRST (its own subprocess, 4 fake
devices, no distributed runtime) and writes an npz oracle the fleet
workers compare against.
"""

import os
import socket
import subprocess
import sys

import pytest

# toy geometry shared by the oracle and the fleet: m=230 over 4 shards
# exercises the NEG_INF/-1 padded tail (m_local=58, last shard 56 rows)
_COMMON = r"""
import numpy as np
import jax, jax.numpy as jnp
from repro.core import simhash
from repro.core.lss import LSSConfig
from repro.serve.engine import Engine, LMDecoder
from repro.utils import compat

M, D, K, BATCH = 230, 16, 6, 8
CFG = LSSConfig(k_bits=3, n_tables=2, use_bucket_major=True,
                slab_dtype="int8")
W = jax.random.normal(jax.random.PRNGKey(0), (M, D), jnp.float32)
Q = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (BATCH, D),
                                 jnp.float32))
THETA = simhash.init_hyperplanes(jax.random.PRNGKey(3), D + 1,
                                 CFG.k_bits, CFG.n_tables)
THETA2 = simhash.init_hyperplanes(jax.random.PRNGKey(11), D + 1,
                                  CFG.k_bits, CFG.n_tables)

from repro.models import transformer as T
LM_CFG = T.TransformerConfig(name="t", n_layers=1, d_model=16, n_heads=2,
                             n_kv_heads=2, head_dim=8, d_ff=32, vocab=64,
                             dtype=jnp.float32, kv_chunk=8)
LM_PARAMS = T.init_params(jax.random.PRNGKey(5), LM_CFG)
PROMPT = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (2, 4),
                                       0, 64), np.int32)

def make_decoder(spmd=None):
    dec = LMDecoder(LM_PARAMS, LM_CFG, LSSConfig(k_bits=3, n_tables=2),
                    max_streams=2, max_len=12, spmd=spmd)
    dec.engine.fit_random(jax.random.PRNGKey(6))
    return dec

def make_engine(spmd=None, mesh=None):
    eng = Engine(None, W, None, CFG, top_k=K, head="lss-sharded",
                 buckets=(BATCH,), mesh=mesh, spmd=spmd)
    eng.fit_random(jax.random.PRNGKey(1))
    return eng
"""

_REF_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
""" + _COMMON + r"""
from repro.core.sharded import make_sharded_predict
from repro.serve.heads import shard_index

w_aug = simhash.augment_neurons(W, None)
stack, w_stack, m_local = shard_index(w_aug, THETA, CFG, 4)
mesh = jax.make_mesh((4,), ("model",),
                        axis_types=compat.auto_axis_types(1))
fwd = make_sharded_predict(mesh, "model", CFG, m_local, K, with_aux=True)
logits, ids, sample = jax.jit(fwd)(Q, stack, w_stack)

eng = make_engine()                     # mesh=None -> all 4 local devices
out = eng.rank(Q)

# post-swap oracle: the refreshed index the fleet must agree on
from repro.core.lss import build_index
eng.swap_index(build_index(eng._w_aug, THETA2, CFG))
out_s = eng.rank(Q)

toks = make_decoder().generate(PROMPT, steps=4, head="lss-sharded")

np.savez(sys.argv[1],
         logits=np.asarray(logits), ids=np.asarray(ids),
         sample=np.asarray(sample),
         e_logits=np.asarray(out.logits), e_ids=np.asarray(out.ids),
         s_logits=np.asarray(out_s.logits), s_ids=np.asarray(out_s.ids),
         toks=np.asarray(toks))
print("REF-OK", flush=True)
"""

_WORKER_SCRIPT = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
ref_path, coord = sys.argv[1], sys.argv[2]
n_procs, pid = int(sys.argv[3]), int(sys.argv[4])
# the distributed runtime must come up before ANY jax computation (the
# _COMMON constants below run some), so init first thing
from repro.serve.multihost import (assemble_global_stack, follower_loop,
                                   init_multihost, leader_generate,
                                   stop_followers)
ctx = init_multihost(coord, n_procs, pid)
assert ctx is not None and ctx.n_shards == 4, ctx
""" + _COMMON + r"""
from repro.core.sharded import make_multihost_predict
from repro.serve.heads import shard_index

ref = np.load(ref_path)

# ---- 1. hierarchical predict == single-process flat merge, exactly ----
r0, r1 = ctx.row_range(M)
w_aug_local = simhash.augment_neurons(W[r0:r1], None)
local_stack, local_w, m_local = shard_index(
    w_aug_local, THETA, CFG, ctx.n_shards,
    shard_range=ctx.shard_range(), m_total=M)
stack = assemble_global_stack(ctx, local_stack, ctx.n_shards)
w_stack = assemble_global_stack(ctx, local_w, ctx.n_shards)
fwd = jax.jit(make_multihost_predict(ctx.mesh, ctx.host_axis,
                                     ctx.model_axis, CFG, m_local, K,
                                     with_aux=True))
qg = compat.broadcast_one_to_all(Q)
logits, ids, sample = fwd(qg, stack, w_stack)
np.testing.assert_array_equal(np.asarray(ids), ref["ids"])
np.testing.assert_array_equal(np.asarray(logits), ref["logits"])
np.testing.assert_array_equal(np.asarray(sample), ref["sample"])
print("MH-PREDICT-OK", flush=True)

# ---- 2. Engine._step seam: leader rank broadcasts, followers replay ---
eng = make_engine(spmd=ctx)
if ctx.is_leader:
    out = eng.rank(Q)
    np.testing.assert_array_equal(np.asarray(out.ids), ref["e_ids"])
    np.testing.assert_array_equal(np.asarray(out.logits), ref["e_logits"])
    out2 = eng.rank(Q)                  # cached wrapped step, same result
    np.testing.assert_array_equal(np.asarray(out2.ids), ref["e_ids"])
    print("MH-ENGINE-OK", flush=True)
else:
    n_ops = follower_loop(eng, ctx, max_ops=2)
    assert n_ops == 2, n_ops
    print("MH-FOLLOWER-OK", flush=True)

# ---- 2b. concurrent leader threads serialize on the opcode channel ----
# (the auditor-vs-dispatcher race: without ctx.lock the two threads'
# header+payload sequences interleave and the fleet desyncs/hangs)
if ctx.is_leader:
    import threading
    res = {}
    t = threading.Thread(target=lambda: res.update(
        full=eng.rank(Q, head="full", record=False)))
    t.start()
    out3 = eng.rank(Q, record=False)
    t.join(timeout=600)
    assert not t.is_alive(), "concurrent full-head rank hung"
    np.testing.assert_array_equal(np.asarray(out3.ids), ref["e_ids"])
    assert res["full"].ids.shape == (BATCH, K), res["full"].ids.shape
    print("MH-CONCURRENT-OK", flush=True)
else:
    n_ops = follower_loop(eng, ctx, max_ops=2)
    assert n_ops == 2, n_ops

# ---- 2c. fleet index swap: abort leaves both on the old epoch, ---------
# ---- commit flips both; leader crash window cannot split the fleet ----
from repro.core.lss import build_index
from repro.testing import faults
if ctx.is_leader:
    idx2 = build_index(eng._w_aug, THETA2, CFG)
    # leader "crashes" after broadcasting the candidate but before the
    # commit: the abort flag must keep BOTH processes on the old epoch
    try:
        with faults.injected(faults.MULTIHOST_SWAP_COMMIT,
                             RuntimeError("crash before commit")):
            eng.swap_index(idx2)
        raise SystemExit("aborted swap should have raised")
    except RuntimeError:
        pass
    assert eng.index_epoch == 1, eng.index_epoch
    out4 = eng.rank(Q, record=False)
    np.testing.assert_array_equal(np.asarray(out4.ids), ref["e_ids"])
    e2 = eng.swap_index(idx2)           # now commit for real
    assert eng.index_epoch == e2 == 2, (eng.index_epoch, e2)
    out5 = eng.rank(Q, record=False)
    np.testing.assert_array_equal(np.asarray(out5.ids), ref["s_ids"])
    np.testing.assert_array_equal(np.asarray(out5.logits),
                                  ref["s_logits"])
    print("MH-SWAP-OK", flush=True)
else:
    # ops: aborted swap, rank, committed swap, rank
    n_ops = follower_loop(eng, ctx, max_ops=4)
    assert n_ops == 4, n_ops
    assert eng.index_epoch == 2, eng.index_epoch

# ---- 3. mirrored decode: leader_generate == single-process generate ---
dec = make_decoder(spmd=ctx)
if ctx.is_leader:
    toks = leader_generate(ctx, dec, PROMPT, steps=4, head="lss-sharded")
    np.testing.assert_array_equal(np.asarray(toks), ref["toks"])
    stop_followers(ctx)
    print("MH-DECODE-OK", flush=True)
else:
    n_ops = follower_loop(eng, ctx, decoder=dec)
    assert n_ops == 1, n_ops
print("MH-ALL-OK", flush=True)
"""


def _env():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    # the fleet must not inherit a stray REPRO_DIST_* fleet config
    for k in ("REPRO_DIST_COORDINATOR", "REPRO_DIST_NUM_PROCESSES",
              "REPRO_DIST_PROCESS_ID"):
        env.pop(k, None)
    return env


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.multihost
@pytest.mark.slow
def test_multihost_fleet_matches_single_process(tmp_path):
    ref_npz = str(tmp_path / "ref.npz")
    env = _env()
    ref = subprocess.run([sys.executable, "-c", _REF_SCRIPT, ref_npz],
                         env=env, capture_output=True, text=True,
                         timeout=1200)
    assert ref.returncode == 0 and "REF-OK" in ref.stdout, \
        ref.stdout + "\n" + ref.stderr

    coord = f"127.0.0.1:{_free_port()}"
    procs = [subprocess.Popen(
        [sys.executable, "-c", _WORKER_SCRIPT, ref_npz, coord, "2", str(i)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for i in range(2)]
    outs = [p.communicate(timeout=1200)[0] for p in procs]
    for i, p in enumerate(procs):
        assert p.returncode == 0, f"worker {i}:\n{outs[i]}"
        assert "MH-PREDICT-OK" in outs[i], outs[i][-3000:]
        assert "MH-ALL-OK" in outs[i], outs[i][-3000:]
    assert "MH-ENGINE-OK" in outs[0] and "MH-DECODE-OK" in outs[0]
    assert "MH-CONCURRENT-OK" in outs[0]
    assert "MH-SWAP-OK" in outs[0]
    assert "MH-FOLLOWER-OK" in outs[1]
