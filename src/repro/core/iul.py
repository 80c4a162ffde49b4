"""Index Update Loss (paper §3.3): learn the hyperplanes.

The learning signal is *retrieval-aware* (this is the paper's key deviation
from standard learning-to-MIPS): pairs are mined against the CURRENT tables —

  positive (q, w_y):  label y missed by the retrieved set S and q·w_y > t1
  negative (q, w_i):  i ∈ S, not a label, and q·w_i < t2

and the loss pulls positives into the query's bucket / pushes negatives out
via the tanh relaxation K(x) = tanh(theta^T x):

  IUL = -Σ_{P+} log σ(K(w)·K(q)) - Σ_{P-} log(1 - σ(K(w)·K(q)))

Static-shape adaptation: pairs carry a validity mask instead of being
compacted; the two sides are *balance-weighted* (each side normalised by its
valid count), matching the paper's g = min(|P+|,|P-|) truncation in
expectation without data-dependent shapes.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import simhash
from repro.core.lss import (LSSConfig, LSSIndex, build_index, retrieve,
                            sparse_logits_gather, label_recall)
from repro.kernels.registry import resolve_impl
from repro.optim import adamw_init, adamw_update

__all__ = ["MinedPairs", "mine_pairs", "calibrate_thresholds", "iul_loss",
           "iul_train_epoch", "fit_lss", "collision_prob",
           "IULState", "iul_init", "iul_refit_epoch", "calib_recall"]


class MinedPairs(NamedTuple):
    """Static-shape pair batch. w-ids index the WOL; masks mark validity."""

    pos_w: jax.Array     # int32 [B, NL]  label neuron ids (or 0 if invalid)
    pos_mask: jax.Array  # bool  [B, NL]
    neg_w: jax.Array     # int32 [B, C]   retrieved non-label ids
    neg_mask: jax.Array  # bool  [B, C]


def calibrate_thresholds(q_aug: jax.Array, w_aug: jax.Array,
                         labels: jax.Array, cfg: LSSConfig
                         ) -> tuple[jax.Array, jax.Array]:
    """Data-driven t1/t2 (the paper hand-tunes them per dataset).

    t1 = low quantile of *label* inner products  (most labels count as
         positives unless their logit is hopeless), t2 = high quantile of
         *all sampled* inner products (most non-labels qualify as negatives
         unless they are genuinely strong).  Guarantees t1 > t2 is NOT
         required by construction; the paper requires t1 > t2 for a "valid
         setting" — we enforce it by clamping t2 below t1.
    """
    lab_ip = jnp.einsum("bd,bld->bl", q_aug,
                        w_aug[jnp.maximum(labels, 0)])
    lab_ip = jnp.where(labels >= 0, lab_ip, jnp.nan)
    t1 = jnp.nanquantile(lab_ip, cfg.t1_quantile)
    all_ip = q_aug @ w_aug[:: max(1, w_aug.shape[0] // 512)].T
    t2 = jnp.quantile(all_ip, cfg.t2_quantile)
    return t1, jnp.minimum(t2, t1 - 1e-6)


def mine_pairs(q_aug: jax.Array, labels: jax.Array, w_aug: jax.Array,
               index: LSSIndex, t1: jax.Array, t2: jax.Array) -> MinedPairs:
    """Algorithm 1 lines 3-11, batched and static-shape.

    labels: int32 ``[B, NL]`` padded with -1.
    """
    cand_ids, _ = retrieve(q_aug, index)                     # [B, C]
    # positives: labels NOT in S with inner product > t1
    in_set = (labels[:, :, None] == cand_ids[:, None, :]).any(-1)
    lab_ip = jnp.einsum("bd,bld->bl", q_aug.astype(jnp.float32),
                        w_aug[jnp.maximum(labels, 0)].astype(jnp.float32))
    pos_mask = (labels >= 0) & ~in_set & (lab_ip > t1)
    # negatives: retrieved non-labels with inner product < t2
    is_label = (cand_ids[:, :, None] == labels[:, None, :]).any(-1)
    cand_ip = sparse_logits_gather(q_aug, w_aug, cand_ids)
    neg_mask = (cand_ids >= 0) & ~is_label & (cand_ip < t2)
    return MinedPairs(jnp.maximum(labels, 0), pos_mask,
                      jnp.maximum(cand_ids, 0), neg_mask)


def iul_loss(theta: jax.Array, q_aug: jax.Array, w_aug: jax.Array,
             pairs: MinedPairs) -> jax.Array:
    """Balanced IUL (paper eq. 1).  log σ via log_sigmoid for stability."""
    kq = simhash.soft_codes(q_aug, theta)                    # [B, KL]
    kw_pos = simhash.soft_codes(w_aug[pairs.pos_w], theta)   # [B, NL, KL]
    kw_neg = simhash.soft_codes(w_aug[pairs.neg_w], theta)   # [B, C, KL]
    ip_pos = jnp.einsum("bk,blk->bl", kq, kw_pos)
    ip_neg = jnp.einsum("bk,bck->bc", kq, kw_neg)
    # -log σ(x) = -log_sigmoid(x); -log(1-σ(x)) = -log_sigmoid(-x)
    pos_terms = -jax.nn.log_sigmoid(ip_pos) * pairs.pos_mask
    neg_terms = -jax.nn.log_sigmoid(-ip_neg) * pairs.neg_mask
    n_pos = jnp.maximum(pairs.pos_mask.sum(), 1.0)
    n_neg = jnp.maximum(pairs.neg_mask.sum(), 1.0)
    # balance: each side contributes its mean (≡ g pairs per side, g=min)
    return pos_terms.sum() / n_pos + neg_terms.sum() / n_neg


def collision_prob(theta: jax.Array, q_aug: jax.Array, w_aug: jax.Array,
                   pairs: MinedPairs, k_bits: int, n_tables: int
                   ) -> tuple[jax.Array, jax.Array]:
    """Fig-2 metric: P(all K bits of a table collide) for pos / neg pairs."""
    def table_collide(x, y):     # [..., KL] bool each (broadcastable)
        eq = x == y
        eq = eq.reshape(eq.shape[:-1] + (n_tables, k_bits))
        return eq.all(-1).astype(jnp.float32).mean(-1)       # [...] over L
    bq = simhash.hash_bits(q_aug, theta)                     # [B, KL]
    bp = simhash.hash_bits(w_aug[pairs.pos_w], theta)        # [B, NL, KL]
    bn = simhash.hash_bits(w_aug[pairs.neg_w], theta)
    cp = table_collide(bq[:, None, :], bp)
    cn = table_collide(bq[:, None, :], bn)
    p_pos = jnp.sum(cp * pairs.pos_mask) / jnp.maximum(pairs.pos_mask.sum(), 1)
    p_neg = jnp.sum(cn * pairs.neg_mask) / jnp.maximum(pairs.neg_mask.sum(), 1)
    return p_pos, p_neg


def iul_train_epoch(theta, opt_state, q_aug_all, labels_all, w_aug, index,
                    t1, t2, cfg: LSSConfig, key):
    """One epoch: mine per batch against the frozen epoch index, Adam on θ."""
    n = q_aug_all.shape[0]
    bsz = min(cfg.iul_batch, n)
    n_batches = n // bsz
    perm = jax.random.permutation(key, n)[: n_batches * bsz]
    order = perm.reshape(n_batches, bsz)

    grad_fn = jax.value_and_grad(iul_loss)

    def body(carry, idx):
        theta, opt_state = carry
        q = q_aug_all[idx]
        lab = labels_all[idx]
        pairs = mine_pairs(q, lab, w_aug, index, t1, t2)

        def inner(carry, _):
            theta, opt_state = carry
            loss, g = grad_fn(theta, q, w_aug, pairs)
            theta, opt_state = adamw_update(g, opt_state, theta,
                                            lr=cfg.iul_lr)
            return (theta, opt_state), loss

        (theta, opt_state), losses = jax.lax.scan(
            inner, (theta, opt_state), None, length=cfg.iul_inner_steps)
        cp, cn = collision_prob(theta, q, w_aug, pairs, cfg.k_bits,
                                cfg.n_tables)
        return (theta, opt_state), (losses[-1], cp, cn)

    (theta, opt_state), hist = jax.lax.scan(body, (theta, opt_state), order)
    return theta, opt_state, hist


# ----------------------------------------------- snapshot-based entry --
# Module-level jitted programs shared by the offline fit AND the online
# refresher: jax.jit caches per function object, so per-call jax.jit
# wrappers would retrace every refresh cycle.  ``cfg`` (a hashable
# NamedTuple) is the static argument; so is the rebuild's ``impl``,
# resolved before the call so the cached program's slab layout cannot
# go stale when the process default or the environment changes.
_EPOCH_JIT = jax.jit(iul_train_epoch, static_argnames=("cfg",))
_REBUILD_JIT = jax.jit(build_index, static_argnames=("cfg", "impl"))


def _rebuild(w_aug: jax.Array, theta: jax.Array, cfg: LSSConfig,
             impl: str | None) -> LSSIndex:
    return _REBUILD_JIT(w_aug, theta, cfg,
                        impl=resolve_impl("lss_topk", impl))


class IULState(NamedTuple):
    """Resumable IUL training state over one calibration snapshot.

    Everything an epoch step needs besides the (immutable) snapshot
    arrays: the hyperplanes being trained, the Adam moments, the mined
    thresholds, and the RNG key.  A background refresher carries this
    across refresh cycles so training CONTINUES from the serving
    hyperplanes instead of restarting cold each interval."""

    theta: jax.Array
    opt_state: Any
    t1: jax.Array
    t2: jax.Array
    key: jax.Array


def iul_init(key, q_aug: jax.Array, labels_all: jax.Array,
             w_aug: jax.Array, cfg: LSSConfig,
             theta: jax.Array | None = None) -> IULState:
    """Seed an IUL training stream against a calibration snapshot.

    ``theta=None`` draws fresh hyperplanes (the offline ``fit_lss``
    path, preserving its exact RNG sequence); passing the SERVING
    index's theta resumes training from it (the online refresh path:
    the snapshot is new, the hash is warm)."""
    if theta is None:
        k0, key = jax.random.split(key)
        theta = simhash.init_hyperplanes(k0, w_aug.shape[1], cfg.k_bits,
                                         cfg.n_tables)
    t1, t2 = calibrate_thresholds(q_aug, w_aug, labels_all, cfg)
    return IULState(theta, adamw_init(theta), t1, t2, key)


def iul_refit_epoch(state: IULState, q_aug: jax.Array,
                    labels_all: jax.Array, w_aug: jax.Array,
                    index: LSSIndex, cfg: LSSConfig,
                    impl: str | None = None
                    ) -> tuple[IULState, LSSIndex, dict]:
    """ONE training epoch + rebuild against a frozen snapshot — the
    online refresher's unit of work (pure jax, no engine state, safe
    entirely off the serving hot path).  Mines against ``index`` (the
    previous rebuild, per Algorithm 1), returns the advanced state, the
    candidate index (in the slab layout ``impl`` serves, see
    ``core.lss.build_index``), and the epoch's metrics."""
    key, ke = jax.random.split(state.key)
    theta, opt_state, (loss, cp, cn) = _EPOCH_JIT(
        state.theta, state.opt_state, q_aug, labels_all, w_aug, index,
        state.t1, state.t2, cfg, ke)
    new_index = _rebuild(w_aug, theta, cfg, impl)
    info = {"loss": float(loss.mean()),
            "p_collide_pos": float(cp.mean()),
            "p_collide_neg": float(cn.mean()),
            "recall": calib_recall(new_index, q_aug, labels_all)}
    return state._replace(theta=theta, opt_state=opt_state, key=key), \
        new_index, info


def calib_recall(index: LSSIndex, q_aug: jax.Array, labels_all: jax.Array,
                 n: int = 1024) -> float:
    """Calibration-set label recall of ``index`` (first ``n`` rows) —
    the model-selection metric fit_lss and the refresher share."""
    cand, _ = retrieve(q_aug[: min(n, q_aug.shape[0])], index)
    return float(label_recall(cand, labels_all[: cand.shape[0]]))


def fit_lss(key, q_all: jax.Array, labels_all: jax.Array, w: jax.Array,
            b: jax.Array | None, cfg: LSSConfig,
            verbose: bool = False, impl: str | None = None):
    """Full offline preprocessing (paper Algorithm 1, iterated).

    ``impl`` is the ``lss_topk`` impl that will serve the index, which
    picks its slab layout.  Returns (index, history dict of per-epoch
    metrics).
    """
    w_aug = simhash.augment_neurons(w, b)
    q_aug = simhash.augment_queries(q_all)
    state = iul_init(key, q_aug, labels_all, w_aug, cfg)

    hist = {"loss": [], "p_collide_pos": [], "p_collide_neg": [],
            "recall": []}
    # One compiled rebuild reused every epoch (module-level _REBUILD_JIT):
    # hash all m neurons, build all L tables (vmapped), and re-bucketize
    # the weight slabs in a single XLA program instead of re-dispatching
    # the whole op chain eagerly per epoch — the dominant fit_lss cost at
    # m >= 1M on CPU.
    index = _rebuild(w_aug, state.theta, cfg, impl)
    best_index, best_rec = index, -1.0
    for ep in range(cfg.iul_epochs):
        state, index, info = iul_refit_epoch(state, q_aug, labels_all,
                                             w_aug, index, cfg, impl)
        rec = info["recall"]
        # model selection: IUL's mining distribution shifts every rebuild,
        # so individual epochs can regress — serve the best epoch's index
        # (calibration recall), not the last one.
        if rec > best_rec:
            best_rec, best_index = rec, index
        hist["loss"].append(info["loss"])
        hist["p_collide_pos"].append(info["p_collide_pos"])
        hist["p_collide_neg"].append(info["p_collide_neg"])
        hist["recall"].append(rec)
        if verbose:
            print(f"[iul] epoch {ep}: loss={info['loss']:.4f} "
                  f"P+collide={info['p_collide_pos']:.3f} "
                  f"P-collide={info['p_collide_neg']:.3f} recall={rec:.3f}")
    return best_index, hist
