"""The comparison that decides ``correct``: what the timed path served,
against the plain reference (``bench.reference``) on the same inputs.

Served models (decode cells).  For a sample of finished sessions, the
reference (``hidden`` of the architecture's ``bench/models/<model_type>.py``)
runs one full forward pass over each prompt with its served tokens, in
fp32 at HIGHEST precision, and reads at every served position the gap by
which the served token's logit (on the architecture's ``head_table``)
lies below the best logit the head's semantics allow there:

* full head: the best over the whole vocabulary;
* LSS head: the best over the bucket that holds the served token (the
  candidates the program ranked when it served it), since a query whose
  hash moves by a rounding of the bf16 body searches another bucket
  with equal right.  ``miss`` then counts how often the served token's
  bucket is not the one the reference's own hidden state hashes to.

``gap`` is the widest such gap, in logits.

Ranked requests (score cells).  For a sample of the requests due in the
window, the reference scores every row exactly and takes its own top-k
(over the query's candidates for LSS).  ``rank_err`` is the widest, over
requests and ranks, of the served logit's distance from the exact logit
of the served id, and of the reference's k-th best above the exact logit
of the served k-th id; relative to ``|q| * max_j |w_j|``.  A served id
that is not a row, or (LSS) not a candidate, reads as infinite.

``control_*`` put the reference at the next precision below the
configuration's in the program's place (``fp8`` under a bf16 model,
``high`` under fp32 at HIGHEST) and read the same numbers from what it
would have served.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import spec

NEG = -jnp.inf


class LSSIndexRef:
    """The reference's own view of the LSS index: neuron codes and which
    neurons hold a slot, rebuilt from the hyperplanes' key and the rows."""

    def __init__(self, key, w, lss: dict, capacity: int):
        self.lss = lss
        self.theta = R.hyperplanes(key, w.shape[1], lss)
        ncodes = R.neuron_codes(w, None, self.theta, lss)
        keep = R.kept(ncodes, capacity)
        if lss["n_tables"] != 1:
            raise NotImplementedError("the reference index reads one table")
        self.codes = jnp.asarray(ncodes[:, 0])
        self.keep = jnp.asarray(keep[:, 0])

    def query_codes(self, q) -> jax.Array:
        return jnp.asarray(R.query_codes(q, self.theta, self.lss)[:, 0])


# ----------------------------------------------------------- decode --

@functools.partial(jax.jit, static_argnames=("lss",))
def _gaps(h, w, chosen, qcodes, ncodes, keep, lss):
    """Per position: the gap of ``chosen`` below the best allowed logit
    (fp32 HIGHEST logits), and whether ``chosen`` is outside the query's
    own bucket."""
    logits = R.mm(h, w.T, "highest")                       # [n, V]
    ok = (chosen >= 0) & (chosen < w.shape[0])
    c = jnp.clip(chosen, 0, w.shape[0] - 1)
    at = jnp.take_along_axis(logits, c[:, None], axis=1)[:, 0]
    if lss:
        same = (ncodes[None, :] == ncodes[c][:, None]) & keep[None, :]
        best = jnp.max(jnp.where(same, logits, NEG), axis=1)
        miss = (ncodes[c] != qcodes) | ~keep[c]
    else:
        best = jnp.max(logits, axis=1)
        miss = jnp.zeros(chosen.shape, bool)
    gap = jnp.where(ok, best - at, jnp.inf)
    return gap, miss | ~ok


@functools.partial(jax.jit, static_argnames=("lss", "prec"))
def _control_choice(h, w, qcodes, ncodes, keep, lss, prec):
    """The token the control would serve: the best logit at the control's
    precision, over its own bucket for LSS."""
    logits = R.mm(h, w.T, prec)
    if lss:
        logits = jnp.where((ncodes[None, :] == qcodes[:, None])
                           & keep[None, :], logits, NEG)
    return jnp.argmax(logits, axis=1).astype(jnp.int32)


def decode_numbers(params: dict, cfg: dict, head: str, sessions: list,
                   index: LSSIndexRef | None, pad_to: int,
                   control: str | None = None) -> dict:
    """``sessions``: ``(prompt int32[p], served int32[n])`` pairs.
    Returns ``{"gap": ..., "miss": ...}`` (``miss`` for LSS only), read
    from the served tokens, or with ``control`` from the tokens the
    control would serve at the same positions."""
    arch = spec.arch(cfg)
    w = arch.head_table(params)
    lss = head != "full"
    dummy = jnp.zeros((1,), jnp.int32)
    gaps, misses = [], []
    for prompt, served in sessions:
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        p = len(prompt)
        h = arch.hidden(params, cfg, seq, "highest", pad_to)[p - 1:]
        qc = index.query_codes(h) if lss else dummy
        ncodes = index.codes if lss else dummy
        keep = index.keep if lss else dummy.astype(bool)
        if control is None:
            chosen = jnp.asarray(served, jnp.int32)
        else:
            hc = arch.hidden(params, cfg, seq, control, pad_to)[p - 1:]
            qcc = index.query_codes(hc) if lss else dummy
            chosen = _control_choice(hc, w, qcc, ncodes, keep, lss, control)
        g, m = _gaps(h, w, chosen, qc, ncodes, keep, lss)
        gaps.append(np.asarray(g))
        misses.append(np.asarray(m))
    g = np.concatenate(gaps)
    out = {"gap": float(g.max())}
    if lss:
        out["miss"] = float(np.concatenate(misses).mean())
    return out


# ------------------------------------------------------------ score --

@functools.partial(jax.jit, static_argnames=("k", "lss", "prec"))
def _topk_ref(q, w, qcodes, ncodes, keep, k, lss, prec):
    logits = R.mm(q, w.T, prec)
    if lss:
        cand = (ncodes[None, :] == qcodes[:, None]) & keep[None, :]
        logits = jnp.where(cand, logits, NEG)
    return jax.lax.top_k(logits, k)


@functools.partial(jax.jit, static_argnames=("lss",))
def _exact_at(q, w, ids, qcodes, ncodes, keep, lss):
    ok = (ids >= 0) & (ids < w.shape[0])
    c = jnp.clip(ids, 0, w.shape[0] - 1)
    rows = w[c]                                            # [n, k, d]
    exact = jnp.einsum("nd,nkd->nk", q.astype(jnp.float32),
                       rows.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST)
    if lss:
        ok &= (ncodes[c] == qcodes[:, None]) & keep[c]
    return exact, ok


def score_queries(params: dict, ids: np.ndarray) -> jax.Array:
    """The word2vec query of each id: ReLU of its input embedding row."""
    return jnp.maximum(params["embed"][jnp.asarray(ids)], 0.0)


def score_numbers(params: dict, head: str, ids: np.ndarray,
                  served_logits: np.ndarray, served_ids: np.ndarray,
                  index: LSSIndexRef | None, control: str | None = None,
                  block: int = 64) -> dict:
    """``rank_err`` over the sampled requests (see the module text)."""
    w = params["w_out"]
    k = served_ids.shape[1]
    lss = head != "full"
    w_norm = float(jnp.sqrt(jnp.max(jnp.sum(w * w, axis=1))))
    dummy = jnp.zeros((1,), jnp.int32)
    worst = 0.0
    for lo in range(0, len(ids), block):
        sl = slice(lo, lo + block)
        q = score_queries(params, ids[sl])
        n = q.shape[0]
        if n < block:
            q = jnp.concatenate([q, jnp.zeros((block - n, q.shape[1]))])
        qc = index.query_codes(q) if lss else dummy
        nc = index.codes if lss else dummy
        keep = index.keep if lss else dummy.astype(bool)
        ref_top, _ = _topk_ref(q, w, qc, nc, keep, k, lss, "highest")
        if control is None:
            s_logits = served_logits[sl]
            s_ids = served_ids[sl]
        else:
            cl, ci = _topk_ref(q, w, qc, nc, keep, k, lss, control)
            s_logits = np.asarray(cl)[:n]
            s_ids = np.asarray(ci)[:n]
        pad_ids = np.zeros((block, k), np.int32)
        pad_ids[:n] = s_ids
        exact, ok = _exact_at(q, w, jnp.asarray(pad_ids), qc, nc, keep, lss)
        exact = np.asarray(exact)[:n]
        ok = np.asarray(ok)[:n]
        ref_top = np.asarray(ref_top)[:n]
        scale = np.linalg.norm(np.asarray(q)[:n], axis=1) * w_norm
        err = np.maximum(np.abs(s_logits - exact), ref_top - exact)
        err = np.where(ok, err / np.maximum(scale, 1e-30)[:, None], math.inf)
        worst = max(worst, float(err.max()))
    return {"rank_err": worst}


# ------------------------------------------------------------ a run --

def samples(impl, mix: dict):
    """What the window served, for the comparison: finished sessions of
    a decode cell, or ``(ids, logits, top ids)`` of a score cell's
    requests; drawn from the seed."""
    if mix["kind"] == "decode":
        return impl.sample(mix["check"]["sessions"])
    idx = impl.sample(mix["check"]["requests"])
    r = impl.req
    return (r.ids[idx], r.logits[idx], r.top_ids[idx])


def compare(params: dict, seed: int, cfg: dict, mix: dict, served,
            control: str | None = None) -> dict:
    """The numbers of a run's sample (``samples``) against the reference
    on the same weights and index; with ``control``, of what the control
    would have served."""
    from bench import weights
    head = mix["head"]
    lss = cfg["lss"]
    table = (spec.arch(cfg).head_table(params) if mix["kind"] == "decode"
             else params["w_out"])
    index = None
    if head != "full":
        index = LSSIndexRef(weights.hash_key(seed), table, lss,
                            lss["capacity"])
    if mix["kind"] == "decode":
        if not served:
            return {}
        return decode_numbers(params, cfg, head, served, index,
                              mix["max_len"], control=control)
    ids, logits, top_ids = served
    if len(ids) == 0:
        return {}
    return score_numbers(params, head, ids, logits, top_ids, index,
                         control=control)
