"""Plain references, written from the published descriptions and
independent of the program: nothing here imports the system under test.

* ``lm_hidden``: the Qwen2 decoder (arXiv:2407.10671; HF ``Qwen2Model``):
  RMSNorm (eps from the config), rotary embeddings on the two halves of
  each head (``rotate_half``), grouped-query attention with QKV bias and
  a causal mask, a SwiGLU MLP, a final RMSNorm.  One full forward pass
  over the whole sequence, no cache.
* ``lss_*``: Algorithm 2 of arXiv:2007.01230 on the same index: a
  neuron ``[w, b]`` and a query ``[q, 0]`` fall in the same bucket of
  table l when the signs of their K projections on table l's hyperplanes
  agree; a bucket keeps its ``capacity`` lowest neuron ids; the
  candidates of a query are the neurons sharing one of its L buckets,
  scored exactly.
* the full head: exact ``q . w_j + b_j`` over every row.

``prec`` sets the arithmetic of every matrix product: ``highest`` (fp32,
the reference), ``high`` (three bf16 passes) and ``fp8`` (operands
rounded to float8_e4m3fn, fp32 accumulation) are the controls, the
nearest precision below fp32 and below bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("highest", "high", "fp8")


def _round(x: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype).astype(jnp.float32)


def mm(a: jax.Array, b: jax.Array, prec: str) -> jax.Array:
    """``a @ b`` with fp32 accumulation, the operands as ``prec`` says:
    exact fp32 (``highest``); split into bf16 high and low parts with the
    low-by-low product dropped, as three bf16 passes do (``high``); or
    rounded to float8_e4m3fn (``fp8``).  The lower precisions are spelled
    out, so they read the same on any backend."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    if prec == "highest":
        return jnp.matmul(a, b, precision=hi)
    if prec == "high":
        a1, b1 = _round(a, jnp.bfloat16), _round(b, jnp.bfloat16)
        a2, b2 = _round(a - a1, jnp.bfloat16), _round(b - b1, jnp.bfloat16)
        return (jnp.matmul(a1, b1, precision=hi)
                + jnp.matmul(a1, b2, precision=hi)
                + jnp.matmul(a2, b1, precision=hi))
    if prec == "fp8":
        return jnp.matmul(_round(a, jnp.float8_e4m3fn),
                          _round(b, jnp.float8_e4m3fn), precision=hi)
    raise ValueError(f"unknown precision {prec!r}")


# ------------------------------------------------------------ the LM --

def _rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, H, D]: rotate the pair (x_i, x_{i + D/2}) by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]          # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


@functools.partial(jax.jit, static_argnames=("shape", "prec"))
def _lm_hidden(params, tokens, shape, prec):
    (n_h, n_kv, hd, eps, theta) = shape
    s = tokens.shape[0]
    x = params["embed"][tokens].astype(jnp.float32)                # [S, d]
    pos = jnp.arange(s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    group = n_h // n_kv

    def layer(x, lp):
        h = _rms_norm(x, lp["ln1"], eps)
        q = mm(h, lp["wq"], prec) + lp["bq"].astype(jnp.float32)
        k = mm(h, lp["wk"], prec) + lp["bk"].astype(jnp.float32)
        v = mm(h, lp["wv"], prec) + lp["bv"].astype(jnp.float32)
        q = _rope(q.reshape(s, n_h, hd), pos, theta)
        k = _rope(k.reshape(s, n_kv, hd), pos, theta)
        v = v.reshape(s, n_kv, hd)
        outs = []
        for head in range(n_h):                  # query head -> its KV group
            kv = head // group
            sc = mm(q[:, head], k[:, kv].T, prec) / jnp.sqrt(jnp.float32(hd))
            sc = jnp.where(causal, sc, -jnp.inf)
            outs.append(mm(jax.nn.softmax(sc, axis=-1), v[:, kv], prec))
        x = x + mm(jnp.concatenate(outs, -1), lp["wo"], prec)
        h = _rms_norm(x, lp["ln2"], eps)
        g = mm(h, lp["w_gate"], prec)
        u = mm(h, lp["w_up"], prec)
        x = x + mm(jax.nn.silu(g) * u, lp["w_down"], prec)
        return x, None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return _rms_norm(x, params["final_norm"], eps)


def lm_hidden(params: dict, cfg: dict, tokens: np.ndarray,
              prec: str = "highest", pad_to: int | None = None) -> jax.Array:
    """Final-norm hidden states ``[S, d]`` of a full causal forward pass.
    ``pad_to`` pads the sequence at its end (causality keeps the real
    positions exact) so that sequences of any length share one program."""
    toks = np.asarray(tokens, np.int32)
    n = toks.shape[0]
    if pad_to is not None and pad_to > n:
        toks = np.concatenate([toks, np.zeros(pad_to - n, np.int32)])
    layers = dict(params["layers"])
    if not cfg.get("qkv_bias"):
        for b, w in (("bq", "wq"), ("bk", "wk"), ("bv", "wv")):
            layers[b] = jnp.zeros(layers[w].shape[::2], jnp.float32)
    shape = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
             cfg["head_dim"], float(cfg["rms_norm_eps"]),
             float(cfg["rope_theta"]))
    p = {"embed": params["embed"], "layers": layers,
         "final_norm": params["final_norm"]}
    return _lm_hidden(p, jnp.asarray(toks), shape, prec)[:n]


# ------------------------------------------------------- the LSS index --

def hyperplanes(key: jax.Array, d: int, lss: dict) -> jax.Array:
    """SimHash hyperplanes: i.i.d. N(0, 1), ``[d + 1, K * L]``."""
    return jax.random.normal(key, (d + 1, lss["k_bits"] * lss["n_tables"]),
                             jnp.float32)


@functools.partial(jax.jit, static_argnames=("k_bits", "n_tables"))
def codes(x_aug: jax.Array, theta: jax.Array, k_bits: int, n_tables: int
          ) -> jax.Array:
    """Per-table bucket codes ``[n, L]`` of augmented rows: the K sign
    bits of table l, packed little-endian.  The sign of a projection
    does not depend on the row's length, so rows are normalized first;
    the projection is exact fp32."""
    x = x_aug.astype(jnp.float32)
    x = x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-12)
    bits = jnp.matmul(x, theta, precision=jax.lax.Precision.HIGHEST) > 0
    bits = bits.reshape(x.shape[0], n_tables, k_bits).astype(jnp.int32)
    return jnp.sum(bits << jnp.arange(k_bits, dtype=jnp.int32), axis=-1)


def neuron_codes(w: jax.Array, b: jax.Array | None, theta: jax.Array,
                 lss: dict, block: int = 1 << 18) -> np.ndarray:
    """Codes ``[m, L]`` of every neuron ``[w_j, b_j]``, in row blocks."""
    m = w.shape[0]
    out = []
    for lo in range(0, m, block):
        wb = w[lo:lo + block].astype(jnp.float32)
        bb = (jnp.zeros((wb.shape[0], 1), jnp.float32) if b is None
              else b[lo:lo + block, None].astype(jnp.float32))
        out.append(np.asarray(codes(jnp.concatenate([wb, bb], -1), theta,
                                    lss["k_bits"], lss["n_tables"])))
    return np.concatenate(out)


def kept(ncodes: np.ndarray, capacity: int) -> np.ndarray:
    """bool ``[m, L]``: the neuron holds a slot of its bucket in table l
    (each bucket keeps its ``capacity`` lowest ids)."""
    m, n_tables = ncodes.shape
    keep = np.zeros((m, n_tables), bool)
    for t in range(n_tables):
        order = np.argsort(ncodes[:, t], kind="stable")
        sc = ncodes[order, t]
        starts = np.searchsorted(sc, sc, side="left")
        keep[order, t] = (np.arange(m) - starts) < capacity
    return keep


def query_codes(q: jax.Array, theta: jax.Array, lss: dict) -> np.ndarray:
    """Codes ``[n, L]`` of queries ``[q, 0]``."""
    qa = jnp.concatenate([q.astype(jnp.float32),
                          jnp.zeros((q.shape[0], 1), jnp.float32)], -1)
    return np.asarray(codes(qa, theta, lss["k_bits"], lss["n_tables"]))


def candidate_mask(qcodes: np.ndarray, ncodes: np.ndarray,
                   keep: np.ndarray) -> np.ndarray:
    """bool ``[n, m]``: neuron j is a candidate of query i."""
    hit = np.zeros((qcodes.shape[0], ncodes.shape[0]), bool)
    for t in range(ncodes.shape[1]):
        hit |= (ncodes[None, :, t] == qcodes[:, None, t]) & keep[None, :, t]
    return hit


# ------------------------------------------------------------ the head --

@functools.partial(jax.jit, static_argnames=("prec",))
def _scores(q, w, prec):
    return mm(q, w.T, prec)


def scores(q: jax.Array, w: jax.Array, prec: str = "highest",
           block: int = 64) -> np.ndarray:
    """Exact ``q . w_j`` for every query and row, ``[n, m]`` fp32 on the
    host, computed ``block`` queries at a time."""
    out = []
    for lo in range(0, q.shape[0], block):
        qb = q[lo:lo + block]
        if qb.shape[0] < block:          # one program for every block
            qb = jnp.concatenate(
                [qb, jnp.zeros((block - qb.shape[0], q.shape[1]), qb.dtype)])
        out.append(np.asarray(_scores(qb, w, prec))[:min(block, q.shape[0] - lo)])
    return np.concatenate(out)
