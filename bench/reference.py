"""Plain references, written from the published descriptions and
independent of the program: nothing here imports the system under test.

* a language model's body: ``hidden`` of its architecture's module,
  ``bench/models/<model_type>.py``, built from the pieces here that
  every LM shares: ``mm``, ``rms_norm`` and ``rope`` (rotary embeddings
  on the two halves of each head, HF ``rotate_half``).
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


# -------------------------------------------------- shared by the LMs --

def rms_norm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """x [S, H, D]: rotate the pair (x_i, x_{i + D/2}) by pos * theta^(-2i/D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]          # [S, D/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    half = d // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rot * sin


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
