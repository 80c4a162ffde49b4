"""Mixture-of-Experts layer: top-k routing and two dispatches.

* Training (:func:`moe_ffn`) is sort-based GShard dispatch
  (gather/scatter, NOT the one-hot einsum: the one-hot formulation
  inflates HLO FLOPs by O(S·E·C·d) and would poison the roofline "useful
  compute" ratio).  Static shapes: per-expert capacity C =
  ceil(tokens·top_k/E) · capacity_factor; overflow tokens are dropped
  (their combine weight is 0), underflow slots are zero-padded.  Experts
  are sharded over the ``model`` mesh axis by the launcher; GSPMD inserts
  the all-to-alls at the scatter/gather boundaries.
* Serving (:func:`moe_ffn_serving`, prefill and decode) drops nothing:
  the (token, expert) assignments are sorted by expert and the experts'
  SwiGLU runs as grouped products (``kernels.moe_gmm``) with no capacity,
  so a row's output never depends on its batch-mates or on padding.
  Where the launcher splits the experts over a ``model`` mesh axis,
  prefill and decode keep the capacity dispatch: GSPMD cannot partition
  the grouped products' kernel.

In prefill and decode a layer may hold only some of the routed experts
(``n_held`` from ``held_offset``; training holds every expert), as one
chip of an expert-parallel group does: the router still scores every
expert and picks its top-k over all of them, and the layer adds only its
held experts' part; what experts held elsewhere would add is left out
(no exchange stands in for them).

Supports the two assigned MoE archs:
  * qwen2-moe: 60 routed top-4 (padded to 64 for even sharding in
    training), the top-4 softmax weights as they are (the config's
    ``norm_topk_prob`` is false), + 1 shared expert with a sigmoid gate.
  * arctic: 128 routed top-2, renormalised, + a DENSE residual MLP in
    parallel.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.moe_gmm import moe_gmm
from repro.utils.sharding import maybe_shard


class MoEConfig(NamedTuple):
    n_experts: int           # routed experts (logical, pre-padding)
    top_k: int
    d_model: int
    d_ff: int                # per-expert hidden
    n_experts_padded: int    # physical experts (divisible by model axis)
    capacity_factor: float = 1.25
    router_dtype: str = "float32"
    # GShard-style dispatch groups (= data-axis size in production).
    # Capacity is PER GROUP and the scatter/gather becomes a batched op
    # sharded on the group dim — a global-sort dispatch forces GSPMD to
    # all-gather the scatter updates (measured 16 GB/device/layer on
    # qwen2-moe train_4k; EXPERIMENTS.md §Perf hillclimb 1).
    n_groups: int = 1
    # renormalise the top-k weights to sum to one (qwen2-moe: no)
    norm_topk: bool = True
    # the routed experts this layer holds: n_held from held_offset
    # (0 = every physical expert)
    n_held: int = 0
    held_offset: int = 0

    @property
    def n_local(self) -> int:
        """Expert stacks this layer holds."""
        return self.n_held or self.n_experts_padded


def router_topk(x: jax.Array, w_router: jax.Array, cfg: MoEConfig
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Token-choice top-k routing.

    x: ``[T, D]`` flattened tokens. Returns (expert ids [T, k],
    combine weights [T, k], aux load-balancing loss []).
    """
    logits = x.astype(jnp.float32) @ w_router.astype(jnp.float32)  # [T, Ep]
    # padded experts never win: mask their logits
    if cfg.n_experts_padded > cfg.n_experts:
        pad_mask = jnp.arange(cfg.n_experts_padded) >= cfg.n_experts
        logits = jnp.where(pad_mask[None], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.top_k)          # [T, k]
    if cfg.norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e f_e * p_e
    me = probs.mean(0)                                       # [Ep]
    ce = jnp.zeros((cfg.n_experts_padded,)).at[top_e.reshape(-1)].add(
        1.0 / top_e.size)
    aux = cfg.n_experts * jnp.sum(me * ce)
    return top_e, top_p.astype(x.dtype), aux


def dispatch_indices(top_e: jax.Array, n_experts: int, capacity: int
                     ) -> tuple[jax.Array, jax.Array]:
    """Sort-based dispatch plan.

    Args:
      top_e: ``[T, k]`` expert assignment per (token, slot).
    Returns:
      buffer_pos: int32 ``[T*k]`` position in the ``[E*C]`` expert buffer
                  (or E*C, a trash slot, when over capacity).
      keep: bool ``[T*k]``.
    """
    flat_e = top_e.reshape(-1)                               # [T*k]
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, sorted_e, side="left")
    rank = jnp.arange(flat_e.shape[0], dtype=jnp.int32) - starts.astype(jnp.int32)
    keep_sorted = rank < capacity
    pos_sorted = jnp.where(keep_sorted, sorted_e * capacity + rank,
                           n_experts * capacity)
    # invert the sort: buffer position per original (token, slot)
    inv = jnp.argsort(order, stable=True)
    return pos_sorted[inv], keep_sorted[inv]


def moe_ffn(x: jax.Array, params: dict, cfg: MoEConfig
            ) -> tuple[jax.Array, jax.Array]:
    """Full MoE FFN on flattened tokens ``[T, D]`` -> (out, aux_loss).

    Group-local (GShard-style) dispatch: tokens are split into
    ``n_groups`` groups (one per data shard in production), each with its
    own capacity and its own sort — the scatter/gather carry a leading
    batch dim that GSPMD partitions without communication, and the expert
    einsum is local over (group=data, expert=model).

    params: router [D, Ep], w_gate/w_up [Ep, D, F], w_down [Ep, F, D].
    """
    t, d = x.shape
    ep = cfg.n_experts_padded
    g_n = cfg.n_groups if t % cfg.n_groups == 0 else 1
    tg = t // g_n
    capacity = max(8, int(cfg.capacity_factor * tg * cfg.top_k / ep))
    top_e, top_p, aux = router_topk(x, params["router"], cfg)

    xg = x.reshape(g_n, tg, d)
    if g_n > 1:
        xg = maybe_shard(xg, P("data", None, None))
    top_e_g = top_e.reshape(g_n, tg, cfg.top_k)
    if g_n > 1:
        pos, keep = jax.vmap(dispatch_indices, in_axes=(0, None, None))(
            top_e_g, ep, capacity)                    # [G, Tg*k]
        # batched scatter into [G, E*C+1, D] (trash row last)
        xk = jnp.repeat(xg, cfg.top_k, axis=1)        # [G, Tg*k, D]
        buf = jnp.zeros((g_n, ep * capacity + 1, d), x.dtype)
        buf = jax.vmap(lambda b, p, u, k: b.at[p].set(
            jnp.where(k[:, None], u, 0), mode="drop"))(buf, pos, xk, keep)
    else:
        # unbatched path (tiny decode batches): a singleton-batched
        # scatter partitions worse than the plain one.
        pos, keep = dispatch_indices(top_e, ep, capacity)
        xk = jnp.repeat(x, cfg.top_k, axis=0)
        buf0 = jnp.zeros((ep * capacity + 1, d), x.dtype)
        buf = buf0.at[pos].set(jnp.where(keep[:, None], xk, 0),
                               mode="drop")[None]
        pos, keep = pos[None], keep[None]
    h = buf[:, :-1].reshape(g_n, ep, capacity, d)     # [G, E, C, D]
    if g_n > 1:
        h = maybe_shard(h, P("data", "model", None, None))

    # expert SwiGLU: local over (G=data, E=model)
    gt = jnp.einsum("gecd,edf->gecf", h, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", h, params["w_up"])
    y = jnp.einsum("gecf,efd->gecd", jax.nn.silu(gt) * u,
                   params["w_down"])
    if g_n > 1:
        y = maybe_shard(y, P("data", "model", None, None))

    # batched gather back + weighted combine
    yk = y.reshape(g_n, ep * capacity, d)
    yk = jnp.concatenate([yk, jnp.zeros_like(yk[:, :1])], 1)
    yk = jax.vmap(lambda b, p: b[p])(yk, pos)         # [G, Tg*k, D]
    yk = jnp.where(keep[..., None], yk, 0)
    w = top_p.reshape(g_n, tg * cfg.top_k, 1).astype(yk.dtype)
    out = (yk * w).reshape(g_n, tg, cfg.top_k, d).sum(2)
    return out.reshape(t, d), aux


def moe_ffn_serving(x: jax.Array, params: dict, cfg: MoEConfig,
                    layer: jax.Array | int
                    ) -> tuple[jax.Array, jax.Array]:
    """Drop-free MoE FFN on flattened tokens ``[T, D]`` -> (out, routed).

    params: this layer's router [D, Ep] and every layer's stacks of the
    held experts, w_gate/w_up [L, El, D, F], w_down [L, El, F, D], which
    the grouped products read at ``layer`` where they lie (a layer scan
    then copies no slice of them out).

    The (token, slot) assignments are sorted by held expert (assignments
    to experts held elsewhere sort last, into no group); each held
    expert's SwiGLU runs on exactly its rows as three grouped products
    with fp32 accumulation; each token sums its slots' outputs, weighted
    by the routing weights, in slot order.  Every step is row-local, so
    a token's output is the same whatever else the call holds.

    ``routed``: int32 ``[2]`` — the assignments to held experts, and the
    held experts that received at least one.
    """
    t, d = x.shape
    k = cfg.top_k
    top_e, top_p, _ = router_topk(x, params["router"], cfg)
    local = top_e - cfg.held_offset
    held = (local >= 0) & (local < cfg.n_local)
    group = jnp.where(held, local, cfg.n_local).reshape(-1)      # [T*k]
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((cfg.n_local + 1,), jnp.int32).at[group].add(1)
    sizes = sizes[:cfg.n_local]
    wdt = params["w_gate"].dtype
    xs = x[order // k].astype(wdt)                               # sorted
    g = moe_gmm(xs, params["w_gate"], sizes, layer=layer)
    u = moe_gmm(xs, params["w_up"], sizes, layer=layer)
    h = (jax.nn.silu(g) * u).astype(wdt)
    y = moe_gmm(h, params["w_down"], sizes, layer=layer)         # fp32
    y = y[jnp.argsort(order)].reshape(t, k, d)                   # slot order
    w = jnp.where(held, top_p, 0).astype(jnp.float32)
    out = jnp.sum(y * w[..., None], axis=1)
    routed = jnp.stack([jnp.sum(held), jnp.sum(sizes > 0)]).astype(jnp.int32)
    return out.astype(x.dtype), routed


def moe_ffn_dense_oracle(x: jax.Array, params: dict, cfg: MoEConfig
                         ) -> jax.Array:
    """No-capacity-drop oracle: run every expert on every token, mask by
    routing weights.  O(T·E·F) — tests only."""
    top_e, top_p, _ = router_topk(x, params["router"], cfg)
    g = jnp.einsum("td,edf->tef", x, params["w_gate"])
    u = jnp.einsum("td,edf->tef", x, params["w_up"])
    y = jnp.einsum("tef,efd->ted", jax.nn.silu(g) * u, params["w_down"])
    weights = jnp.zeros((x.shape[0], cfg.n_experts_padded), x.dtype)
    rows = jnp.arange(x.shape[0])[:, None]
    weights = weights.at[rows, top_e].add(top_p)
    return jnp.einsum("ted,te->td", y, weights)


def init_moe_params(key: jax.Array, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    """Router ``[D, Ep]`` over every expert; expert stacks of the held
    ones, ``[El, ...]``."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d, f, ep = cfg.d_model, cfg.d_ff, cfg.n_experts_padded
    el = cfg.n_local
    s_in = d ** -0.5
    s_ff = f ** -0.5
    return {
        "router": (jax.random.normal(k1, (d, ep)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (el, d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (el, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (el, f, d)) * s_ff).astype(dtype),
    }
