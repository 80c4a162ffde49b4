"""Required operations and bytes, from logical shapes.

Every count here is what the algorithm needs for the real rows of a
call, at the storage dtype of the operands as the program holds them:
bf16 weights and KV for the language model body (counted by its
architecture's module, ``bench/models/<model_type>.py``), fp32 for the WOL
(``Engine`` holds ``w`` in fp32, and the LSS index its slabs in fp32).
Padded shapes never enter: a later change that moves the padding, or the
storage, is read against the same work.

A multiply-add counts as two operations.  The configuration dicts are
the files under ``bench/configs/``.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

from bench import spec

FP32 = 4
BF16 = 2
INT32 = 4


class Work(NamedTuple):
    flops: float
    nbytes: float

    def __add__(self, other: "Work") -> "Work":          # type: ignore[override]
        return Work(self.flops + other.flops, self.nbytes + other.nbytes)


def lss_capacity(m: int, lss: dict) -> int:
    """Bucket capacity P: the configured one, else twice the even load
    rounded up to a multiple of 8 (``LSSConfig.resolve_capacity``)."""
    if lss.get("capacity"):
        return int(lss["capacity"])
    p = -(-2 * m // 2 ** lss["k_bits"])
    return max(8, -(-p // 8) * 8)


def head_width(cfg: dict) -> tuple[int, int]:
    """(m, d) of the wide output layer."""
    if cfg["family"] == "lm":
        return cfg["vocab_size"], cfg["hidden_size"]
    return cfg["output_dim"], cfg["hidden"]


# ------------------------------------------------------------- the head --

def lss_query(cfg: dict) -> Work:
    """One query through the LSS head: hash the augmented query with the
    K*L hyperplanes, read L slabs of P rows of d+1 fp32 and their ids,
    score them, and keep the top-k (comparisons are not counted)."""
    m, d = head_width(cfg)
    lss = cfg["lss"]
    k, n_tables = lss["k_bits"], lss["n_tables"]
    p = lss_capacity(m, lss)
    d_aug = d + 1
    flops = 2 * d_aug * k * n_tables + 2 * n_tables * p * d_aug
    nbytes = d_aug * FP32 + n_tables * p * (d_aug * FP32 + INT32)
    return Work(flops, nbytes)


def lss_call(cfg: dict, rows: float) -> Work:
    """One LSS head call over ``rows`` real queries: the hyperplanes are
    read once per call."""
    m, d = head_width(cfg)
    lss = cfg["lss"]
    theta = (d + 1) * lss["k_bits"] * lss["n_tables"] * FP32
    q = lss_query(cfg)
    return Work(q.flops * rows, q.nbytes * rows + theta)


def full_call(cfg: dict, rows: float) -> Work:
    """One full-head call over ``rows`` queries: every fp32 WOL row is
    read once and scored against every query."""
    m, d = head_width(cfg)
    return Work(2.0 * rows * m * d, m * d * FP32 + m * FP32 + rows * d * FP32)


def head_call(cfg: dict, head: str, rows: float) -> Work:
    return lss_call(cfg, rows) if head == "lss" else full_call(cfg, rows)


# ------------------------------------------------------------ a step --

def decode_steps(cfg: dict, head: str, n_steps: int,
                 contexts: Sequence[int]) -> Work:
    """``n_steps`` fused decode steps that together served rows whose
    contexts are ``contexts``: ``contexts[i]`` is the number of cached
    positions row i attends to, its new one included.

    The body's work is its architecture's (``body_work`` of
    ``bench/models/<model_type>.py``); the head adds its operations per
    row and its reads: its per-call operands once per step."""
    body = spec.arch(cfg).body_work(cfg, n_steps, contexts)
    h = head_call(cfg, head, len(contexts) / n_steps)
    return Work(body.flops + n_steps * h.flops,
                body.nbytes + n_steps * h.nbytes)


def decode_step(cfg: dict, head: str, contexts: Sequence[int]) -> Work:
    """One fused decode step over the active rows (``decode_steps``)."""
    return decode_steps(cfg, head, 1, contexts)
