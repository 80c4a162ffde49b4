"""The one traffic generator: turns a mix file of ``bench/traffic/`` and a
seed into the requests of a run.

Every seed gets the same set of sizes and the same set of gaps between
arrivals, in another order: lengths are stratified quantiles of the
mix's distribution, dealt in blocks so that any whole number of blocks
holds the same multiset, and gaps are stratified quantiles of the
arrival process.  The seed only orders them and draws the contents
(token ids, word ids), which change no cost.  So two seeds ask the
system for the same work.

Mix parameters (see the files for examples):

* ``kind``: ``decode`` (sessions of prompt tokens and a token budget) or
  ``score`` (single ids to rank).
* ``loop``: ``closed`` (``clients`` each send their next session when
  the last one ends) or ``open`` (Poisson arrivals at ``rate`` a second).
* ``prompt_len`` / ``output_len``: lognormal ``{"median", "sigma",
  "min", "max"}``, clipped to ``[min, max]``.
* ``ids_zipf_s``: score mixes draw ids Zipf(``s``) over the
  configuration's ``output_dim`` (word ids are ranks by frequency).
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

BLOCK = 64              # sessions per stratified block
_RNG_WORDS = 4          # 32-bit words of the seed fed to the generator


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per named stream of a run.  Seeds are
    whole numbers of any size: they are split into 32-bit words."""
    words = [(seed >> (32 * i)) & 0xFFFFFFFF for i in range(_RNG_WORDS)]
    tag = [ord(c) for c in stream]
    return np.random.default_rng(np.random.SeedSequence(words + tag))


def length_block(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified lengths of one block, in quantile order."""
    from statistics import NormalDist
    u = (np.arange(n) + 0.5) / n
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(vals), spec["min"], spec["max"]).astype(np.int64)


def length_range(spec: dict) -> tuple[int, int]:
    return int(spec["min"]), int(spec["max"])


class Session(NamedTuple):
    prompt: np.ndarray          # int32 [prompt_len]
    max_new_tokens: int


def decode_sessions(mix: dict, vocab: int, seed: int, n: int
                    ) -> list[Session]:
    """``n`` sessions (rounded up to whole blocks), in the order clients
    take them.  Prompt and output lengths are paired at random within a
    block."""
    rng = rng_for(seed, "sessions")
    tok_rng = rng_for(seed, "tokens")
    out = []
    for _ in range(-(-n // BLOCK)):
        plens = rng.permutation(length_block(mix["prompt_len"], BLOCK))
        olens = rng.permutation(length_block(mix["output_len"], BLOCK))
        for plen, olen in zip(plens, olens):
            toks = tok_rng.integers(0, vocab, int(plen), dtype=np.int32)
            out.append(Session(toks, int(olen)))
    return out


def zipf_ids(m: int, s: float, n: int, rng: np.random.Generator
             ) -> np.ndarray:
    """``n`` ids in ``[0, m)``, id ``r`` drawn with weight ``1/(r+1)^s``."""
    cdf = np.cumsum(1.0 / np.arange(1, m + 1, dtype=np.float64) ** s)
    cdf /= cdf[-1]
    ids = np.searchsorted(cdf, rng.random(n), side="right")
    return np.minimum(ids, m - 1).astype(np.int32)


def arrival_offsets(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (seconds from the window's start) of every request of an
    open-loop window: ``round(rate * seconds)`` arrivals whose gaps are
    the stratified quantiles of a Poisson process, in a seeded order,
    scaled to end exactly at ``seconds``."""
    n = max(1, int(round(float(mix["rate"]) * seconds)))
    rng = rng_for(seed, "arrivals")
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))            # Exp(1) quantiles
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) / gaps.sum() * seconds


def score_ids(mix: dict, m: int, n: int, seed: int) -> np.ndarray:
    return zipf_ids(m, float(mix["ids_zipf_s"]), n, rng_for(seed, "ids"))
