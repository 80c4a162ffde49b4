"""Decode cells: a language model served token by token.

The timed path is the program's own: ``AsyncRuntime.submit_decode`` ->
``DecodeScheduler`` (prefill per power-of-two bucket, the first token
ranked by the head, then one fused ``decode_step_pooled -> head`` step
per token over every slot).  Closed loop: each of ``clients`` sends its
next session when its last one has ended.  The weights from the seed and
the program's configuration are the architecture's
(``bench/models/<model_type>.py``).

Every time is the benchmark's own host clock: a client stamps a session
as it hands it to ``submit_decode`` and each token as its iteration of
the stream receives it, as a streaming client would.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import NamedTuple

import numpy as np

from bench import spec, traffic, weights
from bench.log import note

# sessions drawn up front: more than any closed loop here completes
MAX_SESSIONS_PER_S = 500


class SessionRecord(NamedTuple):
    prompt: np.ndarray
    t_submit: float              # host clock as the client submitted it
    token_times: np.ndarray      # host clock as the client received each
    tokens: np.ndarray
    failed: bool


def warm_lengths(lo: int, hi: int) -> list[int]:
    """Prompt lengths that reach every power-of-two bucket of
    ``[lo, hi]``, and both ends."""
    out = {lo, hi}
    p = 1
    while p <= hi:
        for x in (p, p + 1):
            if lo <= x <= hi:
                out.add(x)
        p *= 2
    return sorted(out)


class DecodeCell:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        if mix["kind"] != "decode" or mix["loop"] != "closed":
            raise ValueError("decode cells run closed-loop decode mixes")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.head = mix["head"]

    # ------------------------------------------------------------ set-up --
    def setup(self, seconds: float) -> None:
        from repro.core.lss import LSSConfig
        from repro.serve import AsyncRuntime
        from repro.serve.engine import LMDecoder
        cfg, mix = self.cfg, self.mix
        arch = spec.arch(cfg)
        self.params = arch.make_params(cfg, self.seed)
        note("weights made")
        lss = cfg["lss"]
        self.dec = LMDecoder(
            self.params, arch.program_config(cfg),
            LSSConfig(k_bits=lss["k_bits"], n_tables=lss["n_tables"],
                      capacity=lss["capacity"]),
            max_streams=mix["max_streams"], max_len=mix["max_len"],
            kv_layout=mix["kv_layout"])
        if self.head != "full":
            self.dec.engine.fit_random(weights.hash_key(self.seed))
            note("index built")
        sched = self.dec.scheduler(head=self.head, min_len=mix["max_len"])
        # every prefill bucket the mix reaches, the first-token rank and
        # the fused step, through the program's own blocking entry
        lo, hi = traffic.length_range(mix["prompt_len"])
        rng = traffic.rng_for(self.seed, "warm")
        for n in warm_lengths(lo, hi):
            prompt = rng.integers(0, cfg["vocab_size"], (1, n), dtype=np.int32)
            self.dec.generate(prompt, steps=2, head=self.head)
            note(f"warmed prompt length {n}")
        sched.reset_stats()
        self.sched = sched
        n = int(MAX_SESSIONS_PER_S * (seconds + mix["preroll_s"]))
        self.sessions = traffic.decode_sessions(mix, cfg["vocab_size"],
                                                self.seed, n)
        self.rt = AsyncRuntime(self.dec.engine, head=self.head,
                               scheduler=sched, max_queue=4 * mix["clients"],
                               close_timeout_s=120.0)

    # ------------------------------------------------------------ window --
    def run(self, seconds: float, on_window=None) -> None:
        """Pre-roll, then the window of ``seconds``; ``on_window(t0, t1)``
        runs in the calling thread once the window has opened."""
        mix = self.mix
        t_start = time.perf_counter()
        self.t0 = t_start + mix["preroll_s"]
        self.t1 = self.t0 + seconds
        counter = itertools.count()
        lock = threading.Lock()
        records: list = []
        self._ran_out = False

        def client():
            while time.perf_counter() < self.t1:
                with lock:
                    i = next(counter)
                if i >= len(self.sessions):
                    self._ran_out = True
                    return
                s = self.sessions[i]
                t_sub = time.perf_counter()
                st = self.rt.submit_decode(s.prompt,
                                           max_new_tokens=s.max_new_tokens)
                toks, times, failed = [], [], False
                try:
                    for tok in st:
                        times.append(time.perf_counter())
                        toks.append(tok)
                except Exception:            # the stream failed
                    failed = True
                rec = SessionRecord(s.prompt, t_sub, np.asarray(times),
                                    np.asarray(toks, np.int32), failed)
                with lock:
                    records.append(rec)

        threads = [threading.Thread(target=client, name=f"bench-client-{i}",
                                    daemon=True)
                   for i in range(mix["clients"])]
        for t in threads:
            t.start()
        time.sleep(max(0.0, self.t0 - time.perf_counter()))
        self.sched.reset_stats()
        self.t_host = None
        if on_window is not None:
            on_window(self.t0, self.t1)
        time.sleep(max(0.0, self.t1 - time.perf_counter()))
        if self.t_host is None:
            self.mark_host_end()
        for t in threads:
            t.join(timeout=660.0)
        self.rt.drain(timeout=600.0)
        if self._ran_out:
            raise RuntimeError("the closed loop used every session drawn; "
                               "raise MAX_SESSIONS_PER_S")
        self.records = records

    def mark_host_end(self) -> None:
        """End of the part of the window the host-clock per-layer metrics
        read: the window's close, or where a profiler starts."""
        self.t_host = time.perf_counter()
        self.counters = self.rt.stats()._asdict()

    def steps_done(self) -> int:
        """Fused decode steps the scheduler has dispatched (its counter)."""
        return self.sched.stats().n_steps

    def close(self) -> None:
        self.rt.close(timeout=120.0)

    # ----------------------------------------------------------- results --
    def in_window(self) -> list[SessionRecord]:
        return [r for r in self.records if self.t0 <= r.t_submit < self.t1]

    def end_to_end(self) -> dict:
        from bench.stats import percentile
        t0, t1 = self.t0, self.t1
        n_tok = sum(int(((r.token_times >= t0) & (r.token_times < t1)).sum())
                    for r in self.records)
        gaps = []
        for r in self.records:
            tt = r.token_times
            if len(tt) > 1:
                later = tt[1:]
                sel = (later >= t0) & (later < t1)
                gaps.extend(np.diff(tt)[sel].tolist())
        ttft = [(r.token_times[0] - r.t_submit) if len(r.token_times)
                else float("inf") for r in self.in_window()]
        return {"decode_tok_s": n_tok / (t1 - t0),
                "itl_p95_ms": percentile(gaps, 95) * 1e3,
                "ttft_p95_ms": percentile(ttft, 95) * 1e3,
                "_n_itl": len(gaps), "_n_ttft": len(ttft)}

    def attempted_failed(self) -> tuple[int, int]:
        w = self.in_window()
        return len(w), sum(1 for r in w if r.failed or len(r.tokens) == 0)

    def sample(self, n: int) -> list[tuple[np.ndarray, np.ndarray]]:
        """Finished sessions for the check: the longest, and ``n - 1``
        drawn from the seed."""
        done = [r for r in self.records if not r.failed and len(r.tokens)]
        if not done:
            return []
        longest = max(range(len(done)),
                      key=lambda i: len(done[i].prompt) + len(done[i].tokens))
        rest = [i for i in range(len(done)) if i != longest]
        rng = traffic.rng_for(self.seed, "check")
        pick = [longest] + list(rng.choice(rest, min(n - 1, len(rest)),
                                           replace=False))
        return [(done[i].prompt, done[i].tokens) for i in pick]

    def free(self) -> None:
        """Drop every array the program holds; the weights stay with the
        benchmark for the reference."""
        del self.rt, self.sched, self.dec
