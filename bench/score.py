"""Score cells: one id per request, ranked by the wide output layer.

The query of a word2vec request is ``models.xc.embed`` of its word id,
computed for every request of the run during set-up (in the benchmark's
own jit, with the parameters as an argument).  The timed path is the
program's own: ``AsyncRuntime.submit`` -> admission queue -> the
dispatcher's bucketed chunk -> ``Engine``'s jitted (head, bucket) step
-> the head.  Open loop: requests are sent at their due times whether or
not earlier ones have finished, and each is timed from its due time.

Every time is the benchmark's own host clock: the pacer stamps each
request as it hands it to ``submit``, and a collector stamps it as its
answer reaches the collector.  The collector waits on the answers in the
order they were sent, which is the order the dispatcher serves them in.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import NamedTuple

import numpy as np

from bench import traffic, weights
from bench.log import note


class Requests(NamedTuple):
    ids: np.ndarray              # word id of each request
    due: np.ndarray              # host clock each was due
    sent: np.ndarray             # host clock each was handed to submit
    done: np.ndarray             # host clock its answer was received (inf:
                                 # never)
    failed: np.ndarray           # bool
    logits: np.ndarray           # [n, k] served
    top_ids: np.ndarray          # [n, k] served


class ScoreCell:
    def __init__(self, cfg: dict, mix: dict, seed: int):
        if mix["kind"] != "score" or mix["loop"] != "open":
            raise ValueError("score cells run open-loop score mixes")
        if cfg["family"] != "word2vec":
            raise ValueError("score cells serve word2vec configurations")
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.head = mix["head"]

    def setup(self, seconds: float) -> None:
        import jax
        from repro.core.lss import LSSConfig
        from repro.models import xc
        from repro.serve import AsyncRuntime
        from repro.serve.engine import Engine
        cfg, mix = self.cfg, self.mix
        self.params = weights.make_word2vec_params(cfg, self.seed)
        note("weights made")
        lss = cfg["lss"]
        self.engine = Engine(
            None, self.params["w_out"], None,
            LSSConfig(k_bits=lss["k_bits"], n_tables=lss["n_tables"],
                      capacity=lss["capacity"]),
            top_k=mix["top_k"], head=self.head, buckets=mix["buckets"])
        if self.head != "full":
            self.engine.fit_random(weights.hash_key(self.seed))
            note("index built")
        # every chunk the runtime can dispatch: each bucket's step, and
        # the completion path's per-size bookkeeping, through the
        # program's own runtime with exactly n requests staged
        zero = np.zeros(cfg["hidden"], np.float32)
        for n in range(1, self.engine.batcher.max_bucket + 1):
            rt = AsyncRuntime(self.engine, head=self.head, start=False,
                              max_queue=n, close_timeout_s=600.0)
            futs = [rt.submit(zero) for _ in range(n)]
            rt.start()
            rt.close(timeout=600.0)
            for f in futs:
                f.result(timeout=0)
        note("every chunk size warmed")
        self._embed = jax.jit(lambda p, i: xc.embed(p, i[:, None]))
        self.draw(seconds)

    def draw(self, seconds: float) -> None:
        """The run's requests, their queries, and a fresh runtime."""
        from repro.serve import AsyncRuntime
        mix = self.mix
        self.offsets = traffic.arrival_offsets(
            mix, mix["preroll_s"] + seconds, self.seed)
        self.ids = traffic.score_ids(mix, self.cfg["output_dim"],
                                     len(self.offsets), self.seed)
        self.queries = np.asarray(self._embed(
            {"embed": self.params["embed"]}, self.ids), np.float32)
        note(f"{len(self.ids)} requests drawn and embedded")
        self.rt = AsyncRuntime(self.engine, head=self.head,
                               max_queue=mix["max_queue"],
                               policy=mix["policy"], close_timeout_s=120.0)
        # thousands of requests a second make Python's cyclic collector
        # run full collections; without this each one rescans every
        # object set-up made (JAX's among them) and stalls the host for
        # seconds.  A server freezes its start-up state the same way.
        gc.collect()
        gc.freeze()

    def run(self, seconds: float, on_window=None) -> None:
        """Pre-roll, then the window; ``on_window(t0, t1)`` runs in the
        calling thread once the window has opened.  A collector thread
        keeps what each request returned and drops its future, so the
        benchmark holds no object per request for the collector of
        cyclic garbage to rescan."""
        import queue
        n = len(self.offsets)
        k = self.mix["top_k"]
        sent = np.full(n, np.inf)
        done = np.full(n, np.inf)
        failed = np.ones(n, bool)
        logits = np.zeros((n, k), np.float32)
        top_ids = np.full((n, k), -1, np.int32)
        t_base = time.perf_counter() + 0.05
        due = t_base + self.offsets
        self.t0 = t_base + self.mix["preroll_s"]
        self.t1 = self.t0 + seconds
        handed = queue.SimpleQueue()

        def pace():
            for i in range(n):
                dt = due[i] - time.perf_counter()
                if dt > 0:
                    time.sleep(dt)
                sent[i] = time.perf_counter()
                handed.put(self.rt.submit(self.queries[i]))

        def collect():
            deadline = self.t1 + 60.0
            for i in range(n):
                f = handed.get()
                try:
                    exc = f.exception(
                        timeout=max(0.0, deadline - time.perf_counter()))
                except TimeoutError:
                    continue
                t = time.perf_counter()
                if exc is None:
                    r = f.result()
                    done[i] = t
                    logits[i] = r.logits
                    top_ids[i] = r.ids
                    failed[i] = False

        pacer = threading.Thread(target=pace, name="bench-pacer", daemon=True)
        collector = threading.Thread(target=collect, name="bench-collector",
                                     daemon=True)
        pacer.start()
        collector.start()
        time.sleep(max(0.0, self.t0 - time.perf_counter()))
        self._c0 = self.rt.stats()
        self.t_host = None
        if on_window is not None:
            on_window(self.t0, self.t1)
        pacer.join(timeout=seconds + 660.0)
        if self.t_host is None:
            self.mark_host_end()
        collector.join(timeout=seconds + 720.0)
        self.req = Requests(self.ids, due, sent, done, failed, logits, top_ids)

    def mark_host_end(self) -> None:
        """End of the part of the window the host-clock per-layer metrics
        read: the window's close, or where a profiler starts.  Counters
        from the window's start: batches dispatched and their fill."""
        self.t_host = time.perf_counter()
        c0, c1 = self._c0, self.rt.stats()
        nb = c1.n_batches - c0.n_batches
        occ = (c1.avg_batch_occupancy * c1.n_batches
               - c0.avg_batch_occupancy * c0.n_batches)
        self.counters = {"n_batches": nb,
                         "avg_batch_occupancy": occ / nb if nb else None}

    def steps_done(self) -> int:
        """Chunks the dispatcher has run (its counter)."""
        return self.rt.stats().n_batches

    def close(self) -> None:
        self.rt.close(timeout=120.0)

    def window_mask(self) -> np.ndarray:
        return (self.req.due >= self.t0) & (self.req.due < self.t1)

    def end_to_end(self) -> dict:
        from bench.stats import percentile
        w = self.window_mask()
        lat = np.where(self.req.failed[w], np.inf,
                       self.req.done[w] - self.req.due[w])
        lag = self.req.sent[w] - self.req.due[w]
        done = np.sort(self.req.done[w][np.isfinite(self.req.done[w])])
        return {"score_p95_ms": percentile(lat.tolist(), 95) * 1e3,
                "_n_score": int(w.sum()),
                "_pacer_lag_max_ms": float(lag.max()) * 1e3,
                "_longest_gap_between_results_ms":
                    float(np.diff(done).max()) * 1e3 if len(done) > 1 else 0.0}

    def attempted_failed(self) -> tuple[int, int]:
        w = self.window_mask()
        return int(w.sum()), int(self.req.failed[w].sum())

    def sample(self, n: int) -> np.ndarray:
        """Indices of served requests due in the window, drawn from the
        seed."""
        ok = np.flatnonzero(self.window_mask() & ~self.req.failed)
        rng = traffic.rng_for(self.seed, "check")
        return np.sort(rng.choice(ok, min(n, len(ok)), replace=False))

    def free(self) -> None:
        del self.rt, self.engine
