#!/usr/bin/env python3
"""Chip smoke test: serve qwen2-0.5b at full width on a TPU through the
repo's own entry points, and check what comes out.

    python chip_smoke.py              # one chip: the serving path
    python chip_smoke.py --chips 4    # only the lss-sharded head, 4 chips

One chip (no arguments): qwen2-0.5b at its published widths (24 layers,
d_model 896, vocab 151,936) with random weights from ``--seed``; the LSS
head is fitted by a short IUL run and served by the fused ``lss_topk``
Pallas kernel.  Phases:

  * ``score``  -- next-token scoring requests through ``AsyncRuntime`` on
    the ``lss`` head and on the exact ``full`` head;
  * ``decode`` -- streaming decode sessions through ``DecodeScheduler`` on
    the ``lss`` head with a dense KV cache;
  * checks -- ``lss_topk`` was dispatched to ``pallas`` only; the pallas
    head agrees with the jnp ``ref`` head (run at ``highest`` matmul
    precision) on the same index and queries; every request and session
    succeeded.

``--chips 4`` runs only the vocab-sharded ``lss-sharded`` head over a
4-device mesh, compares it with the same sharded head on ``impl="ref"``,
prints its recall against the exact ``full`` head and the bytes in use
on every device.

Timings printed here are smoke timings of one run, not benchmark
numbers.  The last line of standard output is one JSON object:
``{"ok": true, "device": {...}}``.  The script exits non-zero, and prints
no such line, when JAX finds no TPU, when it does not run from a
checkout of the repo, or when any phase or check fails.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"
TOP_K = 10
N_REQUESTS = 32         # scoring requests per head
N_SESSIONS = 4          # decode sessions, one KV slot each
PROMPT_LEN, NEW_TOKENS = 16, 16
N_PARITY = 512          # queries in each pallas-vs-ref comparison

# Agreement of the pallas head with the ref head.  Both compute fp32
# logits (the kernel's MXU matmuls at HIGHEST precision, the ref under
# jax.default_matmul_precision("highest")), so they differ only by
# accumulation order: typically ~1e-7 of |q|*|w| over d_aug = 1024 terms.
# A single bf16 MXU pass errs by ~2^-9/sqrt(d) ~ 6e-5 of |q|*|w| on
# typical rows, so LOGIT_RTOL = 1e-5 passes fp32 and fails bf16.
LOGIT_RTOL = 1e-5
# A query lands in a different bucket only if one of its hyperplane
# scores is within fp32 error of zero; that is rare, but possible, so a
# few top-1 disagreements are allowed.
MIN_TOP1_AGREEMENT = 0.99


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


class _Clock:
    """Per-phase wall time plus the compile seconds JAX reports (backend
    compile or persistent-cache retrieval, summed)."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def phase(self, name: str):
        clock = self

        class _Phase:
            def __enter__(self):
                self.t0 = time.perf_counter()
                self.c0 = clock.compile_s
                print(f"[{name}] ...", flush=True)

            def __exit__(self, *exc):
                wall = time.perf_counter() - self.t0
                if exc[0] is None:
                    print(f"[{name}] done: {wall:.1f} s wall, "
                          f"{clock.compile_s - self.c0:.1f} s compiling "
                          f"(smoke timing)", flush=True)

        return _Phase()


def _agreement(name: str, out, ref, q, engine) -> None:
    """Top-1 id agreement, and a logit bound on the agreeing rows,
    relative to |q| * max_j |[w_j, b_j]| (Cauchy-Schwarz on the logit)."""
    import numpy as np
    ids, ref_ids = np.asarray(out.ids), np.asarray(ref.ids)
    agree = ids[:, 0] == ref_ids[:, 0]
    rate = float(agree.mean())
    w = np.asarray(engine.w, np.float64)
    w_norm = np.sqrt((w * w).sum(-1) + np.asarray(engine.b, np.float64) ** 2)
    scale = np.linalg.norm(np.asarray(q, np.float64), axis=-1) * w_norm.max()
    err = np.abs(np.asarray(out.logits)[:, 0]
                 - np.asarray(ref.logits)[:, 0]) / scale
    worst = float(err[agree].max()) if agree.any() else float("inf")
    print(f"{name} vs ref: top-1 agreement {rate:.4f} over {agree.size} "
          f"queries (need >= {MIN_TOP1_AGREEMENT}); max |dlogit| / "
          f"(|q||w|) on agreeing rows {worst:.3e} (need <= {LOGIT_RTOL})",
          flush=True)
    if rate < MIN_TOP1_AGREEMENT or worst > LOGIT_RTOL:
        _fail(f"{name} head disagrees with the ref head")


def _check_dispatch(registry) -> None:
    impls = sorted({impl for op, impl in registry.dispatch_log()
                    if op == "lss_topk"})
    print(f"lss_topk dispatched to: {impls}", flush=True)
    if impls != ["pallas"]:
        _fail(f"lss_topk must be served by pallas only, got {impls}")


def _memory(jax) -> None:
    for d in jax.devices():
        st = d.memory_stats() or {}
        print(f"device {d.id}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}",
              flush=True)


def _served(futures, what: str) -> None:
    excs = [f.exception(timeout=60.0) for f in futures]
    bad = [e for e in excs if e is not None]
    print(f"{what}: {len(excs) - len(bad)}/{len(excs)} succeeded",
          flush=True)
    if bad:
        _fail(f"{len(bad)} {what} failed; first: {bad[0]!r}")


def run_one_chip(args, jax, clock) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.data.synthetic import lm_dataset
    from repro.kernels import registry
    from repro.models import transformer as T
    from repro.serve import AsyncRuntime
    from repro.serve.engine import LMDecoder
    from repro.serve.heads import make_lss_head

    spec = get_config(ARCH)
    cfg = spec.model_cfg
    lss_cfg = spec.lss._replace(iul_epochs=2, iul_inner_steps=4,
                                iul_lr=0.02)
    print(f"config: {cfg}", flush=True)
    print(f"lss: {lss_cfg}", flush=True)
    toks = lm_dataset(args.seed, N_REQUESTS * (PROMPT_LEN + 1), cfg.vocab,
                      PROMPT_LEN + 1)
    calib = jnp.asarray(lm_dataset(args.seed + 1, 64 * 33, cfg.vocab, 33))

    with clock.phase("build"):
        params = T.init_params(jax.random.PRNGKey(args.seed), cfg)
        dec = LMDecoder(params, cfg, lss_cfg, max_streams=N_SESSIONS,
                        max_len=PROMPT_LEN + NEW_TOKENS, kv_layout="dense")
        engine = dec.engine
        jax.block_until_ready(engine.w)
    with clock.phase("fit"):
        hist = dec.fit_lss(jax.random.PRNGKey(args.seed + 2), calib)
        print(f"IUL calibration recall per epoch: {hist['recall']}",
              flush=True)

    registry.reset_dispatch_log()
    prompts = np.asarray(toks[:N_REQUESTS, :PROMPT_LEN], np.int32)
    last_hidden = jax.jit(
        lambda p, t: T.prefill(p, t, cfg, max_len=PROMPT_LEN)[0][:, -1])
    queries = np.asarray(last_hidden(params, prompts).astype(jnp.float32))
    for head in ("lss", "full"):
        with clock.phase(f"score[{head}]"):
            # compile every bucket the runtime may coalesce into first
            for b in engine.batcher.buckets:
                if b <= N_REQUESTS:
                    engine.rank(np.zeros((b, cfg.d_model), np.float32),
                                head=head, record=False)
            with AsyncRuntime(engine, head=head, close_timeout_s=600.0) \
                    as rt:
                futs = [rt.submit(q) for q in queries]
                rt.drain(timeout=600.0)
            _served(futs, f"{head} scoring requests")

    with clock.phase("decode[lss]"):
        # warm prefill, first-token and fused decode programs, then hand
        # the scheduler to the runtime (as launch/serve does)
        dec.generate(prompts[:1], steps=2, head="lss")
        sched = dec.scheduler(head="lss", min_len=PROMPT_LEN + NEW_TOKENS)
        with AsyncRuntime(engine, head="lss", scheduler=sched,
                          close_timeout_s=600.0) as rt:
            streams = [rt.submit_decode(prompts[i],
                                        max_new_tokens=NEW_TOKENS)
                       for i in range(N_SESSIONS)]
            rt.drain(timeout=600.0)
        _served(streams, "decode sessions")
        out = np.stack([s.result() for s in streams])
        if out.shape != (N_SESSIONS, NEW_TOKENS) or \
                out.min() < 0 or out.max() >= cfg.vocab:
            _fail(f"decode produced {out.shape} tokens in "
                  f"[{out.min()}, {out.max()}]")
        print(f"decoded tokens {out.shape}, first row {out[0].tolist()}",
              flush=True)
    _check_dispatch(registry)

    with clock.phase("parity"):
        q = engine.calib[0][:N_PARITY]
        index = engine.index
        heads = {impl: make_lss_head(index, None, TOP_K, impl=impl)
                 for impl in ("pallas", "ref")}
        out = jax.jit(heads["pallas"].with_operands)(
            q, *heads["pallas"].operands)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(heads["ref"].with_operands)(
                q, *heads["ref"].operands)
        _agreement("pallas lss head", out, ref, q, engine)
    _memory(jax)


def run_four_chips(args, jax, clock) -> None:
    import jax.numpy as jnp
    import numpy as np
    from repro.configs.registry import get_config
    from repro.kernels import registry
    from repro.serve.engine import Engine

    spec = get_config(ARCH)
    cfg = spec.model_cfg
    print(f"lss-sharded over {jax.device_count()} devices; "
          f"WOL {cfg.vocab} x {cfg.d_model}; lss: {spec.lss}", flush=True)
    key_w, key_q, key_h = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    # the tied embedding's scale (models.transformer.init_params); each
    # query is a WOL row plus 10% noise, so the full head's top-1 is
    # mostly that row and the recall below measures the hash
    w = jax.random.normal(key_w, (cfg.vocab, cfg.d_model),
                          jnp.float32) * cfg.d_model ** -0.5
    k_row, k_noise = jax.random.split(key_q)
    rows = jax.random.randint(k_row, (N_PARITY,), 0, cfg.vocab)
    q = w[rows] + 0.1 * cfg.d_model ** -0.5 * jax.random.normal(
        k_noise, (N_PARITY, cfg.d_model), jnp.float32)

    def sharded_engine(impl):
        eng = Engine(None, w, None, spec.lss, top_k=TOP_K,
                     head="lss-sharded", impl=impl)
        eng.fit_random(key_h)
        return eng

    registry.reset_dispatch_log()
    with clock.phase("lss-sharded[pallas]"):
        engine = sharded_engine(None)
        out = engine.rank(q, head="lss-sharded", record=False)
        jax.block_until_ready(out.ids)
        print(f"mesh: {engine.mesh}", flush=True)
    _check_dispatch(registry)
    _memory(jax)
    with clock.phase("lss-sharded[ref]"):
        with jax.default_matmul_precision("highest"):
            ref = sharded_engine("ref").rank(q, head="lss-sharded",
                                             record=False)
        _agreement("pallas lss-sharded head", out, ref, q, engine)
    with clock.phase("full"):
        full = engine.rank(q, head="full", record=False)
        hit = (np.asarray(out.ids)[:, :, None]
               == np.asarray(full.ids)[:, None, :]).any(-1)
        print(f"lss-sharded recall@{TOP_K} against the full head: "
              f"{float(hit.mean()):.4f}", flush=True)
    _memory(jax)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and inputs")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        _fail(f"{ROOT} is not a checkout of the repo (no src/repro)")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.utils.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()

    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _fail(f"no TPU found: {e}")
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {dev.platform!r}; "
              f"this smoke test has no CPU fallback")
    if len(devices) < args.chips:
        _fail(f"--chips {args.chips} needs {args.chips} devices, JAX sees "
              f"{len(devices)}")
    print(f"device: {dev.device_kind} x{len(devices)}; jax "
          f"{jax.__version__}; compile cache {cache_dir}", flush=True)

    clock = _Clock(jax)
    t0 = time.perf_counter()
    (run_four_chips if args.chips == 4 else run_one_chip)(args, jax, clock)
    print(f"total {time.perf_counter() - t0:.1f} s wall, "
          f"{clock.compile_s:.1f} s compiling, {clock.cache_hits} "
          f"persistent-cache hits (smoke timings)", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
