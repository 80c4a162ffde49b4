"""Serving launcher: build an LM from a seed (``--train-steps 0``) or
train it briefly, fit the LSS head (hash geometry from the registry
config), then serve one of three modes:

  * ``--mode generate`` (default) — blocking batched decode through the
    unified serving engine (``--runtime async`` instead serves open-loop
    next-token SCORING traffic: Poisson arrivals at ``--qps``, optional
    ``--deadline-ms`` load shedding).
  * ``--mode decode --streams N`` — streaming decode through the
    AsyncRuntime: open-loop Poisson SESSION arrivals at ``--qps``
    sessions/s (0 = burst), N concurrent streams interleaved in one
    fused decode step, per-token TokenStream futures, TTFT/ITL stats.

Observability: ``--metrics-port P`` starts the stdlib ``/metrics``
endpoint (Prometheus text; ``/metrics.json``, ``/trace`` too — see
``repro.obs.export``) BEFORE training begins, so a scraper can watch the
whole run; ``--hold-metrics S`` keeps the process (and endpoint) alive S
seconds after serving finishes so a one-shot scrape (CI) always lands.
``--audit-rate F`` samples fraction F of LSS-served scoring requests
through the online label-recall auditor (``lss_audit_recall_at_k``;
also settable via ``$REPRO_OBS_AUDIT_RATE``).

The process exits non-zero when any request or session failed for a
reason other than an explicit shed (full queue, missed deadline).

    python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 16 --steps 32 [--head full|lss|lss-sharded] \
        [--runtime async --qps 500 --deadline-ms 50] \
        [--mode decode --streams 8 --sessions 32 --qps 0] \
        [--metrics-port 9100 --audit-rate 0.25 --hold-metrics 30]
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--train-steps", type=int, default=150,
                    help="LM training steps before serving; 0 serves "
                         "seeded random weights (no trainer)")
    ap.add_argument("--head", choices=("full", "lss", "lss-sharded"),
                    default="lss")
    ap.add_argument("--impl", choices=("ref", "pallas", "pallas_interpret"),
                    default=None,
                    help="pin the kernel-registry impl for the LSS head "
                         "(default: auto — pallas on TPU, ref elsewhere)")
    ap.add_argument("--dedup", choices=("quadratic", "bitonic"),
                    default=None,
                    help="pin the lss_topk cross-table dedup strategy "
                         "(default: auto — quadratic below the C "
                         "crossover, bitonic above)")
    ap.add_argument("--slab-dtype", choices=("fp32", "bf16", "int8"),
                    default=None,
                    help="bucket-major slab storage format for the LSS "
                         "index (default: lss_topk.slab_dtype strategy / "
                         "$REPRO_LSS_SLAB_DTYPE, auto -> fp32)")
    ap.add_argument("--no-lss", action="store_true",
                    help="legacy alias for --head full")
    ap.add_argument("--mode", choices=("generate", "decode"),
                    default="generate",
                    help="generate: blocking batched decode (or scoring "
                         "with --runtime async); decode: streaming "
                         "sessions through the AsyncRuntime")
    ap.add_argument("--runtime", choices=("sync", "async"), default="sync",
                    help="sync: blocking batched decode; async: open-loop "
                         "next-token scoring through the AsyncRuntime")
    ap.add_argument("--streams", type=int, default=8,
                    help="concurrent decode streams (KV-pool slots) for "
                         "--mode decode")
    ap.add_argument("--sessions", type=int, default=None,
                    help="decode sessions to submit (default: --batch)")
    ap.add_argument("--qps", type=float, default=500.0,
                    help="offered Poisson rate: requests/s for --runtime "
                         "async, sessions/s for --mode decode "
                         "(0 = burst: everything arrives at once)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request (or per-session) deadline; "
                         "already-late work is shed, not executed")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus text on "
                         "http://127.0.0.1:PORT/metrics (0 = ephemeral; "
                         "started before training so the whole run is "
                         "observable)")
    ap.add_argument("--audit-rate", type=float, default=None,
                    help="online label-recall audit: fraction of "
                         "LSS-served scoring requests re-ranked through "
                         "the exact full head (default: "
                         "$REPRO_OBS_AUDIT_RATE, 0 = off)")
    ap.add_argument("--hold-metrics", type=float, default=0.0,
                    help="keep the process (and /metrics) alive this many "
                         "seconds after serving, for one-shot scrapers")
    ap.add_argument("--refresh-interval", type=float, default=None,
                    help="online index refresh: re-run IUL on the "
                         "calibration snapshot every S seconds and swap "
                         "the new index in without a serving pause "
                         "(default: off; $REPRO_REFRESH_INTERVAL sets "
                         "the cadence once enabled)")
    ap.add_argument("--refresh-probation", type=float, default=None,
                    help="seconds the recall auditor watches a freshly "
                         "swapped index before trusting it "
                         "($REPRO_REFRESH_PROBATION)")
    ap.add_argument("--refresh-rollback-delta", type=float, default=None,
                    help="roll the swap back if audited recall drops "
                         "more than this below the pre-swap baseline "
                         "($REPRO_REFRESH_ROLLBACK_DELTA)")
    ap.add_argument("--coordinator", default=None,
                    help="multi-host serving: jax.distributed coordinator "
                         "host:port (default: $REPRO_DIST_COORDINATOR); "
                         "run one launcher per process with the same "
                         "flags, distinct --process-id; --mode decode "
                         "is not supported on a fleet")
    ap.add_argument("--num-processes", type=int, default=None,
                    help="multi-host serving: fleet size (default: "
                         "$REPRO_DIST_NUM_PROCESSES; <= 1 = single-host)")
    ap.add_argument("--process-id", type=int, default=None,
                    help="multi-host serving: this process's rank "
                         "(default: $REPRO_DIST_PROCESS_ID; 0 owns "
                         "admission, others mirror in follower_loop)")
    args = ap.parse_args()
    head = "full" if args.no_lss else args.head

    from repro.utils.compile_cache import use_compile_cache
    use_compile_cache()

    server = None
    if args.metrics_port is not None:
        from repro.obs.export import MetricsServer
        server = MetricsServer(port=args.metrics_port)
        print(f"metrics: {server.url}")
    if args.audit_rate is not None:
        import os
        from repro import obs as _obs
        os.environ[_obs.AUDIT_RATE_ENV] = str(args.audit_rate)

    # BEFORE any jax computation: gloo selection + distributed init
    # (None on every arg falls back to the REPRO_DIST_COORDINATOR-family
    # env vars)
    from repro.serve.multihost import (follower_loop, init_multihost,
                                       stop_followers)
    import os
    from repro.utils.compat import (DIST_COORDINATOR_ENV,
                                    DIST_NUM_PROCESSES_ENV)
    n_proc = (args.num_processes if args.num_processes is not None
              else int(os.environ.get(DIST_NUM_PROCESSES_ENV, "1")))
    coord = args.coordinator or os.environ.get(DIST_COORDINATOR_ENV)
    if args.mode == "decode" and n_proc > 1 and coord:
        # streaming decode sessions are not routed through the OP_DECODE
        # opcode channel: the leader's fused decode steps embed fleet
        # collectives the followers would never enter, deadlocking at
        # the first generate.  Checked BEFORE distributed init (which
        # blocks until the whole fleet connects) from the same
        # flag/env defaults init_multihost resolves, so every process
        # fails fast and consistently instead of hanging.
        raise SystemExit(
            "--mode decode is not supported with multi-host serving "
            "(--coordinator/--num-processes): use --mode generate for "
            "blocking fleet decode, or --runtime async for open-loop "
            "scoring")
    ctx = init_multihost(args.coordinator, args.num_processes,
                         args.process_id)
    if ctx is not None:
        from repro.obs.export import set_global_labels
        set_global_labels(process=str(ctx.process_id))
        print(f"multihost: process {ctx.process_id}/{ctx.n_processes} "
              f"({'leader' if ctx.is_leader else 'follower'}), "
              f"{ctx.n_shards} vocab shards")

    import jax
    import jax.numpy as jnp
    from repro.configs.registry import get_config
    from repro.configs.reduced import reduced_model_cfg
    from repro.core.lss import LSSConfig
    from repro.data.synthetic import lm_dataset
    from repro.models import transformer as T
    from repro.serve.engine import LMDecoder

    spec = get_config(args.arch)
    cfg = reduced_model_cfg(args.arch) if args.reduced else spec.model_cfg
    cfg = cfg._replace(vocab=min(cfg.vocab, 4096) if args.reduced
                       else cfg.vocab)

    toks = lm_dataset(0, 150_000, cfg.vocab, 33)
    if args.train_steps > 0:
        from repro.data.pipeline import ShardedBatchIterator
        from repro.train.trainer import TrainConfig, Trainer
        tc = TrainConfig(lr=3e-3, warmup_steps=15,
                         total_steps=args.train_steps, ckpt_every=10 ** 9)
        tr = Trainer(lambda p, b: T.lm_loss(p, b, cfg),
                     lambda k: T.init_params(k, cfg), tc)
        it = ShardedBatchIterator({"tokens": toks[:, :-1],
                                   "labels": toks[:, 1:]}, 64)
        state, _ = tr.fit(jax.random.PRNGKey(0), it, args.train_steps,
                          log_every=10 ** 9)
        params = state.params
    else:
        params = T.init_params(jax.random.PRNGKey(0), cfg)

    # hash geometry (k_bits, n_tables) from the registry config; a brief
    # IUL schedule so the fit stays a start-up cost
    lss_cfg = (spec.lss or LSSConfig())._replace(
        iul_epochs=4, iul_inner_steps=8, iul_lr=0.02)
    # decode mode: --streams slots; generate mode: one slot per prompt
    # row so the batch decodes in a single wave, like the pre-streaming
    # loop.  Pool width covers the warm call's 2-step floor.
    n_slots = args.streams if args.mode == "decode" else args.batch
    dec = LMDecoder(params, cfg, lss_cfg, impl=args.impl,
                    max_streams=n_slots,
                    max_len=16 + max(args.steps, 2), dedup=args.dedup,
                    slab_dtype=args.slab_dtype, spmd=ctx)
    if head != "full":
        dec.fit_lss(jax.random.PRNGKey(1), jnp.asarray(toks[:128]))
    prompt = jnp.asarray(toks[500:500 + args.batch, :16])

    refresher = None
    if (args.refresh_interval is not None and head != "full"
            and (ctx is None or ctx.is_leader)):
        from repro.serve.refresh import IndexRefresher, RefreshConfig
        rcfg = RefreshConfig.from_env(interval_s=args.refresh_interval)
        if args.refresh_probation is not None:
            rcfg = rcfg._replace(probation_s=args.refresh_probation)
        if args.refresh_rollback_delta is not None:
            rcfg = rcfg._replace(
                rollback_delta=args.refresh_rollback_delta)
        refresher = IndexRefresher(dec.engine, cfg=rcfg).start()
        print(f"index refresh: every {rcfg.interval_s}s, probation "
              f"{rcfg.probation_s}s, rollback delta "
              f"{rcfg.rollback_delta}")

    n_failed = 0
    try:
        if ctx is not None and not ctx.is_leader:
            # followers mirrored the (deterministic) train + fit above,
            # so their engine state matches the leader's; now replay the
            # leader's opcode stream until it stops us
            n = follower_loop(dec.engine, ctx, decoder=dec)
            print(f"follower {ctx.process_id}: {n} ops served")
        elif args.mode == "decode":
            n_failed = serve_decode(dec, toks, head, args)
        elif args.runtime == "async":
            n_failed = serve_async(dec, prompt, head, args)
        elif ctx is not None:
            from repro.serve.multihost import leader_generate
            out = leader_generate(ctx, dec, prompt, args.steps, head)
            print(f"decoded {out.shape} tokens on {ctx.n_processes} "
                  f"processes; head={head}")
            print(out[:2])
        else:
            out = dec.generate(prompt, steps=args.steps, head=head)
            print(f"decoded {out.shape} tokens; head={head}")
            print(out[:2])
            print(f"engine compiles (head, bucket): "
                  f"{dec.engine.compile_counts}")
    finally:
        # the exporter teardown gets its own finally: a wedged runtime
        # close (TimeoutError), a follower-stop failure, or an
        # interrupted hold must still release the /metrics port — a
        # leaked HTTP thread otherwise outlives the whole launch
        try:
            if refresher is not None:
                refresher.close()
            if ctx is not None and ctx.is_leader:
                stop_followers(ctx)
            if args.hold_metrics > 0:
                import time
                print(f"holding /metrics for {args.hold_metrics}s",
                      flush=True)
                time.sleep(args.hold_metrics)
        finally:
            if server is not None:
                server.close()
    if n_failed:
        raise SystemExit(f"{n_failed} requests or sessions failed")


def _n_failed(excs) -> int:
    """Failures among resolved futures/streams, explicit sheds aside."""
    from repro.serve.runtime.future import (DeadlineExceededError,
                                            QueueFullError)
    return sum(e is not None and not isinstance(
        e, (DeadlineExceededError, QueueFullError)) for e in excs)


def serve_decode(dec, toks, head: str, args) -> int:
    """Streaming decode: open-loop decode SESSIONS through the
    AsyncRuntime at --qps sessions/s, --streams concurrent slots.
    Returns the number of sessions that failed (sheds aside)."""
    import numpy as np
    from repro.serve import AsyncRuntime
    from repro.serve.runtime import submit_decode_open_loop

    n_sessions = (args.sessions if args.sessions is not None
                  else args.batch)
    prompts = np.asarray(toks[500:500 + n_sessions, :16], np.int32)
    # warm every compile the run needs (prefill, bucket-1 first-token
    # step, fused decode step — steps >= 2, or the fused step never
    # dispatches), THEN fetch the scheduler: the warm call must not
    # outgrow and replace the pool the runtime is about to own (the
    # decoder's max_len already covers the 2-step floor)
    dec.generate(prompts[:1], steps=2, head=head)
    sched = dec.scheduler(head=head, min_len=16 + args.steps)
    sched.reset_stats()
    deadline_s = (None if args.deadline_ms is None
                  else args.deadline_ms / 1e3)
    with AsyncRuntime(dec.engine, head=head, policy="shed",
                      default_deadline_s=deadline_s,
                      scheduler=sched, close_timeout_s=600.0) as rt:
        streams, _ = submit_decode_open_loop(
            rt, list(prompts), args.qps, max_new_tokens=args.steps, seed=0)
        rt.drain(timeout=600.0)
        s = rt.stats()
    excs = [st.exception(timeout=1.0) for st in streams]
    ok = sum(e is None for e in excs)
    print(f"streaming decode: head={head} streams={args.streams} "
          f"qps={args.qps} {ok}/{len(streams)} sessions served, "
          f"{s.n_decode_tokens} tokens")
    print(f"  {s.decode_tokens_per_s:,.0f} tok/s  "
          f"ttft p50={s.ttft_p50_ms:.2f} p95={s.ttft_p95_ms:.2f} "
          f"p99={s.ttft_p99_ms:.2f} ms (incl. queue wait)")
    print(f"  itl p50={s.itl_p50_ms:.2f} p95={s.itl_p95_ms:.2f} "
          f"p99={s.itl_p99_ms:.2f} ms  "
          f"slot occupancy={s.decode_slot_occupancy:.2f}")
    print(f"  shed: queue={s.n_shed_queue} deadline={s.n_shed_deadline}")
    print(f"engine compiles (head, shape): {dec.engine.compile_counts}")
    return _n_failed(excs)


def serve_async(dec, prompt, head: str, args) -> int:
    """Open-loop next-token scoring: prefill once, then submit each
    sequence's final hidden state as an independent rank request through
    the AsyncRuntime at the offered QPS.  Returns the number of requests
    that failed (sheds aside)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.runtime import AsyncRuntime, submit_open_loop

    hidden, _ = dec.T.prefill(dec.params, prompt, dec.cfg,
                              max_len=prompt.shape[1])
    h = np.asarray(hidden[:, -1].astype(jnp.float32))        # [B, d]
    reqs = np.tile(h, (max(1, args.steps), 1))               # B*steps reqs
    # compile every ladder bucket the run could coalesce into (any group
    # size <= the backlog's max chunk), so the measured segment reports
    # serving latency, not trace time — a cold 1-row bucket otherwise
    # costs a >1s trace and deadline-sheds the whole backlog behind it
    batcher = dec.engine.batcher
    b_max = batcher.bucket_for(min(reqs.shape[0], batcher.max_bucket))
    for b in [b for b in batcher.buckets if b <= b_max]:
        dec.engine.rank(np.zeros((b, reqs.shape[1]), np.float32),
                        head=head, record=False)
    deadline_s = (None if args.deadline_ms is None
                  else args.deadline_ms / 1e3)
    with AsyncRuntime(dec.engine, head=head, policy="shed",
                      default_deadline_s=deadline_s,
                      close_timeout_s=300.0) as rt:
        futs, _ = submit_open_loop(rt, reqs, args.qps, seed=0)
        rt.drain(timeout=300.0)
        s = rt.stats()
    excs = [f.exception() for f in futs]
    ok = sum(e is None for e in excs)
    aud = dec.engine.auditor
    if aud is not None:
        aud.drain()
        print(f"  audit recall@k={aud.recall:.4f} over {aud.n_rows} "
              f"rows (sampled at {aud.rate})")
    print(f"async runtime: head={head} qps={args.qps} "
          f"{ok}/{len(futs)} served")
    print(f"  throughput={s.throughput_rps:,.0f} rps  "
          f"p50={s.latency_p50_ms:.2f} p95={s.latency_p95_ms:.2f} "
          f"p99={s.latency_p99_ms:.2f} ms (incl. queue wait)")
    print(f"  batches={s.n_batches} occupancy={s.avg_batch_occupancy:.2f} "
          f"shed: queue={s.n_shed_queue} deadline={s.n_shed_deadline}")
    print(f"engine compiles (head, bucket): {dec.engine.compile_counts}")
    return _n_failed(excs)


if __name__ == "__main__":
    main()
