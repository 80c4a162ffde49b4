"""Observability spine: bounded streaming histograms (O(1)-memory soak
regression), registry get-or-create semantics, Prometheus/JSON/trace
exporters, non-blocking stats snapshots, span integrity on EVERY
runtime failure path (queue shed, deadline shed, KV OOM, chunk-local
fault, close), the no-op disabled mode, and the online recall auditor
against an offline brute-force rerank."""

import inspect
import json
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.lss import LSSConfig
from repro.data.synthetic import lm_dataset
from repro.models import transformer as T
from repro.obs.audit import RecallAuditor
from repro.obs.export import MetricsServer, prometheus_text
from repro.obs.metrics import NOOP_METRIC
from repro.obs.tracing import NOOP_SPAN
from repro.serve import (AsyncRuntime, DeadlineExceededError, Engine,
                         KVPoolExhaustedError, LMDecoder, RuntimeClosedError)
from tools.check_metrics import parse_exposition


@pytest.fixture(autouse=True)
def _span_hygiene():
    """Every test starts with a clean trace ring and must leave no span
    open — the span-leak regression for every failure path below."""
    obs.reset_tracer()
    yield
    obs.assert_quiescent()
    obs.reset_tracer()


def _engine(m=512, d=32, top_k=5, buckets=(8,), audit_rate=None):
    w = jax.random.normal(jax.random.PRNGKey(0), (m, d))
    eng = Engine(None, w, None, LSSConfig(k_bits=4, n_tables=2),
                 top_k=top_k, head="lss", buckets=buckets,
                 audit_rate=audit_rate)
    eng.fit_random(jax.random.PRNGKey(1))
    return eng


# -------------------------------------------------------------- metrics --

def test_histogram_quantiles_exact_under_reservoir_cap():
    h = obs.Histogram("h_exact")
    rng = np.random.default_rng(0)
    vals = rng.lognormal(0.0, 2.0, size=1000)
    for v in vals:
        h.record(v)
    assert h.count == 1000
    assert h.quantile(50) == np.percentile(vals, 50)
    p50, p95, p99 = h.quantile((50, 95, 99))
    assert (p50, p95, p99) == tuple(np.percentile(vals, (50, 95, 99)))
    assert p50 <= p95 <= p99
    assert h.mean() == pytest.approx(vals.mean())


def test_histogram_empty_and_edge_values():
    h = obs.Histogram("h_edge")
    assert np.isnan(h.quantile(50)) and np.isnan(h.mean())
    assert all(np.isnan(v) for v in h.quantile((50, 99)))
    h.record(0.0)                       # non-positive -> first bucket
    h.record(-3.0)
    h.record(1e12)                      # beyond hi -> +inf bucket
    assert h.count == 3
    snap = h.bucket_snapshot()
    assert snap[0][1] == 2 and snap[-1] == (float("inf"), 3)


def test_soak_bounded_memory():
    """200k records must not grow the histogram past its construction
    footprint, and 3x the trace cap of spans must not grow the ring —
    the O(1)-memory regression for week-long serving windows."""
    h = obs.Histogram("h_soak", reservoir=512)
    n_buckets = len(h.bounds)
    rng = np.random.default_rng(1)
    for v in rng.lognormal(0.0, 3.0, size=200_000):
        h.record(v)
    assert h.count == 200_000
    assert len(h.sample()) == 512               # reservoir pinned at cap
    assert len(h.bounds) == n_buckets           # bucket grid never grows
    assert h.bucket_snapshot()[-1][1] == 200_000
    q = h.quantile((50, 95, 99))                # still unbiased + ordered
    assert all(np.isfinite(q)) and q[0] <= q[1] <= q[2]

    for i in range(3 * 4096):
        obs.start_span("soak", i=i).end()
    events = obs.trace_export()["traceEvents"]
    assert len(events) <= 4096                  # ring held its cap


def test_registry_get_or_create_and_type_mismatch():
    reg = obs.MetricsRegistry("t0", enabled=True)
    c = reg.counter("hits", "help text")
    assert reg.counter("hits") is c
    c.inc(), c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(TypeError):
        reg.gauge("hits")
    g = reg.gauge("depth")
    g.set(7)
    assert g.value == 7.0
    assert reg in obs.all_registries()


def test_registry_snapshot_collectors_and_reset():
    reg = obs.MetricsRegistry("t1", enabled=True)
    reg.counter("n").inc(4)
    reg.histogram("lat").record(0.5)
    reg.collect(lambda r: r.gauge("live").set(42.0))
    snap = reg.snapshot()
    assert snap["scope"] == "t1"
    assert snap["metrics"]["n"] == {"type": "counter", "value": 4.0}
    assert snap["metrics"]["live"]["value"] == 42.0
    assert snap["metrics"]["lat"]["count"] == 1
    json.dumps(snap)                            # JSON-ready by contract
    reg.reset()
    assert reg.counter("n").value == 0.0
    assert reg.histogram("lat").count == 0


def test_noop_mode_hands_out_shared_stubs():
    prev = obs.enabled()
    obs.set_enabled(False)
    try:
        reg = obs.MetricsRegistry("off")
        assert reg.counter("c") is NOOP_METRIC
        assert reg.histogram("h") is NOOP_METRIC
        NOOP_METRIC.inc(), NOOP_METRIC.record(1.0), NOOP_METRIC.set(2.0)
        assert np.isnan(NOOP_METRIC.quantile(50))
        assert reg not in obs.all_registries()
        span = obs.start_span("s")
        assert span is NOOP_SPAN
        span.event("e"), span.end()
        obs.event("instant")                    # swallowed, not recorded
        assert obs.trace_export()["traceEvents"] == []
    finally:
        obs.set_enabled(prev)


# ------------------------------------------------------------ exporters --

def test_prometheus_text_is_valid_exposition():
    reg = obs.MetricsRegistry("promtest", enabled=True)
    reg.counter("ptest_requests_total", "served").inc(3)
    reg.gauge("ptest_depth", "queue depth").set(2)
    h = reg.histogram("ptest_lat_seconds", "latency")
    for v in (0.001, 0.01, 0.1, 1.0, 10.0):
        h.record(v)
    text = prometheus_text([reg])
    families, errors = parse_exposition(text)
    assert errors == []
    assert families["ptest_requests_total"]["type"] == "counter"
    assert families["ptest_lat_seconds"]["type"] == "histogram"
    buckets = [(n, lab, v) for n, lab, v
               in families["ptest_lat_seconds"]["samples"]
               if n.endswith("_bucket")]
    counts = [v for _, _, v in buckets]
    assert counts == sorted(counts)             # cumulative + monotone
    assert counts[-1] == 5.0
    assert 'scope="promtest"' in text


def test_metrics_server_routes():
    reg = obs.MetricsRegistry("srvtest", enabled=True)
    reg.counter("srv_up").inc()
    with MetricsServer(port=0) as srv:
        base = f"http://{srv.host}:{srv.port}"
        with urllib.request.urlopen(srv.url) as r:
            assert r.status == 200
            body = r.read().decode()
        assert "srv_up" in body
        assert parse_exposition(body)[1] == []
        with urllib.request.urlopen(base + "/metrics.json") as r:
            snap = json.load(r)
        assert any(s.get("scope") == "srvtest" for s in snap["registries"])
        with urllib.request.urlopen(base + "/trace") as r:
            assert "traceEvents" in json.load(r)
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope")


def test_trace_export_chrome_format(tmp_path):
    s = obs.start_span("outer", rid=1)
    s.event("mark", detail="x")
    s.end("ok", extra=2)
    obs.event("global_instant", pid=3)
    hung = obs.start_span("hung")
    out = obs.trace_export(str(tmp_path / "trace.json"))
    hung.end("error")                           # close before teardown
    on_disk = json.loads((tmp_path / "trace.json").read_text())
    assert out["traceEvents"] == on_disk["traceEvents"]
    by_ph = {}
    for ev in out["traceEvents"]:
        by_ph.setdefault(ev["ph"], []).append(ev)
    [outer] = [e for e in by_ph["X"] if e["name"] == "outer"]
    assert outer["args"] == {"rid": 1, "extra": 2, "status": "ok"}
    assert outer["dur"] >= 0
    names = {e["name"] for e in by_ph["i"]}
    assert {"outer.mark", "global_instant"} <= names
    assert [e["name"] for e in by_ph["B"]] == ["hung"]


# -------------------------------------------- non-blocking stats snapshot --

def _held(lock) -> bool:
    """Is the lock held (Lock) / held by this thread (RLock)?"""
    if hasattr(lock, "locked"):
        return lock.locked()
    return lock._is_owned()


class _QuantileSpy:
    """Histogram wrapper that records whether a lock was held when
    quantile math ran — pinning the 'percentiles outside the component
    lock' contract without timing assumptions."""

    def __init__(self, h, lock):
        self._h, self._lock = h, lock
        self.locked_during: list[bool] = []

    def __getattr__(self, name):
        return getattr(self._h, name)

    def quantile(self, q):
        self.locked_during.append(_held(self._lock))
        return self._h.quantile(q)

    def mean(self):
        self.locked_during.append(_held(self._lock))
        return self._h.mean()


def test_stats_quantiles_run_outside_locks():
    eng = _engine()
    with AsyncRuntime(eng) as rt:
        for _ in range(8):
            rt.submit(np.zeros(32, np.float32))
        rt.drain(timeout=60.0)
        lat_spy = _QuantileSpy(rt._h_lat, rt._mu)
        dev_spy = _QuantileSpy(rt._h_device, rt._mu)
        rt._h_lat, rt._h_device = lat_spy, dev_spy
        s = rt.stats()
        rt._h_lat, rt._h_device = lat_spy._h, dev_spy._h
    assert s.latency_p50_ms > 0
    assert lat_spy.locked_during == [False]     # p50/p95/p99: one call
    assert dev_spy.locked_during == [False]

    espy = _QuantileSpy(eng._h_lat, eng.lock)
    eng._h_lat = espy
    m = eng.metrics()
    eng._h_lat = espy._h
    assert m.n_requests == 8
    assert espy.locked_during == [False]


# ------------------------------------------------ span integrity: sheds --

def test_queue_shed_spans_end_with_shed_queue():
    eng = _engine()
    rt = AsyncRuntime(eng, max_queue=2, policy="shed", start=False)
    futs = [rt.submit(np.zeros(32, np.float32)) for _ in range(5)]
    shed = [f for f in futs if f.done()]
    assert len(shed) == 3
    assert all(f.span.status == "shed_queue" for f in shed)
    rt.start()
    rt.drain(timeout=60.0)
    rt.close()
    served = [f for f in futs if f not in shed]
    assert all(f.span.status == "ok" for f in served)


def test_deadline_shed_spans_end_with_shed_deadline():
    eng = _engine()
    rt = AsyncRuntime(eng, start=False)
    futs = [rt.submit(np.zeros(32, np.float32), deadline_s=0.01)
            for _ in range(3)]
    time.sleep(0.05)
    rt.start()
    rt.drain(timeout=60.0)
    rt.close()
    for f in futs:
        assert isinstance(f.exception(5.0), DeadlineExceededError)
        assert f.span.status == "shed_deadline"


def test_close_fails_pending_spans_with_closed():
    eng = _engine()
    rt = AsyncRuntime(eng, start=False)
    f = rt.submit(np.zeros(32, np.float32))
    rt.close()
    assert isinstance(f.exception(5.0), RuntimeClosedError)
    assert f.span.status == "closed"


def test_chunk_fault_spans_end_with_error_and_isolate():
    eng = _engine(buckets=(8,))
    with AsyncRuntime(eng) as rt:
        bad = rt.submit(np.zeros(33, np.float32))    # d=33 != 32
        assert bad.exception(timeout=60.0) is not None
        good = rt.submit(np.zeros(32, np.float32))
        assert good.result(timeout=60.0) is not None
    assert bad.span.status == "error"
    assert good.span.status == "ok"
    chunk_status = [e["args"]["status"]
                    for e in obs.trace_export()["traceEvents"]
                    if e["ph"] == "X" and e["name"] == "chunk"]
    assert "error" in chunk_status and "ok" in chunk_status


def test_kv_oom_shed_span_and_event():
    """A decode session starved at a page boundary fails with
    KVPoolExhaustedError: its decode_session span must end shed_kv_oom,
    the survivor's must end ok, and the shed_kv_oom instant event must
    land in the trace."""
    cfg = T.TransformerConfig(name="tp-obs", n_layers=2, d_model=32,
                              n_heads=2, n_kv_heads=2, head_dim=16,
                              d_ff=64, vocab=256, dtype=jnp.float32,
                              kv_chunk=32)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    toks = np.asarray(lm_dataset(0, 8 * 17, 256, 17))
    dec = LMDecoder(params, cfg, max_streams=2, max_len=16,
                    kv_layout="paged", kv_page_tokens=4, kv_pages=4)
    sched = dec.scheduler(head="full")
    rt = AsyncRuntime(dec.engine, scheduler=sched, start=False)
    starved = rt.submit_decode(toks[0, :3], max_new_tokens=10)
    survivor = rt.submit_decode(toks[1, :5], max_new_tokens=2)
    rt.start()
    rt.drain(timeout=120.0)
    rt.close()
    assert isinstance(starved.exception(), KVPoolExhaustedError)
    assert starved.span.status == "shed_kv_oom"
    assert survivor.finish_reason == "max_tokens"
    assert survivor.span.status == "ok"
    oom_events = [e for e in obs.trace_export()["traceEvents"]
                  if e["name"] == "shed_kv_oom"]
    assert oom_events


# ------------------------------------- scoped spans on the profiler clock --

SCOPED = {"runtime.dispatch", "runtime.handoff", "runtime.record",
          "decode.tick", "decode.admit"}


def _tiny_lm(name, **kw):
    cfg = T.TransformerConfig(name=name, n_layers=2, d_model=32, n_heads=2,
                              n_kv_heads=2, head_dim=16, d_ff=64, vocab=256,
                              dtype=jnp.float32, kv_chunk=32)
    params = T.init_params(jax.random.PRNGKey(3), cfg)
    return LMDecoder(params, cfg, max_streams=2, max_len=16, **kw)


def _serve_chunk_and_session():
    """One score chunk through an AsyncRuntime, then one decode session
    admitted and ticked to its end by a standalone scheduler."""
    eng = _engine()
    with AsyncRuntime(eng) as rt:
        rt.submit(np.zeros(32, np.float32)).result(timeout=60.0)
    sched = _tiny_lm("tp-obs-prof").scheduler(head="full")
    st = sched.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    sched.run(timeout=120.0)
    assert st.finish_reason == "max_tokens"


def _profiled(tmp_path, fn):
    """Run ``fn`` under a CPU ``jax.profiler`` trace with the Python
    tracer off, as the benchmark's traced runs record, and read the
    trace back with the benchmark's own reader."""
    from bench import trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return trace.load_events(trace.xplane_file(str(tmp_path)))


def test_scoped_spans_reach_the_profiler_host_plane(tmp_path):
    _serve_chunk_and_session()                  # compile outside the trace
    obs.reset_tracer()
    evs = _profiled(tmp_path, _serve_chunk_and_session)
    host = [e for e in evs if e.plane.startswith("/host:CPU")]
    names = {e.name for e in host}
    assert {"runtime.dispatch", "runtime.record", "decode.tick",
            "decode.admit"} <= names
    ticks = [e for e in host if e.name == "decode.tick"]
    admits = [e for e in host if e.name == "decode.admit"]
    assert len(admits) == 1
    a = admits[0]
    assert any(t.start <= a.start and a.start + a.dur <= t.start + t.dur
               for t in ticks)
    # the same spans are in the ring, with their attributes
    ring = {e["name"]: e for e in obs.trace_export()["traceEvents"]
            if e["ph"] == "X"}
    assert ring["runtime.record"]["args"]["bucket"] == 8
    assert ring["decode.admit"]["args"]["status"] == "ok"
    assert {"tick", "prefill"}.isdisjoint(ring)


def test_idle_dispatcher_records_no_span():
    """An idle runtime polls its queue and ticks its scheduler every
    50 ms; idle polls are not work, so they must not push the operator's
    ``request`` / ``chunk`` / ``decode_session`` spans out of the ring."""
    dec = _tiny_lm("tp-obs-idle")
    with AsyncRuntime(dec.engine, scheduler=dec.scheduler(head="full")):
        time.sleep(0.3)
    assert obs.trace_export()["traceEvents"] == []


def test_disabled_obs_leaves_no_span_in_ring_or_trace(tmp_path):
    prev = obs.enabled()
    obs.set_enabled(False)
    try:
        evs = _profiled(tmp_path, _serve_chunk_and_session)
        assert obs.trace_export()["traceEvents"] == []
    finally:
        obs.set_enabled(prev)
    assert SCOPED.isdisjoint(e.name for e in evs)


def test_scoped_span_status_follows_the_block():
    with pytest.raises(ValueError):
        with obs.span("test.step", k=1):
            raise ValueError("boom")
    with obs.span("test.shed") as s:
        s.end("shed_queue")                     # first terminal status wins
    with obs.span("test.ok"):
        pass
    status = {e["name"]: e["args"]["status"]
              for e in obs.trace_export()["traceEvents"] if e["ph"] == "X"}
    assert status == {"test.step": "error", "test.shed": "shed_queue",
                      "test.ok": "ok"}


def test_join_oom_ends_the_admit_span_shed_kv_oom():
    """A prompt the paged arena cannot hold fails at join: its
    ``decode.admit`` span ends shed_kv_oom and counts no join."""
    dec = _tiny_lm("tp-obs-joinoom", kv_layout="paged", kv_page_tokens=4,
                   kv_pages=2)                  # scratch + 1 page
    sched = dec.scheduler(head="full")
    st = sched.submit(np.arange(1, 7, dtype=np.int32), max_new_tokens=2)
    sched.run(timeout=120.0)
    assert isinstance(st.exception(), KVPoolExhaustedError)
    [admit] = [e for e in obs.trace_export()["traceEvents"]
               if e["ph"] == "X" and e["name"] == "decode.admit"]
    assert admit["args"]["status"] == "shed_kv_oom"
    assert sched.stats().n_joined == 0


def test_queue_and_join_waits_are_counted():
    eng = _engine()
    rt = AsyncRuntime(eng, start=False)
    futs = [rt.submit(np.zeros(32, np.float32)) for _ in range(3)]
    time.sleep(0.05)
    rt.start()
    for f in futs:
        f.result(timeout=60.0)
    rt.close()
    s = rt.stats()
    assert s.n_dispatched == 3
    assert 3 * 0.05 <= s.queue_wait_s_total < 3 * 60.0

    dec = _tiny_lm("tp-obs-joinwait")
    sched = dec.scheduler(head="full")
    rt = AsyncRuntime(dec.engine, scheduler=sched, start=False)
    st = rt.submit_decode(np.arange(1, 6, dtype=np.int32), max_new_tokens=2)
    time.sleep(0.05)
    rt.start()
    rt.drain(timeout=120.0)
    rt.close()
    assert st.finish_reason == "max_tokens"
    s = rt.stats()
    assert s.n_joined == 1 and s.n_dispatched == 0
    assert 0.05 <= s.join_wait_s_total < 120.0
    sched.reset_stats()
    ds = sched.stats()
    assert ds.n_joined == 0 and ds.join_wait_s_total == 0.0


def _jitted(step):
    """The jitted program and head operands a step closure calls."""
    params = inspect.signature(step).parameters
    return params["_j"].default, params["_ops"].default


@pytest.mark.parametrize("kind", ["full", "lss"])
def test_jitted_steps_lower_to_named_modules(kind):
    eng = _engine()
    j, ops = _jitted(eng._step(kind, 8))
    assert (f"module @jit_score_step_{kind} "
            in j.lower(jnp.zeros((8, 32)), *ops).as_text())
    dec = _tiny_lm(f"tp-obs-name-{kind}")
    if kind != "full":
        dec.engine.fit_random(jax.random.PRNGKey(1))
    sched = dec.scheduler(head=kind)
    j, ops = _jitted(dec.engine.decode_logits(kind, sched._tag, sched._body))
    text = j.lower(sched.params, sched.tok, *sched.pool.step_operands(),
                   *ops).as_text()
    assert f"module @jit_decode_step_{kind} " in text
    assert "raw_step" not in text


def test_step_names_replace_the_head_kinds_dash():
    from repro.serve.engine import _name_step

    def f(x):
        return x
    _name_step(f, "decode_step", "lss-sharded")
    assert "module @jit_decode_step_lss_sharded " in \
        jax.jit(f).lower(jnp.zeros(2)).as_text()


# --------------------------------------------------------- recall audit --

def test_audit_recall_matches_offline_brute_force_exactly():
    """At rate 1.0 the auditor's cumulative recall must EQUAL the
    offline brute-force recall of the same served traffic (integer
    hit accumulation, not a sampling estimate)."""
    eng = _engine(buckets=(8,), audit_rate=1.0)
    assert eng.auditor is not None and eng.auditor.rate == 1.0
    rng = np.random.default_rng(7)
    xs = rng.standard_normal((32, 32)).astype(np.float32)
    for i in range(0, 32, 8):
        eng.rank(xs[i:i + 8], head="lss", record=True)
    eng.auditor.drain()
    online = eng.auditor.recall
    assert eng.auditor.n_rows == 32
    eng.auditor.close()

    hits = total = 0
    for i in range(0, 32, 8):
        x = xs[i:i + 8]
        served = np.asarray(eng.rank(x, head="lss", record=False).ids)
        exact = np.asarray(eng.rank(x, head="full", record=False).ids)
        hit = (exact[:, :, None] == served[:, None, :]).any(-1)
        hits, total = hits + int(hit.sum()), total + hit.size
    assert abs(online - hits / total) < 1e-6


def test_audit_never_audits_exact_head_traffic():
    eng = _engine(buckets=(8,), audit_rate=1.0)
    eng.rank(np.zeros((8, 32), np.float32), head="full", record=True)
    eng.auditor.drain()
    assert eng.auditor.n_rows == 0              # full head needs no audit
    eng.auditor.close()


def test_audit_backlog_bounded_drops_count_as_staleness():
    """A full audit queue sheds the sample (serving never blocks) and
    counts it on the staleness counter."""
    gate = threading.Event()

    class _SlowEngine:
        def rank(self, x, head="full", record=False):
            gate.wait(timeout=10.0)

            class Out:
                ids = np.zeros((1, 2), np.int64)
            return Out()

    reg = obs.MetricsRegistry("audittest", enabled=True)
    aud = RecallAuditor(_SlowEngine(), 1.0, queue_cap=1, registry=reg)
    row = (np.zeros((1, 4), np.float32), np.zeros((1, 2), np.int64))
    assert aud.offer(*row)                      # worker takes it, blocks
    deadline = time.monotonic() + 5.0
    while aud._q.qsize() and time.monotonic() < deadline:
        time.sleep(0.005)                       # wait for the dequeue
    assert aud.offer(*row)                      # refills the cap-1 queue
    assert not aud.offer(*row)                  # full -> shed, not block
    assert reg.counter("lss_audit_dropped_total").value == 1.0
    gate.set()
    aud.drain()
    aud.close()
    assert aud.n_rows == 2
    assert reg.counter("lss_audit_rows_total").value == 2.0


def test_audit_offer_thunk_only_materialized_when_sampled():
    calls = []

    class _NullEngine:
        def rank(self, x, head="full", record=False):
            class Out:
                ids = np.zeros((1, 2), np.int64)
            return Out()

    reg = obs.MetricsRegistry("thunktest", enabled=True)
    aud = RecallAuditor(_NullEngine(), 0.0, registry=reg)
    aud.offer(lambda: calls.append(1), np.zeros((1, 2), np.int64))
    assert calls == []                          # rate 0: thunk never runs
    aud.close()
    aud2 = RecallAuditor(_NullEngine(), 1.0, registry=reg, seed=1)
    aud2.offer(lambda: (calls.append(1),
                        np.zeros((1, 4), np.float32))[1],
               np.zeros((1, 2), np.int64))
    aud2.drain()
    aud2.close()
    assert calls == [1]                         # rate 1: materialized once
