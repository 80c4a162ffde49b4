"""The readers of the program's own spans, counters and step programs, on
hand-made events and counters: each gives its mean, and ``None`` where
what it reads did not run (as on a program without the spans)."""

from types import SimpleNamespace

import pytest

from bench import spans, spec, trace
from bench.trace import Event
from tinycells import REPO


def _read(metric, run):
    return spec.metric_reader(metric, REPO)(run)


def _host(name, start, dur, plane="/host:CPU", line="repro-dispatch"):
    return Event(plane, line, name, start, dur)


def _module(name, start, dur):
    return Event("/device:TPU:0", trace.MODULES_LINE, name, start, dur)


def _traced(events, impl=None):
    return SimpleNamespace(trace=SimpleNamespace(events=events),
                           trace_mod=trace, impl=impl)


def test_host_spans_match_names_exactly_on_host_planes():
    evs = [_host("runtime.record", 0.0, 1.0),
           _host("runtime.record.x", 0.0, 1.0),
           _host("runtime.recor", 0.0, 1.0),
           Event("/device:TPU:0", trace.OPS_LINE, "runtime.record", 0, 1),
           _host("runtime.record", 2.0, 3.0, plane="/host:CPU 1")]
    assert [e.dur for e in spans.host_spans(evs, "runtime.record")] == \
        [1.0, 3.0]
    assert spans.mean_ms([]) is None
    assert spans.mean_ms(spans.host_spans(evs, "runtime.record")) == \
        pytest.approx(2000.0)


@pytest.mark.parametrize("metric,name", [("record_ms", "runtime.record"),
                                         ("admit_ms", "decode.admit")])
def test_span_readers_average_their_spans(metric, name):
    evs = [_host(name, 0.0, 0.004), _host(name, 1.0, 0.008),
           _host("decode.tick", 0.0, 0.5), _host("runtime.handoff", 2.0, 0.1)]
    assert _read(metric, _traced(evs)) == pytest.approx(6.0)
    assert _read(metric, _traced(evs[2:])) is None


@pytest.mark.parametrize("metric,prefix,other", [
    ("first_token_rank_ms", "jit_score_step_lss", "jit_decode_step_lss"),
    ("decode_step_ms", "jit_decode_step_full", "jit_score_step_full")])
def test_module_readers_average_their_programs(metric, prefix, other):
    evs = [_module(f"{prefix}(123)", 0.0, 0.002),
           _module(f"{prefix}(123)", 1.0, 0.004),
           _module(f"{other}(9)", 2.0, 0.5),
           _module("jit__prefill_jit(7)", 3.0, 0.5),
           _host(f"PjitFunction({prefix[4:]})", 0.0, 1.0)]
    assert _read(metric, _traced(evs)) == pytest.approx(3.0)
    # a program whose steps all run as one unnamed module reads nothing
    old = [_module("jit_raw_step(5)", 0.0, 0.002)]
    assert _read(metric, _traced(old)) is None


def _stats(wait, n):
    return SimpleNamespace(queue_wait_s_total=wait, n_dispatched=n)


def test_queue_wait_is_the_window_delta_of_the_counters():
    impl = SimpleNamespace(_c0=_stats(1.0, 100),
                           rt=SimpleNamespace(stats=lambda: _stats(1.6, 400)))
    assert _read("queue_wait_ms", _traced([], impl)) == pytest.approx(2.0)
    impl.rt = SimpleNamespace(stats=lambda: _stats(1.0, 100))
    assert _read("queue_wait_ms", _traced([], impl)) is None    # none ran
    # a program whose stats lack the counters, and a decode cell
    impl._c0 = SimpleNamespace(n_batches=3)
    assert _read("queue_wait_ms", _traced([], impl)) is None
    assert _read("queue_wait_ms",
                 _traced([], SimpleNamespace(counters={}))) is None


def test_join_wait_is_the_counters_mean():
    impl = SimpleNamespace(counters={"join_wait_s_total": 0.5,
                                     "n_joined": 50})
    assert _read("join_wait_ms", _traced([], impl)) == pytest.approx(10.0)
    impl.counters = {"join_wait_s_total": 0.0, "n_joined": 0}
    assert _read("join_wait_ms", _traced([], impl)) is None
    # a score cell's counters, and a program without the counter
    impl.counters = {"n_batches": 4, "avg_batch_occupancy": 0.5}
    assert _read("join_wait_ms", _traced([], impl)) is None


def _op(start, dur):
    return Event("/device:TPU:0", trace.OPS_LINE, "%fusion.1 = f32[8] "
                 "fusion(...)", start, dur)


def _slice():
    """Device ops with idle gaps [1, 2), [3, 3.5) and [4, 6); a tick
    covering the first gap with an admission inside it covering half of
    it, a runtime call inside the admission, a handoff over the last
    gap's first half."""
    return [_op(0.0, 1.0), _op(2.0, 1.0), _op(3.5, 0.5), _op(6.0, 1.0),
            _host("decode.tick", 0.9, 1.2), _host("decode.admit", 1.5, 0.6),
            _host("np.asarray(jax.Array)", 1.5, 0.55),
            _host("runtime.handoff", 4.0, 1.0)]


def test_idle_in_spans_counts_each_idle_second_once():
    got = spans.idle_in_spans(_slice())
    assert got["idle_s"] == pytest.approx(3.5)
    assert got["in_spans_s"] == pytest.approx(2.0)
    assert got["by_name_s"] == pytest.approx(
        {"decode.tick": 1.0, "decode.admit": 0.5, "runtime.handoff": 1.0})


def test_gaps_are_named_by_the_innermost_program_span():
    got = spans.gaps_by_span(_slice())
    assert [g[0] for g in got] == ["runtime.handoff @4000.000ms",
                                   "decode.admit @1000.000ms",
                                   "no program span @3000.000ms"]
    assert [g[1] for g in got] == pytest.approx([2.0, 1.0, 0.5])
    # the breakdown's own rule names the runtime call inside the span
    assert trace.idle_gaps(_slice())[1][0].startswith("np.asarray")
    assert spans.gaps_by_span(_slice(), n=1, min_gap_s=2.5) == []


def test_a_program_without_spans_has_no_idle_inside_them():
    evs = trace.load_events(str(REPO / "tests" / "bench" / "fixtures"
                                / "decode_lss.xplane.pb"))
    got = spans.idle_in_spans(evs)
    assert got["idle_s"] > 0 and got["in_spans_s"] == 0.0
    assert got["by_name_s"] == {}
    assert all(g[0].startswith("no program span")
               for g in spans.gaps_by_span(evs))


def test_span_report_reads_spans_modules_and_host_queue_wait():
    from bench import span_report
    evs = _slice() + [_module("jit_decode_step_lss(12)", 0.0, 0.004),
                      _module("jit_decode_step_lss(12)", 2.0, 0.006)]
    got = span_report.report(evs, _stats(1.0, 100), _stats(1.3, 250))
    assert got["spans"]["decode.admit"] == [1, pytest.approx(600.0)]
    assert got["modules"] == {"jit_decode_step_lss": [2, pytest.approx(5.0)]}
    assert got["queue_wait_ms_host"] == pytest.approx(2.0)
    assert got["idle"]["in_spans_s"] == pytest.approx(2.0)
    # a decode cell, and a program whose stats lack the counters
    assert "queue_wait_ms_host" not in span_report.report(evs)
    assert "queue_wait_ms_host" not in span_report.report(
        evs, SimpleNamespace(n_batches=1), SimpleNamespace(n_batches=2))
