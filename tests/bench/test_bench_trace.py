"""The reduction from a profiler trace to numbers: on hand-made events,
and on a small trace recorded on a TPU v5e (``fixtures/``, made by
``fixtures/record_trace.py``)."""

import json
from pathlib import Path

import pytest

from bench import trace
from bench.trace import Event

FIX = Path(__file__).resolve().parent / "fixtures"


def _op(name, start, dur, plane="/device:TPU:0"):
    return Event(plane, trace.OPS_LINE, name, start, dur)


def test_busy_is_the_union_averaged_over_devices():
    evs = [_op("a", 0.0, 2.0), _op("b", 1.0, 2.0), _op("c", 5.0, 1.0),
           _op("d", 0.0, 1.0, "/device:TPU:1"),
           Event("/device:TPU:0", trace.MODULES_LINE, "jit_x(1)", 0.0, 9.0)]
    assert trace.busy_s(evs) == pytest.approx((4.0 + 1.0) / 2)


def test_self_time_subtracts_nested_ops():
    evs = [_op("%while.1 = s32[] while()", 0.0, 10.0),
           _op("%fusion.2 = f32[8] fusion()", 1.0, 3.0),
           _op("%fusion.2 = f32[8] fusion()", 5.0, 3.0),
           _op("%pad.3 = f32[8] pad()", 12.0, 1.0)]
    st = trace.self_times(evs)
    assert st["%while.1 = s32[] while()"] == pytest.approx(4.0)
    assert st["%fusion.2 = f32[8] fusion()"] == pytest.approx(6.0)
    top = trace.top_device_ops(evs, n=2)
    assert [t[0] for t in top] == ["fusion.2 fusion f32[8]",
                                   "while.1 while s32[]"]


def test_short_op_keeps_instruction_opcode_shape():
    name = ("%pad.19 = f32[1024,384,1024]{2,1,0:T(8,128)} pad(f32[1024,304,"
            "897]{2,1,0:T(8,128)} %bitcast.6, f32[]{:T(128)} %constant.46)")
    assert trace.short_op(name) == "pad.19 pad f32[1024,384,1024]"


def test_idle_gaps_are_named_by_the_host():
    evs = [_op("a", 0.0, 1.0), _op("b", 3.0, 1.0), _op("c", 4.5, 0.1),
           Event("/host:CPU", "python3", "$scheduler.py:379 _prefill",
                 0.9, 2.2),
           Event("/host:CPU", "python3", "$runtime.py:499 _dispatch_loop",
                 0.0, 10.0)]
    gaps = trace.idle_gaps(evs, n=5, min_gap_s=0.01)
    assert gaps[0] == ["$scheduler.py:379 _prefill @1000.000ms",
                       pytest.approx(2.0)]
    assert gaps[1][0].startswith("$runtime.py:499 _dispatch_loop @4000")
    assert gaps[1][1] == pytest.approx(0.5)


def test_kernel_events_match_the_instruction_name():
    evs = [_op("%lss_topk_pallas.1 = (f32[8,5]) custom-call()", 0, 1),
           _op("%lss_topk_pallas = (f32[8,5]) custom-call()", 2, 1),
           _op("%lss_topk_pallas_x = f32[1] add()", 4, 1)]
    assert len(trace.kernel_events(evs, "lss_topk_pallas")) == 2


@pytest.fixture(scope="module")
def recorded():
    evs = trace.load_events(str(FIX / "decode_lss.xplane.pb"))
    meta = json.loads((FIX / "decode_lss.json").read_text())
    return evs, meta


def test_recorded_trace_has_one_tpu(recorded):
    evs, _ = recorded
    assert trace.device_planes(evs) == ["/device:TPU:0"]


def test_recorded_prefills_and_kernel_calls(recorded):
    evs, meta = recorded
    pre = trace.modules(evs, "jit__prefill_jit")
    assert len(pre) == meta["prefills"]
    assert all(0 < e.dur < 0.1 for e in pre)
    kern = trace.kernel_events(evs, "lss_topk_pallas")
    assert len(kern) == meta["first_token_ranks"] + meta["fused_steps"]
    assert 0 < sum(e.dur for e in kern) < trace.busy_s(evs)


def test_recorded_busy_and_idle(recorded):
    evs, _ = recorded
    ops = trace.ops(evs)
    span = max(e.start + e.dur for e in ops) - min(e.start for e in ops)
    busy = trace.busy_s(evs)
    assert 0 < busy < span
    gaps = trace.idle_gaps(evs)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in gaps) <= span - busy + 1e-9
    top = trace.top_device_ops(evs)
    assert len(top) == 10
    assert sum(t[1] for t in top) <= busy + 1e-9
