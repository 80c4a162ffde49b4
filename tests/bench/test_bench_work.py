"""Required operations and bytes of both configurations, against values
worked out by hand from the published shapes."""

import json

import pytest

from bench import peaks, work
from tinycells import REPO


def _cfg(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


def test_text8_lss_query_by_hand():
    # d+1 = 129, K = 11, L = 1, P = 1328: hash 2*129*11, slab 2*1328*129;
    # bytes: the query, 1328 fp32 rows of 129 and their int32 ids
    w = work.lss_query(_cfg("text8"))
    assert w.flops == 2 * 129 * 11 + 2 * 1328 * 129 == 345_462
    assert w.nbytes == 129 * 4 + 1328 * (129 * 4 + 4) == 691_076


def test_text8_full_head_call_by_hand():
    w = work.full_call(_cfg("text8"), 128)
    assert w.flops == 2 * 128 * 1_355_336 * 128 == 44_411_650_048
    # the fp32 WOL once, its (zero) bias, the 128 queries
    assert w.nbytes == 1_355_336 * 128 * 4 + 1_355_336 * 4 + 128 * 128 * 4
    assert w.nbytes == 699_418_912


def test_qwen2_decode_step_by_hand():
    cfg = _cfg("qwen2-0.5b")
    # per layer: q 896x896, k,v 896x128, o 896x896, gate/up/down 896x4864,
    # biases 896+128+128 -> 14,910,592 weights; 24 layers
    layer = 896 * 896 + 2 * 896 * 128 + 896 * 896 + 3 * 896 * 4864 + 1152
    assert layer == 14_910_592
    w = work.decode_step(cfg, "lss", [100, 200])
    attn = 4 * 896 * 24 * 300
    lss_q = 2 * 897 * 10 + 2 * 304 * 897
    assert w.flops == 2 * layer * 24 * 2 + attn + 2 * lss_q == 1_458_348_264
    kv = 24 * 2 * 2 * 64 * 2                      # bf16 K and V per position
    lss_b = 2 * (897 * 4 + 304 * (897 * 4 + 4)) + 897 * 10 * 4
    want = (layer * 24 * 2 + 49 * 896 * 4 + 2 * 896 * 2 + kv * 300 + lss_b)
    assert w.nbytes == want == 721_801_008
    full = work.decode_step(cfg, "full", [100, 200])
    head_b = 151_936 * 896 * 4 + 151_936 * 4 + 2 * 896 * 4
    assert full.nbytes == want - lss_b + head_b == 1_264_727_552
    assert full.flops == 2 * layer * 24 * 2 + attn + 2 * 2 * 151_936 * 896


def test_capacity_rule_matches_the_configs():
    for name in ("qwen2-0.5b", "text8"):
        cfg = _cfg(name)
        m, _ = work.head_width(cfg)
        assert work.lss_capacity(m, dict(cfg["lss"], capacity=0)) == \
            cfg["lss"]["capacity"]


def test_peaks_table_and_floor():
    p = peaks.peaks_for("TPU v5 lite")
    assert (p.flops_bf16, p.ops_int8, p.hbm_bytes_s) == (197e12, 393e12, 819e9)
    assert "TPU v5e" in p.source
    assert peaks.floor_time_s(197e12, 0, p) == pytest.approx(1.0)
    assert peaks.floor_time_s(0, 819e9, p) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_decode_steps_read_weights_once_per_step():
    cfg = _cfg("qwen2-0.5b")
    for head in ("lss", "full"):
        two = work.decode_steps(cfg, head, 2, [100, 200, 300, 400])
        one = (work.decode_step(cfg, head, [100, 200])
               + work.decode_step(cfg, head, [300, 400]))
        assert two.flops == pytest.approx(one.flops)
        assert two.nbytes == pytest.approx(one.nbytes)
    # one step more reads the bf16 layer weights, the norms and the fp32
    # head (its table and bias) once more
    ctx = [100, 200, 300, 400]
    more = (work.decode_steps(cfg, "full", 3, ctx).nbytes
            - work.decode_steps(cfg, "full", 2, ctx).nbytes)
    assert more == pytest.approx(14_910_592 * 24 * 2 + 49 * 896 * 4
                                 + 151_936 * 896 * 4 + 151_936 * 4)


def test_decode_step_mfu_reads_the_step_counter():
    from types import SimpleNamespace

    import numpy as np

    from bench.spec import metric_reader

    cfg = _cfg("qwen2-0.5b")
    p = peaks.peaks_for("TPU v5 lite")
    recs = [SimpleNamespace(prompt=np.zeros(100),
                            token_times=np.array([9.9, 10.2, 10.4, 11.5])),
            SimpleNamespace(prompt=np.zeros(50),
                            token_times=np.array([10.1, 10.2, 10.4]))]
    run = SimpleNamespace(
        cell=SimpleNamespace(config=cfg, mix={"head": "lss"}),
        impl=SimpleNamespace(records=recs), work=work, window_s=1.0,
        trace=SimpleNamespace(t_a=10.0, t_b=11.0, steps=2),
        work_floor=lambda w: peaks.floor_time_s(w.flops, w.nbytes, p))
    got = metric_reader("decode_step_mfu")(run)
    # rows received in the slice: tokens 1 and 2 of each session (token 0
    # of the second came from its prefill)
    want = work.decode_steps(cfg, "lss", 2, [101, 102, 51, 52])
    assert got == pytest.approx(100 * peaks.floor_time_s(
        want.flops, want.nbytes, p))
    run.trace.steps = 0
    assert metric_reader("decode_step_mfu")(run) is None
