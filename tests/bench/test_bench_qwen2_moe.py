"""The qwen2_moe architecture module, ``bench/models/qwen2_moe.py``: it has
what a decode cell needs of an architecture, its reference imports
nothing of the program, a tiny MoE decode cell runs through
``bench/run.py`` on a CPU to a correct result, the same served tokens
read against a reference whose held experts are zeroed come out wrong,
and its expert-work count is what its docstring says."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

from bench import check, peaks, run, spec
from tinycells import REPO, decode_mix, jax_config_restored, make_root

TINY_MOE = {
    "name": "tiny-moe", "family": "lm", "model_type": "qwen2_moe",
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "vocab_size": 1024,
    "tie_word_embeddings": False, "qkv_bias": True, "rms_norm_eps": 1e-06,
    "rope_theta": 10000.0, "decoder_sparse_step": 1, "mlp_only_layers": [],
    "num_experts": 4, "expert_offset": 2, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 96,
    "norm_topk_prob": False, "torch_dtype": "bfloat16",
    "matmul_precision": "default",
    "lss": {"k_bits": 4, "n_tables": 1, "capacity": 128},
    "published": {"num_experts": 16},
}
CELL = "tiny-moe.decode.lss"
# set between the program's and the control's readings at this size on a
# CPU, 16 sessions a sample (seeds 1-5, 2**31 + 5, 2**31 + 7): gap <=
# 0.061 and miss <= 0.024 against fp8 gaps >= 0.20 and misses >= 0.10;
# with the held experts zeroed the gap reads >= 1.38.  With 8 experts,
# top-2 and expert outputs at 4x the fan-in scale, a routing flip between
# the 2nd and 3rd expert (each with ~0.3 of the weight) read a gap of 1.26
# on a sound run: top-4 of 16 keeps the 4th's weight small, as top-4 of 60
# does at full size.
LIMITS = {"gap": {"limit": 0.08}, "miss": {"limit": 0.045}}
FIVE = ("make_params", "program_config", "head_table", "hidden",
        "body_work")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny checkout with one more configuration and its LSS decode
    cell, entered where ``tiny-lm.decode.lss`` is."""
    root = make_root(tmp_path_factory.mktemp("tiny-moe"))
    (root / "bench" / "configs" / "tiny-moe.json").write_text(
        json.dumps(TINY_MOE))
    (root / "bench" / "limits" / f"{CELL}.json").write_text(
        json.dumps({"numbers": LIMITS}))
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-moe", "source": "tiny",
                          "file": "bench/configs/tiny-moe.json",
                          "reduced": ["num_experts"], "why": "t"})
    bm["workloads"].append({"name": CELL, "config": "tiny-moe",
                            "traffic": "tiny-decode.lss", "chips": 1,
                            "why": "t"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny-lm.decode.lss" in m.get("workloads", ()) \
                or m["name"] == "moe_gmm_roofline.decode":
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


def test_module_has_what_a_decode_cell_needs():
    arch = spec.arch(TINY_MOE, REPO)
    assert arch.__file__ == str(REPO / "bench" / "models" / "qwen2_moe.py")
    for name in FIVE + ("expert_work",):
        assert callable(getattr(arch, name))
    p = arch.make_params(TINY_MOE, 3)
    assert arch.head_table(p) is p["lm_head"]
    moe = p["layers"]["moe"]
    assert moe["router"].shape == (2, 64, 16)           # every expert
    assert moe["w_gate"].shape == (2, 4, 64, 32)        # the held ones
    cfg = arch.program_config(TINY_MOE)
    assert (cfg.n_experts, cfg.moe_held, cfg.moe_held_offset,
            cfg.moe_norm_topk) == (16, 4, 2, False)


def test_reference_imports_nothing_of_the_program():
    code = f"""
import json, sys
import numpy as np
sys.path[:0] = [{str(REPO)!r}, {str(REPO / "src")!r}]
from bench import spec
cfg = json.loads({json.dumps(json.dumps(TINY_MOE))})
arch = spec.arch(cfg)
params = arch.make_params(cfg, 3)
h = np.asarray(arch.hidden(params, cfg, np.arange(10) * 7 % 1024, "highest", 16))
assert h.shape == (10, cfg["hidden_size"]) and np.isfinite(h).all()
print(json.dumps(sorted(m for m in sys.modules
                        if m == "repro" or m.startswith("repro."))))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-2000:]
    assert json.loads(p.stdout.strip().splitlines()[-1]) == []


def test_tiny_moe_cell_runs_to_a_correct_result(root, capsys, monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    with jax_config_restored():
        assert run.main(["--workload", CELL, "--seed", str(2 ** 31 + 5),
                         "--seconds", "1", "--trace", "1"],
                        require_chip=False, root=root) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"gap", "miss"}
    assert "decode_step_mfu" in res["metrics"]
    # no moe_gmm_pallas runs on a CPU: the kernel's reader finds nothing
    assert "moe_gmm_roofline.decode" not in res["metrics"]


@pytest.fixture(scope="module")
def served():
    """What a tiny MoE decode cell's window served, and its weights."""
    from bench.lm_decode import DecodeCell
    mix = decode_mix("lss")
    seed = 2 ** 31 + 7
    with jax_config_restored():
        impl = DecodeCell(TINY_MOE, mix, seed)
        impl.setup(1.0)
        impl.run(1.0)
        impl.close()
        sample = check.samples(impl, mix)
        assert impl.rt.stats().moe_assignments > 0      # counted in serving
        impl.free()
    return impl.params, seed, mix, sample


def test_served_tokens_against_zeroed_experts_read_incorrect(served):
    params, seed, mix, sample = served
    sound = check.compare(params, seed, TINY_MOE, mix, sample)
    assert all(sound[k] <= v["limit"] for k, v in LIMITS.items()), sound
    moe = params["layers"]["moe"]
    cut = dict(params, layers=dict(params["layers"], moe=dict(
        moe, w_down=jnp.zeros_like(moe["w_down"]))))
    broken = check.compare(cut, seed, TINY_MOE, mix, sample)
    assert any(broken[k] > v["limit"] for k, v in LIMITS.items()), broken


def test_expert_work_counts_what_its_docstring_says():
    arch = spec.arch(TINY_MOE, REPO)
    d, f = 64, 32
    w = arch.expert_work(TINY_MOE, assignments=10, active=3)
    assert w.flops == 10 * 2 * 3 * d * f
    assert w.nbytes == 3 * 3 * d * f * 2 + 10 * (4 * d + 8 * f + 2 * f
                                                  + 4 * d)
    assert arch.expert_work(TINY_MOE, 0, 0) == (0, 0)


def test_body_work_counts_the_held_experts_expected_active():
    """At the benchmark's configuration and 32 rows a step, ~89% of the
    15 held experts are active (15 * (1 - (56/60)^32)), and a step reads
    ~1.39 GB of expert weights besides ~0.62 GB of attention, router and
    shared expert."""
    cfg = json.loads((REPO / "bench" / "configs" /
                      "qwen2-moe-a2.7b.json").read_text())
    arch = spec.arch(cfg, REPO)
    act = arch.expected_active(cfg, 32)
    assert act == pytest.approx(15 * (1 - (56 / 60) ** 32))
    one = arch.body_work(cfg, 1, [1] * 32)
    two = arch.body_work(cfg, 2, [1] * 64)
    assert two.nbytes == pytest.approx(2 * one.nbytes)
    assert two.flops == pytest.approx(2 * one.flops)
    expert_bytes = act * 3 * 2048 * 1408 * 2 * 6
    assert expert_bytes == pytest.approx(1.39e9, rel=0.01)
    weights = one.nbytes - 32 * (2048 * 2 + arch.kv_bytes_per_position(cfg))
    assert weights - expert_bytes == pytest.approx(0.62e9, rel=0.01)


def test_moe_gmm_roofline_reads_only_the_kernels_inside_decode_steps():
    """The reader counts ``%moe_gmm_pallas`` executions that lie inside a
    ``jit_decode_step_*`` module (not a prefill's), and prices each at one
    call's share of a step's work at the counters' mean per step (three
    calls a layer); without counters (a program that keeps none) it reads
    nothing."""
    from types import SimpleNamespace

    from bench import trace
    from bench.trace import Event
    cfg = json.loads((REPO / "bench" / "configs" /
                      "qwen2-moe-a2.7b.json").read_text())
    dev = "/device:TPU:0"

    def op(name, start, dur):
        return Event(dev, trace.OPS_LINE, name, start, dur)

    events = [
        Event(dev, trace.MODULES_LINE, "jit_decode_step_lss(1)", 0.0, 1.0),
        Event(dev, trace.MODULES_LINE, "jit__prefill_jit(2)", 2.0, 1.0),
        op("%moe_gmm_pallas.1 = f32[128,1408] custom-call()", 0.1, 0.2),
        op("%moe_gmm_pallas = f32[128,2048] custom-call()", 0.5, 0.3),
        op("%moe_gmm_pallas.2 = f32[4096,1408] custom-call()", 2.1, 0.5),
    ]
    pk = peaks.PEAKS["TPU v5 lite"]
    counters = {"n_decode_steps": 10, "moe_assignments": 1800,
                "moe_active_experts": 720}
    run_ = SimpleNamespace(
        cell=SimpleNamespace(config=cfg),
        impl=SimpleNamespace(counters=counters),
        trace=SimpleNamespace(events=events, steps=3), trace_mod=trace,
        work_floor=lambda w: peaks.floor_time_s(w.flops, w.nbytes, pk))
    read = spec.metric_reader("moe_gmm_roofline.decode", REPO)
    calls = 10 * 3 * 6                      # steps x (gate, up, down) x layers
    one = spec.arch(cfg, REPO).expert_work(cfg, 1800 / calls, 720 / calls)
    assert read(run_) == pytest.approx(
        100.0 * 2 * peaks.floor_time_s(one.flops, one.nbytes, pk) / 0.5)
    run_.impl = SimpleNamespace(counters={"n_decode_steps": 10})
    assert read(run_) is None
