"""The harness finds every configuration, mix, limit, per-layer metric
and language-model architecture by its name, so a new one is added as
files and entries alone; and on a host without a chip, in a directory
without the program, or for an architecture it has no module of, the run
refuses with no result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import peaks, run, spec
from tinycells import REPO, TINY_LM, jax_config_restored, make_root


def test_every_cell_of_the_benchmark_resolves():
    bm = spec.load_benchmark(REPO)
    for w in bm["workloads"]:
        c = spec.cell(w["name"], REPO)
        assert c.chips == w["chips"] == 1
        assert c.config["name"] == w["config"]
        assert c.limits["numbers"]
        names = {m["name"] for m in c.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer
        for m in c.per_layer:
            assert m["moves"] in names          # it reports what it moves
            assert callable(spec.metric_reader(m["name"], REPO))


def test_metrics_of_one_layer_share_its_name():
    bm = spec.load_benchmark(REPO)
    files = {p.stem for p in (REPO / "bench" / "metrics").glob("*.py")}
    assert {m["name"] for m in bm["per_layer"]} <= files
    assert len({m["layer"] for m in bm["per_layer"]}) == 7


def test_added_config_mix_metric_and_cell_are_found(tmp_path):
    root = make_root(tmp_path)
    (root / "bench" / "configs" / "tiny-lm-2.json").write_text(json.dumps(
        dict(json.loads((root / "bench/configs/tiny-lm.json").read_text()),
             name="tiny-lm-2", num_hidden_layers=3)))
    (root / "bench" / "traffic" / "tiny-decode.burst.json").write_text(
        json.dumps({"kind": "decode", "loop": "closed", "head": "full",
                    "clients": 2}))
    (root / "bench" / "limits" / "tiny-lm-2.decode.burst.json").write_text(
        json.dumps({"numbers": {"gap": {"limit": 0.5}}}))
    metrics = tmp_path / "metrics2"
    metrics.mkdir()
    for p in (REPO / "bench" / "metrics").glob("*.py"):
        (metrics / p.name).write_text(p.read_text())
    (metrics / "answer_ms.py").write_text("def read(run):\n    return 42.0\n")
    os.unlink(root / "bench" / "metrics")
    os.symlink(metrics, root / "bench" / "metrics")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-lm-2", "source": "tiny",
                          "file": "bench/configs/tiny-lm-2.json",
                          "reduced": ["num_hidden_layers"], "why": "t"})
    bm["workloads"].append({"name": "tiny-lm-2.decode.burst",
                            "config": "tiny-lm-2", "traffic":
                            "tiny-decode.burst", "chips": 1, "why": "t"})
    bm["per_layer"].append({"name": "answer_ms", "unit": "ms",
                            "better": "lower", "source": "program_span",
                            "layer": "test", "moves": "itl_p95_ms",
                            "workloads": ["tiny-lm-2.decode.burst"]})
    for m in bm["end_to_end"]:
        if m["name"] in ("itl_p95_ms", "decode_tok_s"):
            m["workloads"].append("tiny-lm-2.decode.burst")
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    c = spec.cell("tiny-lm-2.decode.burst", root)
    assert c.config["num_hidden_layers"] == 3
    assert c.mix["clients"] == 2
    assert [m["name"] for m in c.per_layer] == ["answer_ms"]
    assert {m["name"] for m in c.end_to_end} == \
        {"itl_p95_ms", "decode_tok_s", "setup_s"}
    assert spec.metric_reader("answer_ms", root)(None) == 42.0
    with pytest.raises(KeyError):
        spec.cell("no-such-cell", root)


def _run(cwd, *extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "text8.score.lss",
         "--seed", str(2 ** 31 + 7), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.strip().startswith("{") for line in out.splitlines())


def test_cpu_host_exits_nonzero_with_no_result():
    p = _run(REPO)
    assert p.returncode != 0 and _no_result(p.stdout), p.stdout
    assert "no accelerator" in p.stderr


def test_directory_without_the_program_exits_nonzero(tmp_path):
    (tmp_path / "bench").mkdir()
    for sub in ("configs", "traffic", "limits", "metrics", "models"):
        (tmp_path / "bench" / sub).symlink_to(REPO / "bench" / sub)
    for p in (REPO / "bench").glob("*.py"):
        (tmp_path / "bench" / p.name).write_text(p.read_text())
    (tmp_path / "BENCHMARK.json").write_text(
        (REPO / "BENCHMARK.json").read_text())
    p = _run(tmp_path)
    assert p.returncode != 0 and _no_result(p.stdout), p.stdout


def _add_lm_cell(root, model_type, cell="tiny-arch.decode.full"):
    """A configuration of ``model_type`` and its cell, served by the full
    head, with the mix, limits and entries of ``tiny-lm.decode.full``."""
    cfg = dict(TINY_LM, name="tiny-arch")
    if model_type is None:
        del cfg["model_type"]
    else:
        cfg["model_type"] = model_type
    (root / "bench" / "configs" / "tiny-arch.json").write_text(json.dumps(cfg))
    shutil.copy(root / "bench" / "limits" / "tiny-lm.decode.full.json",
                root / "bench" / "limits" / f"{cell}.json")
    bm = json.loads((root / "BENCHMARK.json").read_text())
    bm["configs"].append({"name": "tiny-arch", "source": "tiny",
                          "file": "bench/configs/tiny-arch.json",
                          "reduced": [], "why": "t"})
    bm["workloads"].append({"name": cell, "config": "tiny-arch",
                            "traffic": "tiny-decode.full", "chips": 1,
                            "why": "t"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny-lm.decode.full" in m.get("workloads", ()):
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return cfg


def _run_cell(root, cell, trace):
    return run.main(["--workload", cell, "--seed", str(2 ** 31 + 5),
                     "--seconds", "1", "--trace", str(trace)],
                    require_chip=False, root=root)


def test_added_architecture_is_found_by_its_model_type(tmp_path, capsys,
                                                       monkeypatch):
    """A model_type the repository does not know, added as one module
    under ``bench/models/`` with a configuration, limits and entries,
    serves its cell to a correct result, and the per-layer readers count
    its body with its own ``body_work``."""
    root = make_root(tmp_path)
    os.unlink(root / "bench" / "models")
    shutil.copytree(REPO / "bench" / "models", root / "bench" / "models",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (root / "bench" / "models" / "tinyarch.py").write_text(
        (REPO / "bench" / "models" / "qwen2.py").read_text() + """

BODY_CALLS = []
_body_work = body_work


def body_work(cfg, n_steps, contexts):
    BODY_CALLS.append(n_steps)
    return _body_work(cfg, n_steps, contexts)
""")
    cfg = _add_lm_cell(root, "tinyarch")
    assert not (REPO / "bench" / "models" / "tinyarch.py").exists()
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    with jax_config_restored():
        assert _run_cell(root, "tiny-arch.decode.full", trace=1) == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    arch = spec.arch(cfg)
    assert arch.__file__ == str(root / "bench" / "models" / "tinyarch.py")
    assert arch.BODY_CALLS                      # decode_step_mfu read it
    assert "decode_step_mfu" in res["metrics"]


@pytest.mark.parametrize("model_type", [None, "no_such_arch"])
def test_architecture_without_a_module_exits_nonzero(tmp_path, capsys,
                                                     model_type):
    root = make_root(tmp_path)
    _add_lm_cell(root, model_type)
    with jax_config_restored():
        assert _run_cell(root, "tiny-arch.decode.full", trace=0) != 0
    out, err = capsys.readouterr()
    assert _no_result(out), out
    assert "model_type" in err
