"""Tiny cells for exercising the benchmark on a CPU: a checkout-shaped
directory whose ``BENCHMARK.json``, configurations, mixes and limits are
small, and whose program and metric readers are the repository's own."""

from __future__ import annotations

import json
import os
from contextlib import contextmanager
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

TINY_LM = {
    "name": "tiny-lm", "family": "lm", "model_type": "qwen2",
    "hidden_act": "silu",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 1024, "tie_word_embeddings": True, "qkv_bias": True,
    "rms_norm_eps": 1e-06, "rope_theta": 10000.0, "torch_dtype": "bfloat16",
    "matmul_precision": "default",
    "lss": {"k_bits": 4, "n_tables": 1, "capacity": 128},
}

TINY_LM_UNTIED = dict(TINY_LM, name="tiny-lm-untied",
                      tie_word_embeddings=False)

TINY_W2V = {
    "name": "tiny-w2v", "family": "word2vec", "input_dim": 4096,
    "hidden": 32, "output_dim": 4096, "output_bias": False,
    "dtype": "float32", "matmul_precision": "highest",
    "lss": {"k_bits": 5, "n_tables": 1, "capacity": 256},
}


def decode_mix(head: str) -> dict:
    return {"kind": "decode", "loop": "closed", "head": head, "clients": 4,
            "max_streams": 4, "max_len": 64, "kv_layout": "dense",
            "preroll_s": 0.3,
            "prompt_len": {"median": 12, "sigma": 0.5, "min": 8, "max": 24},
            "output_len": {"median": 6, "sigma": 0.5, "min": 4, "max": 12},
            "check": {"sessions": 16}}


def score_mix(head: str) -> dict:
    return {"kind": "score", "loop": "open", "head": head,
            "rate": 200, "preroll_s": 0.2, "ids_zipf_s": 1.0, "top_k": 5,
            "buckets": [1, 2, 4, 8], "policy": "block", "max_queue": 4096,
            "check": {"requests": 64}}


CELLS = {
    "tiny-lm.decode.lss": ("tiny-lm", "tiny-decode.lss"),
    "tiny-lm.decode.full": ("tiny-lm", "tiny-decode.full"),
    "tiny-lm-untied.decode.lss": ("tiny-lm-untied", "tiny-decode.lss"),
    "tiny-lm-untied.decode.full": ("tiny-lm-untied", "tiny-decode.full"),
    "tiny-w2v.score.lss": ("tiny-w2v", "tiny-score.lss"),
    "tiny-w2v.score.full": ("tiny-w2v", "tiny-score.full"),
}

# set between the program's and the control's readings at these sizes
# on a CPU under load, 16 sessions a sample (10 to 15 runs a cell on
# five seeds): gap <= 0.014 LSS and <= 0.016 full tied, <= 0.011 and
# <= 0.027 untied, against >= 0.186 and >= 0.24 for fp8; miss <= 0.028
# against >= 0.053; rank_err <= 1.2e-7 against >= 2.2e-6 for three bf16
# passes.  With 4 sessions a sample, miss read up to 0.081 and the fp8
# gap down to 0.018: too few tokens to separate them.
LIMITS = {
    "tiny-lm.decode.lss": {"gap": {"limit": 0.08}, "miss": {"limit": 0.06}},
    "tiny-lm.decode.full": {"gap": {"limit": 0.05}},
    "tiny-lm-untied.decode.lss": {"gap": {"limit": 0.08},
                                  "miss": {"limit": 0.06}},
    "tiny-lm-untied.decode.full": {"gap": {"limit": 0.06}},
    "tiny-w2v.score.lss": {"rank_err": {"limit": 1e-6}},
    "tiny-w2v.score.full": {"rank_err": {"limit": 1e-6}},
}


def make_root(tmp: Path) -> Path:
    """A checkout with the tiny cells; ``src`` and the metric readers are
    links to the repository's."""
    root = Path(tmp)
    (root / "bench" / "configs").mkdir(parents=True)
    (root / "bench" / "traffic").mkdir()
    (root / "bench" / "limits").mkdir()
    os.symlink(REPO / "src", root / "src")
    for sub in ("metrics", "models"):
        os.symlink(REPO / "bench" / sub, root / "bench" / sub)
    configs = (TINY_LM, TINY_LM_UNTIED, TINY_W2V)
    for cfg in configs:
        (root / "bench" / "configs" / f"{cfg['name']}.json").write_text(
            json.dumps(cfg))
    for head in ("lss", "full"):
        (root / "bench" / "traffic" / f"tiny-decode.{head}.json").write_text(
            json.dumps(decode_mix(head)))
        (root / "bench" / "traffic" / f"tiny-score.{head}.json").write_text(
            json.dumps(score_mix(head)))
    for cell, nums in LIMITS.items():
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps({"numbers": nums}))
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    bm = {
        "command": real["command"], "paths": real["paths"],
        "run_seconds": 1,
        "configs": [{"name": c["name"], "source": "tiny",
                     "file": f"bench/configs/{c['name']}.json",
                     "reduced": [], "why": "tiny"}
                    for c in configs],
        "workloads": [{"name": n, "config": c, "traffic": t, "chips": 1,
                       "why": "tiny"} for n, (c, t) in CELLS.items()],
        "end_to_end": [dict(m, workloads=_tiny(m["workloads"]))
                       if "workloads" in m else m
                       for m in real["end_to_end"]],
        "per_layer": [dict(m, workloads=_tiny(m["workloads"]))
                      for m in real["per_layer"]],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


# the tiny cells that stand for each of the benchmark's cells
TINY_OF = {
    "qwen2-0.5b.decode.lss": ["tiny-lm.decode.lss",
                              "tiny-lm-untied.decode.lss"],
    "qwen2-0.5b.decode.full": ["tiny-lm.decode.full",
                               "tiny-lm-untied.decode.full"],
    "text8.score.lss": ["tiny-w2v.score.lss"],
    "text8.score.full": ["tiny-w2v.score.full"],
}


def _tiny(workloads: list) -> list:
    return [c for w in workloads for c in TINY_OF[w]]


_JAX_KNOBS = ("jax_default_matmul_precision", "jax_compilation_cache_dir",
              "jax_persistent_cache_min_compile_time_secs",
              "jax_persistent_cache_min_entry_size_bytes")


@contextmanager
def jax_config_restored():
    """A run sets JAX's cache directory and matmul precision for its
    process, and a score cell freezes the collector's view of set-up;
    put them back for the tests that share the process."""
    import gc

    import jax
    from jax.experimental.compilation_cache import compilation_cache
    saved = {k: getattr(jax.config, k) for k in _JAX_KNOBS}
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
        gc.unfreeze()
