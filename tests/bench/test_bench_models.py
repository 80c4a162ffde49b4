"""A language model's architecture is a module of its own,
``bench/models/<model_type>.py``: Qwen2's weights are drawn as they were
before the module held them, an untied head draws its rows from a key
stream of its own, and the reference imports nothing of the program."""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import spec
from tinycells import REPO, TINY_LM, TINY_LM_UNTIED

# sha256 of tiny-lm's weights, by leaf, as the harness drew them before
# the architecture had a module of its own (``weights.make_lm_params``)
PINNED = {
    2 ** 31 + 99:
        "71a0a3747f8908f2d0c011c48b052ca64d77a80aa4aa97068d1e9236c2b3c044",
    3_000_000_001:
        "7e9b7f8fd340483067976fc2d9931b1a5101a650565774f842588bf6dcbfe2c7",
}


def _leaves(params) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in flat}


def checksum(params) -> str:
    h = hashlib.sha256()
    for key, a in sorted(_leaves(params).items()):
        h.update(f"{key}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_qwen2_module_is_the_tiny_configs_architecture():
    arch = spec.arch(TINY_LM, REPO)
    assert Path(arch.__file__).resolve() == REPO / "bench" / "models" / "qwen2.py"
    for name in ("make_params", "program_config", "head_table", "hidden",
                 "body_work"):
        assert callable(getattr(arch, name))


@pytest.mark.parametrize("seed", sorted(PINNED))
def test_tied_weights_match_the_pinned_checksum(seed):
    params = spec.arch(TINY_LM, REPO).make_params(TINY_LM, seed)
    assert "lm_head" not in params
    assert checksum(params) == PINNED[seed]


def test_untied_head_draws_a_stream_of_its_own():
    arch = spec.arch(TINY_LM, REPO)
    seed = 2 ** 31 + 99
    tied = _leaves(arch.make_params(TINY_LM, seed))
    untied = _leaves(arch.make_params(TINY_LM_UNTIED, seed))
    assert set(untied) == set(tied) | {"['lm_head']"}
    for key, a in tied.items():                # nothing else moves
        assert a.dtype == untied[key].dtype
        assert a.tobytes() == untied[key].tobytes(), key
    head = untied["['lm_head']"]
    emb = tied["['embed']"]
    assert head.shape == emb.shape and head.dtype == emb.dtype
    assert not np.array_equal(head, emb)
    std = float(np.std(head.astype(np.float32)))
    assert std == pytest.approx(TINY_LM["hidden_size"] ** -0.5, rel=0.05)
    p = arch.make_params(TINY_LM_UNTIED, seed)
    assert arch.head_table(p) is p["lm_head"]
    q = arch.make_params(TINY_LM, seed)
    assert arch.head_table(q) is q["embed"]


def test_program_config_follows_the_head():
    arch = spec.arch(TINY_LM, REPO)
    assert arch.program_config(TINY_LM).tie_embeddings is True
    assert arch.program_config(TINY_LM_UNTIED).tie_embeddings is False


def test_reference_imports_nothing_of_the_program():
    code = f"""
import json, sys
import numpy as np
sys.path[:0] = [{str(REPO)!r}, {str(REPO / "src")!r}]
from bench import spec
cfg = json.loads({json.dumps(json.dumps(TINY_LM))})
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
