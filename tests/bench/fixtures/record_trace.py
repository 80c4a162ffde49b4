"""Records ``decode_lss.xplane.pb``, the chip trace the reduction tests
read, and ``decode_lss.json``, what the recorded run did.

    python3 tests/bench/fixtures/record_trace.py <out dir>

On a TPU: a small language model (2 layers, d_model 256, vocab 8192)
serves 4 sessions of 16-token prompts and 8 tokens each through the
program's LSS decode path (prefill, first-token rank, fused steps with
``lss_topk``), with the profiler on.  Small, so the trace is small.
"""

import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.lss import LSSConfig  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.serve.engine import LMDecoder  # noqa: E402

SESSIONS, PROMPT, TOKENS = 4, 16, 8


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "record on a TPU"
    cfg = T.TransformerConfig(
        name="fixture-lm", n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
        head_dim=64, d_ff=512, vocab=8192, qkv_bias=True,
        tie_embeddings=True, dtype=jnp.bfloat16)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    dec = LMDecoder(params, cfg, LSSConfig(k_bits=6, n_tables=1),
                    max_streams=SESSIONS, max_len=64, kv_layout="dense")
    dec.engine.fit_random(jax.random.PRNGKey(1))
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab, (SESSIONS, PROMPT)).astype(np.int32)
    dec.generate(prompts, steps=TOKENS, head="lss")          # warm
    sched = dec.scheduler(head="lss")
    sched.reset_stats()
    os.makedirs(out, exist_ok=True)
    tdir = os.path.join(out, "trace")
    jax.profiler.start_trace(tdir)
    dec.generate(prompts + 1, steps=TOKENS, head="lss")
    jax.profiler.stop_trace()
    st = sched.stats()
    found = [os.path.join(d, f) for d, _, fs in os.walk(tdir) for f in fs
             if f.endswith(".xplane.pb")]
    os.replace(found[0], os.path.join(out, "decode_lss.xplane.pb"))
    with open(os.path.join(out, "decode_lss.json"), "w") as f:
        json.dump({"prefills": SESSIONS, "first_token_ranks": SESSIONS,
                   "fused_steps": st.n_steps, "tokens": st.n_tokens}, f)


if __name__ == "__main__":
    main(sys.argv[1])
