"""qwen2-moe-a2.7b [moe] 24L d_model=2048 16H (MHA, kv=16) head_dim=128,
vocab=151936 untied, 60 routed experts of 1408 top-4 plus one shared
expert of 5632 [hf:Qwen/Qwen1.5-MoE-A2.7B; hf].

Every layer is sparse (``decoder_sparse_step`` 1, no ``mlp_only_layers``).
The router's top-4 softmax weights are used as they are
(``norm_topk_prob: false``).  The shared expert is ONE SwiGLU of hidden
5632, scaled by ``sigmoid(h @ w_sg)`` with ``w_sg`` of shape [2048, 1],
as HF ``Qwen2MoeSparseMoeBlock`` has it.

60 experts don't divide the 16-way model axis: padded to 64 physical
experts (the router masks the 4 pads; see models/moe.py).  One chip of an
expert-parallel deployment holds a share of them instead
(``TransformerConfig.moe_held`` / ``moe_held_offset``): the router keeps
all 60 outputs and top-4, and the layer adds only its held experts' part
(the benchmark's configuration holds experts 0-14 of each layer).
"""

import jax.numpy as jnp

from repro.configs.base import ArchSpec, lm_shapes
from repro.core.lss import LSSConfig
from repro.models.transformer import TransformerConfig

CONFIG = ArchSpec(
    arch_id="qwen2-moe-a2.7b",
    family="lm",
    model_cfg=TransformerConfig(
        name="qwen2-moe-a2.7b", n_layers=24, d_model=2048, n_heads=16,
        n_kv_heads=16, head_dim=128, d_ff=1408, vocab=151936,
        qkv_bias=True, rope_base=1e6, dtype=jnp.bfloat16,
        moe_style="replace", n_experts=60, n_experts_padded=64,
        moe_top_k=4, moe_d_ff=1408, shared_expert_ff=5632,
        moe_norm_topk=False),
    shapes=lm_shapes(),
    lss=LSSConfig(k_bits=10, n_tables=1),
)
