"""moonlight-16b-a3b [moe] — DeepSeek-V3 block at d=2048: 27 layers, the
first dense (d_ff 11264), the other 26 MoE; MLA with no query low-rank
(16 heads, kv_lora 512, qk nope/rope 128/64, v 128, θ=5e4); 64 routed
experts of width 1408, top-6 by sigmoid score plus a selection bias
(``noaux_tc``), normalised and scaled by 2.446, and 2 shared experts;
vocab 163840, untied head.
[hf:moonshotai/Moonlight-16B-A3B config.json]

``full()`` is one chip's share of an expert-parallel deployment, the cut
of ``perfbench/configs/moonlight-16b-a3b.json``: 8 chips share each layer,
each holding 8 of the 64 experts and an eighth of the vocabulary (20480
rows of the token table and the head); the router keeps its 64 outputs and
routes over all of them.  The chip holds the dense layer and 5 MoE layers
(one whole period: the layers left out would lie on further pipeline
stages): 669 M parameters.  Every width is as published.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import Arch
from repro.models.transformer import (
    MLASettings,
    MoESettings,
    TransformerConfig,
    TransformerLM,
)

# the deployment this chip's share stands for
EP_CHIPS = 8
N_EXPERTS = 64
VOCAB = 163840




def full(dtype=jnp.bfloat16) -> TransformerLM:
    return TransformerLM(TransformerConfig(
        name="moonlight-16b-a3b", n_layers=6, n_dense_layers=1, d_model=2048,
        n_heads=16, n_kv_heads=16, d_ff=11264, vocab_size=VOCAB // EP_CHIPS,
        rope_theta=50000.0, norm_eps=1e-5,
        mla=MLASettings(q_lora_rank=None, kv_lora_rank=512, qk_nope_dim=128,
                        qk_rope_dim=64, v_head_dim=128),
        moe=MoESettings(n_experts=N_EXPERTS, top_k=6, d_ff_expert=1408,
                        n_shared_experts=2, d_ff_shared=1408,
                        experts_held=(0, N_EXPERTS // EP_CHIPS),
                        scoring="sigmoid", routed_scale=2.446),
        dtype=dtype,
    ))


def smoke() -> TransformerLM:
    return TransformerLM(TransformerConfig(
        name="moonlight-smoke", n_layers=3, n_dense_layers=1, d_model=64,
        n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=128, norm_eps=1e-5,
        rope_theta=50000.0,
        mla=MLASettings(q_lora_rank=None, kv_lora_rank=16, qk_nope_dim=16,
                        qk_rope_dim=8, v_head_dim=16),
        moe=MoESettings(n_experts=16, top_k=4, d_ff_expert=32, n_shared_experts=2,
                        d_ff_shared=32, experts_held=(0, 4), scoring="sigmoid",
                        routed_scale=2.446),
        dtype=jnp.float32,
    ))


ARCH = Arch(
    name="moonlight-16b-a3b", family="moe", make_model=full, make_smoke=smoke,
    source="hf:moonshotai/Moonlight-16B-A3B",
    notes="one chip of 8-way expert parallelism: 8 of 64 experts, 1/8 vocab, "
          "dense layer + 5 MoE layers",
)
