"""kimi-k2-1t-a32b [moe] — the DeepSeek-V3 block at d=7168: 61 layers, the
first dense (d_ff 18432), the other 60 MoE; MLA with q_lora 1536, kv_lora
512, qk nope/rope 128/64, v 128, 64 heads, θ=5e4; 384 routed experts of
width 2048, top-8 by sigmoid score plus a selection bias (``noaux_tc``),
normalised and scaled by 2.827, and 1 shared expert; vocab 163840.
[hf:moonshotai/Kimi-K2-Instruct config.json]

Not modelled: the config's YaRN rope scaling (factor 32 over 4096
original positions); rotary runs at θ=5e4 unscaled."""

from __future__ import annotations

import jax.numpy as jnp

from repro.configs.base import Arch
from repro.models.transformer import (
    MLASettings,
    MoESettings,
    TransformerConfig,
    TransformerLM,
)




def full(dtype=jnp.bfloat16) -> TransformerLM:
    return TransformerLM(TransformerConfig(
        name="kimi-k2-1t-a32b", n_layers=61, n_dense_layers=1, d_model=7168,
        n_heads=64, n_kv_heads=64, d_ff=18432, vocab_size=163840,
        rope_theta=5e4, norm_eps=1e-6,
        mla=MLASettings(q_lora_rank=1536, kv_lora_rank=512, qk_nope_dim=128,
                        qk_rope_dim=64, v_head_dim=128),
        moe=MoESettings(n_experts=384, top_k=8, d_ff_expert=2048, n_shared_experts=1,
                        d_ff_shared=2048, scoring="sigmoid", routed_scale=2.827),
        dtype=dtype,
    ))


def smoke() -> TransformerLM:
    return TransformerLM(TransformerConfig(
        name="kimi-smoke", n_layers=3, n_dense_layers=1, d_model=64, n_heads=8,
        n_kv_heads=8, d_ff=128, vocab_size=128,
        mla=MLASettings(q_lora_rank=32, kv_lora_rank=16, qk_nope_dim=16,
                        qk_rope_dim=8, v_head_dim=16),
        moe=MoESettings(n_experts=16, top_k=4, d_ff_expert=64, n_shared_experts=1,
                        d_ff_shared=64, scoring="sigmoid", routed_scale=2.827),
        dtype=jnp.float32,
    ))


ARCH = Arch(
    name="kimi-k2-1t-a32b", family="moe", make_model=full, make_smoke=smoke,
    source="hf:moonshotai/Kimi-K2-Instruct",
    notes="1T total / 32B active; fits 256 v5e only fully 2-D sharded",
)
