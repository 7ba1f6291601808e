"""nn layers: flash-vs-reference attention, decode parity for every
temporal mixer, MoE routing invariants, rotary properties."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import nn
from repro.nn.attention import flash_attention, reference_attention


def _pos(b, s):
    return jnp.broadcast_to(jnp.arange(s)[None], (b, s))


@pytest.mark.parametrize("sq,skv,h,kvh,d", [
    (128, 128, 4, 4, 32),
    (256, 256, 4, 2, 16),   # GQA
    (64, 192, 2, 2, 8),     # cross-length
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(sq, skv, h, kvh, d, causal):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, sq, h, d))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, skv, kvh, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, skv, kvh, d))
    qp, kp = _pos(2, sq), _pos(2, skv)
    ref = reference_attention(q, k, v, q_pos=qp, kv_pos=kp, causal=causal)
    out = flash_attention(q, k, v, q_pos=qp, kv_pos=kp, causal=causal,
                          q_chunk=64, k_chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_local_window_matches_reference():
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, 256, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 256, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 256, 2, 16))
    qp = _pos(1, 256)
    ref = reference_attention(q, k, v, q_pos=qp, kv_pos=qp, causal=True, window=64)
    out = flash_attention(q, k, v, q_pos=qp, kv_pos=qp, causal=True, window=64,
                          q_chunk=64, k_chunk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("mixer", ["attn", "mla", "mamba", "rglru"])
def test_decode_parity(mixer):
    """Incremental decode == full parallel forward for every mixer."""
    key = jax.random.PRNGKey(0)
    T = 12
    if mixer == "attn":
        mod = nn.Attention(d_model=32, n_heads=4, n_kv_heads=2)
    elif mixer == "mla":
        mod = nn.MLAttention(d_model=32, n_heads=2, q_lora_rank=16,
                             kv_lora_rank=8, qk_nope_dim=8, qk_rope_dim=4,
                             v_head_dim=8)
    elif mixer == "mamba":
        mod = nn.Mamba2Block(d_model=32, d_state=16, head_dim=16, chunk=4)
    else:
        mod = nn.RGLRUBlock(d_model=32, d_rnn=48)
    p = mod.init(key)
    x = jax.random.normal(key, (2, T, 32))
    full = mod(p, x)
    cache = mod.init_cache(2, T)
    outs = []
    cl = jnp.zeros((2,), jnp.int32)
    for t in range(T):
        o, cache = mod.decode(p, x[:, t : t + 1], cache, cl + t)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), rtol=1e-4, atol=2e-5)


def test_windowed_ring_cache_decode_matches_reference():
    """Ring-buffer (window) cache == full-cache attention with window mask."""
    key = jax.random.PRNGKey(1)
    T, W = 32, 8
    ring = nn.Attention(d_model=16, n_heads=2, n_kv_heads=1, window=W)
    p = ring.init(key)
    x = jax.random.normal(key, (1, T, 16))
    full = ring(p, x)
    cache = ring.init_cache(1, W)  # ring buffer of window size
    assert cache["k"].shape[1] == W
    outs = []
    cl = jnp.zeros((1,), jnp.int32)
    for t in range(T):
        o, cache = ring.decode(p, x[:, t : t + 1], cache, cl + t)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec), rtol=1e-4, atol=2e-5)


def test_moe_routing_invariants():
    moe = nn.MoE(d_model=16, d_ff_expert=32, n_experts=8, top_k=2)
    p = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    _, scores, experts, weights = moe.route(p, x.reshape(32, 16))
    # each token goes to top_k distinct experts, its highest scores
    e = np.asarray(experts)
    assert e.shape == (32, 2) and np.all(e[:, 0] != e[:, 1])
    top = np.sort(np.asarray(scores), -1)[:, -2:]
    np.testing.assert_allclose(np.sort(np.take_along_axis(np.asarray(scores), e, -1), -1), top)
    # combine weights sum to 1 per token (norm_topk_prob)
    assert np.allclose(np.asarray(weights.sum(-1)), 1.0, atol=1e-5)
    # no capacity: every assignment is computed, none dropped
    _, aux = moe(p, x)
    assert float(aux["routed_here"]) == 1.0 and float(aux["rows_routed"]) == 64.0
    assert float(aux["load_max_over_mean"]) >= 1.0


def test_moe_group_scan_consistent_with_single_group():
    """Routing is per token (no capacity couples tokens): the layer over a
    batch equals the layer over each sequence alone."""
    moe = nn.MoE(d_model=16, d_ff_expert=32, n_experts=4, top_k=2)
    p = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 32, 16))
    y1, _ = moe(p, x)
    y2 = jnp.concatenate([moe(p, x[i:i + 1])[0] for i in range(4)], axis=0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4, atol=1e-5)


@hypothesis.given(s=st.integers(2, 33), d=st.sampled_from([8, 16, 32]))
@hypothesis.settings(max_examples=20, deadline=None)
def test_rotary_preserves_norm_and_relative_phase(s, d):
    from repro.nn.embeddings import apply_rotary, rotary_angles

    key = jax.random.PRNGKey(s * 100 + d)
    x = jax.random.normal(key, (1, s, 2, d))
    pos = _pos(1, s)
    cos, sin = rotary_angles(pos, d)
    y = apply_rotary(x, cos, sin)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(y), axis=-1), rtol=1e-4)
    # relative property: <q_m, k_n> depends only on m-n
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, d))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 1, d))
    def dot_at(m, n):
        cm, sm = rotary_angles(jnp.array([[m]]), d)
        cn, sn = rotary_angles(jnp.array([[n]]), d)
        qm = apply_rotary(q, cm, sm)
        kn = apply_rotary(k, cn, sn)
        return float(jnp.sum(qm * kn))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-3


def test_ssd_chunked_equals_unchunked():
    """Mamba2 SSD: chunked scan == different chunking (state-space duality)."""
    key = jax.random.PRNGKey(0)
    u = jax.random.normal(key, (2, 32, 16))
    m1 = nn.Mamba2Block(d_model=16, d_state=8, head_dim=8, chunk=4)
    m2 = nn.Mamba2Block(d_model=16, d_state=8, head_dim=8, chunk=16)
    p = m1.init(key)
    np.testing.assert_allclose(np.asarray(m1(p, u)), np.asarray(m2(p, u)),
                               rtol=1e-4, atol=1e-5)
