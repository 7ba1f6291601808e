"""Per-kernel validation: shape/dtype sweeps vs the pure-jnp oracle
(ref.py), in Pallas interpret mode, plus hypothesis property tests."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import photonics
from repro.kernels import ops, ref

IDEAL = photonics.PhotonicConfig(noise_std=0.0)
NOISY = photonics.PhotonicConfig(noise_std=0.098)


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape)
    return x.astype(dtype)


SHAPES = [
    (4, 8, 16),      # tiny, sub-block
    (64, 10, 800),   # the paper's MLP projection (e 10-dim -> 800)
    (128, 128, 128), # exactly one block
    (200, 300, 257), # ragged (exercises padding)
    (256, 512, 384), # multi-block
]


@pytest.mark.parametrize("t,k,m", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_photonic_matmul_noiseless_matches_ref(t, k, m, dtype):
    ka, kb = jax.random.split(jax.random.PRNGKey(t * 7 + k))
    a = _rand(ka, (t, k), dtype)
    b = _rand(kb, (m, k), dtype)
    out = ops.photonic_matmul(a, b, IDEAL, interpret=True)
    expect = ref.photonic_matmul_ref(a, b)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(expect, np.float32),
        rtol=tol, atol=tol * np.abs(np.asarray(expect)).max() + 1e-6)


def test_noise_statistics_match_model():
    """Injected noise std equals σ·s_a·s_b·sqrt(ceil(K/bank_cols))."""
    key = jax.random.PRNGKey(3)
    t, k, m = 256, 40, 512  # 40 cols = 2 bank passes at bank_cols=20
    a = _rand(key, (t, k), jnp.float32)
    b = _rand(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    out = ops.photonic_matmul(a, b, NOISY, key=key, interpret=True)
    err = np.asarray(out - a @ b.T)
    s = float(jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(b)))
    expect_std = 0.098 * s * np.sqrt(2)
    assert abs(err.std() / expect_std - 1.0) < 0.05
    assert abs(err.mean()) < 3 * expect_std / np.sqrt(err.size)


def test_noise_reproducible_by_key():
    key = jax.random.PRNGKey(4)
    a = _rand(key, (32, 16), jnp.float32)
    b = _rand(jax.random.fold_in(key, 1), (64, 16), jnp.float32)
    o1 = ops.photonic_matmul(a, b, NOISY, key=key, interpret=True)
    o2 = ops.photonic_matmul(a, b, NOISY, key=key, interpret=True)
    o3 = ops.photonic_matmul(a, b, NOISY, key=jax.random.PRNGKey(9), interpret=True)
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    assert np.abs(np.asarray(o1 - o3)).max() > 0


def test_prng_mode_compiles_in_interpret():
    """TPU in-kernel PRNG path: structural validation (zero-bit noise in the
    interpreter ⇒ output equals the exact product)."""
    key = jax.random.PRNGKey(5)
    a = _rand(key, (64, 32), jnp.float32)
    b = _rand(jax.random.fold_in(key, 1), (128, 32), jnp.float32)
    out = ops.photonic_matmul(a, b, NOISY, key=key, noise_mode="prng", interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b.T), rtol=1e-4, atol=1e-4)


def test_quantization_matches_core_path():
    cfg = photonics.PhotonicConfig(noise_std=0.0, weight_bits=6, input_bits=8)
    key = jax.random.PRNGKey(6)
    a = _rand(key, (32, 24), jnp.float32)
    b = _rand(jax.random.fold_in(key, 1), (48, 24), jnp.float32)
    out_k = ops.photonic_matmul(a, b, cfg, interpret=True)
    out_c = photonics.photonic_matmul(a, b, cfg)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c), rtol=1e-5, atol=1e-5)


@hypothesis.given(
    t=st.integers(1, 64), k=st.integers(1, 96), m=st.integers(1, 96),
    bt=st.sampled_from([8, 32, 128]), bk=st.sampled_from([16, 64, 512]),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_block_shape_invariance(t, k, m, bt, bk):
    """Kernel output is invariant to BlockSpec tiling (noiseless)."""
    key = jax.random.PRNGKey(t * 1000 + k * 10 + m)
    a = _rand(key, (t, k), jnp.float32)
    b = _rand(jax.random.fold_in(key, 1), (m, k), jnp.float32)
    o1 = ops.photonic_matmul(a, b, IDEAL, interpret=True, block_t=bt, block_k=bk)
    o2 = ops.photonic_matmul(a, b, IDEAL, interpret=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), rtol=1e-5, atol=1e-5)


def test_vmem_budget_helper():
    from repro.kernels.photonic_matmul import vmem_bytes

    assert vmem_bytes(128, 128, 512) < 16 * 2**20  # fits v5e VMEM
