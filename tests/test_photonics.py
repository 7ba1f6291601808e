"""Photonic execution model: noise calibration, GeMM tiling, quantization."""

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import photonics


def test_effective_bits_match_paper():
    # Fig. 3(c) and Fig. 5(a): log2(2/σ)
    assert abs(photonics.sigma_to_resolution(0.019) - 6.72) < 0.01
    assert abs(photonics.sigma_to_resolution(0.098) - 4.35) < 0.01
    assert abs(photonics.sigma_to_resolution(0.202) - 3.31) < 0.01
    for bits in [3.31, 4.35, 6.72, 8.0]:
        sigma = photonics.resolution_to_sigma(bits)
        assert abs(photonics.sigma_to_resolution(sigma) - bits) < 1e-9


@hypothesis.given(bits=st.floats(0.25, 40.0))
@hypothesis.settings(max_examples=50, deadline=None)
def test_resolution_sigma_round_trip_is_exact(bits):
    """resolution_to_sigma / sigma_to_resolution are inverses to float
    precision (computed via 1 - log2(σ), no division rounding) — and
    PhotonicConfig.effective_bits is the same function."""
    sigma = photonics.resolution_to_sigma(bits)
    assert abs(photonics.sigma_to_resolution(sigma) - bits) < 1e-9
    cfg = photonics.PhotonicConfig(noise_std=sigma)
    assert abs(cfg.effective_bits - bits) < 1e-9


@hypothesis.given(sigma=st.floats(1e-9, 2.0))
@hypothesis.settings(max_examples=50, deadline=None)
def test_sigma_resolution_round_trip_is_exact(sigma):
    bits = photonics.sigma_to_resolution(sigma)
    back = photonics.resolution_to_sigma(bits)
    assert abs(back - sigma) <= 1e-12 * sigma


def test_resolution_degenerate_cases():
    assert photonics.sigma_to_resolution(0.0) == float("inf")
    assert photonics.PhotonicConfig(noise_std=0.0).effective_bits == float("inf")


def test_gemm_cycles_paper_mlp():
    """800×10 matvec on the 50×20 bank: ceil(800/50)·ceil(10/20) = 16."""
    cfg = photonics.PhotonicConfig()
    assert photonics.gemm_cycles(800, 10, cfg) == 16
    assert photonics.n_bank_passes(10, cfg) == 1
    assert photonics.n_bank_passes(40, cfg) == 2


def test_noiseless_is_exact():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (32, 24))
    b = jax.random.normal(jax.random.fold_in(key, 1), (48, 24))
    out = photonics.photonic_matmul(a, b, photonics.preset("ideal"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(a @ b.T), rtol=1e-5, atol=1e-5)


def test_disabled_bypasses_everything():
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (8, 4))
    b = jax.random.normal(key, (6, 4))
    out = photonics.photonic_matmul(a, b, photonics.preset("digital"), key=key)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(a @ b.T))


@pytest.mark.parametrize("convention,expect_mult", [("absolute", 1.0), ("fullscale", 20.0)])
def test_noise_conventions(convention, expect_mult):
    cfg = photonics.PhotonicConfig(noise_std=0.1, noise_convention=convention)
    sigma = photonics.noise_sigma_total(20, 1.0, 1.0, cfg)  # one bank pass
    assert abs(sigma - 0.1 * expect_mult) < 1e-9


def test_noise_accumulates_sqrt_passes():
    cfg = photonics.PhotonicConfig(noise_std=0.1)
    s1 = photonics.noise_sigma_total(20, 1.0, 1.0, cfg)
    s4 = photonics.noise_sigma_total(80, 1.0, 1.0, cfg)  # 4 passes
    assert abs(s4 / s1 - 2.0) < 1e-9


def test_empirical_noise_std_calibrated():
    cfg = photonics.preset("offchip_bpd")
    key = jax.random.PRNGKey(2)
    a = jax.random.uniform(key, (512, 10), minval=-1, maxval=1)
    b = jax.random.uniform(jax.random.fold_in(key, 1), (800, 10), minval=-1, maxval=1)
    out = photonics.photonic_matmul(a, b, cfg, key=key)
    err = np.asarray(out - a @ b.T)
    s = float(jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(b)))
    assert abs(err.std() / (0.098 * s) - 1.0) < 0.03


def test_fake_quant_levels():
    x = jnp.linspace(-1, 1, 1001)
    q = photonics.fake_quant(x, 4)
    assert len(np.unique(np.asarray(q))) <= 2**4 - 1 + 2
    np.testing.assert_allclose(np.asarray(photonics.fake_quant(x, None)), np.asarray(x))


@hypothesis.given(
    m=st.integers(1, 300), k=st.integers(1, 100),
    rows=st.integers(5, 100), cols=st.integers(5, 100),
)
@hypothesis.settings(max_examples=50, deadline=None)
def test_gemm_cycles_cover_matrix(m, k, rows, cols):
    """GeMM compiler invariant: cycles × bank area >= matrix area, and the
    tiling never exceeds one extra panel per dimension."""
    cfg = photonics.PhotonicConfig(bank_rows=rows, bank_cols=cols)
    cycles = photonics.gemm_cycles(m, k, cfg)
    assert cycles * rows * cols >= m * k
    assert cycles <= ((m // rows + 1) * (k // cols + 1))


@hypothesis.given(t=st.integers(1, 16), k=st.integers(1, 32), m=st.integers(1, 32))
@hypothesis.settings(max_examples=30, deadline=None)
def test_projection_linearity_ideal(t, k, m):
    """Ideal hardware is linear: photonic(a1+a2) == photonic(a1)+photonic(a2)."""
    key = jax.random.PRNGKey(t + 13 * k + 131 * m)
    a1 = jax.random.normal(key, (t, k))
    a2 = jax.random.normal(jax.random.fold_in(key, 1), (t, k))
    b = jax.random.normal(jax.random.fold_in(key, 2), (m, k))
    cfg = photonics.preset("ideal")
    lhs = photonics.photonic_matmul(a1 + a2, b, cfg)
    rhs = photonics.photonic_matmul(a1, b, cfg) + photonics.photonic_matmul(a2, b, cfg)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs), rtol=1e-4, atol=1e-4)


def test_project_shapes():
    cfg = photonics.preset("ideal")
    e = jnp.ones((3, 7, 10))
    b = jnp.ones((64, 10))
    out = photonics.photonic_project(e, b, cfg)
    assert out.shape == (3, 7, 64)
