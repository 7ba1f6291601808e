"""Pure-jnp oracles for the Pallas kernels (the ground truth in tests)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def photonic_matmul_ref(a, b, *, noise=None):
    """C = A @ Bᵀ (+ noise).  a:(T,K) b:(M,K) noise:(T,M)|None."""
    out = jnp.einsum("tk,mk->tm", a.astype(jnp.float32), b.astype(jnp.float32))
    if noise is not None:
        out = out + noise.astype(jnp.float32)
    return out.astype(a.dtype)


def total_noise(key, shape, k_dim: int, cfg, dtype=jnp.float32):
    """Draw the accumulated bank noise for a (T,M) output with contraction
    length k_dim — shared by ops.py ("input" mode) and the reference path."""
    from repro.core import photonics

    sigma = photonics.noise_sigma_total(k_dim, 1.0, 1.0, cfg)
    return sigma * jax.random.normal(key, shape, dtype=dtype)
