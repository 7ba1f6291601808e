"""Public jit'd wrapper around the weight-bank Pallas kernel.

Handles: operand normalisation to the photonic [-1,1] range, fake-quant,
padding to block multiples, noise-mode selection, and rescaling — so callers
see the same semantics as ``repro.core.photonics.photonic_matmul`` (the
pure-JAX path) but executed by the weight-bank kernel.

The kernel implements the *abstract* noise model (σ per MAC/block) only:
device-level effects carried by ``PhotonicConfig.mrr`` — Lorentzian
transfer, thermal crosstalk, resonance drift — are the "emu" backend's
domain (``repro.hardware.channel``) and are intentionally ignored here, so
ref↔pallas equivalence is exact and perf comparisons stay apples-to-apples.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.core import photonics
from repro.kernels import ref as kref
from repro.kernels.photonic_matmul import photonic_matmul_pallas


def _pad_to(x, mult, axis):
    size = x.shape[axis]
    rem = (-size) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(
    jax.jit,
    static_argnames=("cfg", "noise_mode", "block_t", "block_m", "block_k", "interpret"),
)
def photonic_matmul(a, b, cfg, key=None, *, noise_mode="auto",
                    block_t=128, block_m=128, block_k=512, interpret=False):
    """Weight-bank product with the paper's noise model, kernel-executed.

    a: (T, K) inputs; b: (M, K) weights.
    noise_mode: auto|none|input|prng — "auto" picks `input` when a key is
    given (reproducible, CPU-validatable) and `none` for ideal hardware.
    """
    t, k_dim = a.shape
    if not cfg.enabled:
        return a @ b.T

    a_n, b_n, s_a, s_b = photonics.normalise_operands(a, b, cfg)

    if noise_mode == "auto":
        noise_mode = "input" if (cfg.noise_std > 0 and key is not None) else "none"

    a_p = _pad_to(_pad_to(a_n, block_t, 0), block_k, 1)
    b_p = _pad_to(_pad_to(b_n, block_m, 0), block_k, 1)
    bt = min(block_t, a_p.shape[0])
    bm = min(block_m, b_p.shape[0])
    bk = min(block_k, a_p.shape[1])

    noise = None
    seed = None
    sigma_step = 0.0
    if noise_mode == "input":
        noise = kref.total_noise(key, (a_p.shape[0], b_p.shape[0]), k_dim, cfg)
    elif noise_mode == "prng":
        from jax.experimental.pallas import tpu as pltpu

        nk = a_p.shape[1] // bk
        sigma_total = photonics.noise_sigma_total(k_dim, 1.0, 1.0, cfg)
        # host math on config floats, not a device sync
        sigma_step = float(sigma_total / math.sqrt(nk))  # lint: disable=RL002
        seed = (
            jax.random.key_data(key)[-1].astype(jnp.int32)
            if key is not None
            else jnp.int32(0)
        )
        if interpret:
            # pltpu PRNG primitives need the TPU-semantics interpreter
            # (bits come back zero there — structure-only validation).
            interpret = pltpu.InterpretParams()

    out = photonic_matmul_pallas(
        a_p, b_p, noise=noise, seed=seed, sigma_step=sigma_step,
        block_t=bt, block_m=bm, block_k=bk, out_dtype=jnp.float32,
        interpret=interpret,
    )
    out = out[:t, : b.shape[0]] * (s_a * s_b)
    return out.astype(a.dtype)
