"""Pallas TPU kernel: fused DFA gradient  δ = (A @ Bᵀ + η) ⊙ g'(a).

This is the paper's full electro-optic circuit in one VMEM pass (Fig. 4b):
the weight-bank product (MRR array + BPDs), the analog read noise, and the
TIA gain stage that implements the Hadamard with g'(a) — fused as a matmul
epilogue so δ never round-trips HBM between the product and the mask.

Same noise modes as photonic_matmul (none / input / prng); the mask is a
mandatory operand tiled like the output.  For ReLU networks the mask is
binary, exactly as the paper notes for the TIA gains.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.photonic_matmul import _gaussian_tile


def _kernel(a_ref, b_ref, mask_ref, *rest, nk: int, noise_mode: str,
            sigma_step: float, out_dtype):
    idx = 0
    noise_ref = None
    seed_ref = None
    if noise_mode == "input":
        noise_ref = rest[idx]
        idx += 1
    if noise_mode == "prng":
        seed_ref = rest[idx]
        idx += 1
    o_ref = rest[idx]
    acc_ref = rest[idx + 1]

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.float32)
    b = b_ref[...].astype(jnp.float32)
    part = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    if noise_mode == "prng" and sigma_step > 0.0:
        i = pl.program_id(0)
        j = pl.program_id(1)
        nm = pl.num_programs(1)
        pltpu.prng_seed(seed_ref[0] + (i * nm + j) * nk + k)
        part = part + sigma_step * _gaussian_tile(part.shape)
    acc_ref[...] += part

    @pl.when(k == nk - 1)
    def _done():
        out = acc_ref[...]
        if noise_mode == "input":
            out = out + noise_ref[...].astype(jnp.float32)
        out = out * mask_ref[...].astype(jnp.float32)  # TIA gain epilogue
        o_ref[...] = out.astype(out_dtype)


def dfa_gradient_pallas(
    a: jax.Array,
    b: jax.Array,
    mask: jax.Array,
    *,
    noise: jax.Array | None = None,
    seed: jax.Array | None = None,
    sigma_step: float = 0.0,
    block_t: int = 128,
    block_m: int = 128,
    block_k: int = 512,
    out_dtype=None,
    interpret: bool = False,
) -> jax.Array:
    """δ = (A @ Bᵀ + η) ⊙ mask.  A:(T,K) B:(M,K) mask:(T,M) → (T,M)."""
    t, k_dim = a.shape
    m, kb = b.shape
    assert k_dim == kb and mask.shape == (t, m)
    block_t = min(block_t, t)
    block_m = min(block_m, m)
    block_k = min(block_k, k_dim)
    assert t % block_t == 0 and m % block_m == 0 and k_dim % block_k == 0
    nt, nm, nk = t // block_t, m // block_m, k_dim // block_k
    out_dtype = out_dtype or a.dtype

    if noise is not None:
        noise_mode = "input"
    elif seed is not None:
        # keep the prng operand/grid structure even at sigma_step == 0
        # (zero-noise interpret validation — see photonic_matmul.py)
        noise_mode = "prng"
    else:
        noise_mode = "none"

    in_specs = [
        pl.BlockSpec((block_t, block_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
        pl.BlockSpec((block_t, block_m), lambda i, j, k: (i, j)),
    ]
    operands = [a, b, mask]
    if noise_mode == "input":
        in_specs.append(pl.BlockSpec((block_t, block_m), lambda i, j, k: (i, j)))
        operands.append(noise)
    if noise_mode == "prng":
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.int32).reshape(1))

    kern = functools.partial(
        _kernel, nk=nk, noise_mode=noise_mode, sigma_step=sigma_step,
        out_dtype=out_dtype,
    )

    return pl.pallas_call(
        kern,
        grid=(nt, nm, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_t, block_m), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, m), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_t, block_m), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
