"""Pallas TPU kernel: photonic weight-bank matrix product.

Computes  C = A @ Bᵀ (+ bank read-noise)  where A:(T,K) are the
amplitude-encoded inputs (DFA error vectors) and B:(M,K) is the inscribed
weight panel.  This is the TPU realisation of the paper's M×N MRR bank +
balanced photodetectors (DESIGN.md §2):

* HBM→VMEM tiles play the role of weight-bank panels; the grid's K steps are
  the GeMM compiler's "operational cycles".
* Tiles are MXU-aligned (multiples of 128) instead of physical bank width;
  noise is drawn per K-step with variance σ²·(block_k/bank_cols) so the
  accumulated statistics match block_k/bank_cols physical bank passes.
* Noise modes:
    - "none"  : ideal hardware (exact matmul) — CPU-validatable.
    - "input" : total accumulated noise streamed as an operand (one draw per
                output element) — CPU-validatable bit-exactly vs ref.py.
    - "prng"  : on-chip noise from the TPU PRNG (Box–Muller over
                pltpu.prng_random_bits) — the zero-copy production path.
                (In interpret mode the PRNG stub yields zero bits ⇒ zero
                noise ⇒ output equals the exact product, which is exactly
                what the structural test asserts.)

Accumulation is f32 in a VMEM scratch tile regardless of operand dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _uniform_from_bits(bits):
    """uint32 -> uniform [0, 1) float32 using 24 high bits."""
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def _gaussian_tile(shape):
    """Box–Muller gaussian from the on-core PRNG (seed must be set)."""
    u1 = _uniform_from_bits(pltpu.prng_random_bits(shape))
    u2 = _uniform_from_bits(pltpu.prng_random_bits(shape))
    # log(1-u1): u1 in [0,1) keeps the argument in (0,1]; zero bits -> z=0.
    r = jnp.sqrt(-2.0 * jnp.log1p(-u1))
    return r * jnp.cos(2.0 * jnp.pi * u2)


def _kernel(a_ref, b_ref, *rest, nk: int, noise_mode: str,
            sigma_step: float, out_dtype):
    """rest = [noise_ref?], [seed_ref?], o_ref, acc_ref (positional layout)."""
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
        o_ref[...] = out.astype(out_dtype)


def photonic_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
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
    """C = A @ Bᵀ with optional bank noise.  A:(T,K) B:(M,K) → (T,M).

    Shapes must be multiples of the block sizes (ops.py pads).  Exactly one
    of {noise (T,M) array, seed scalar (with sigma_step>0)} selects the
    noise mode; neither ⇒ ideal hardware.
    """
    t, k_dim = a.shape
    m, kb = b.shape
    assert k_dim == kb, (a.shape, b.shape)
    block_t = min(block_t, t)
    block_m = min(block_m, m)
    block_k = min(block_k, k_dim)
    assert t % block_t == 0 and m % block_m == 0 and k_dim % block_k == 0
    nt, nm, nk = t // block_t, m // block_m, k_dim // block_k
    out_dtype = out_dtype or a.dtype

    if noise is not None:
        noise_mode = "input"
    elif seed is not None:
        # prng structure (seed operand, SMEM spec, grid) is kept even at
        # sigma_step == 0 — the kernel skips the PRNG draw but the zero-noise
        # interpret path still validates the real operand layout
        noise_mode = "prng"
    else:
        noise_mode = "none"

    in_specs = [
        pl.BlockSpec((block_t, block_k), lambda i, j, k: (i, k)),
        pl.BlockSpec((block_m, block_k), lambda i, j, k: (j, k)),
    ]
    operands = [a, b]
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
        name="photonic_matmul",
        metadata={"kernel": "photonic_matmul"},
    )(*operands)


def vmem_bytes(block_t: int, block_m: int, block_k: int, itemsize: int = 4) -> int:
    """Working-set estimate for BlockSpec selection (must fit ~16 MB VMEM)."""
    return (
        block_t * block_k * itemsize  # A tile
        + block_m * block_k * itemsize  # B tile
        + 2 * block_t * block_m * 4  # acc scratch + out tile
        + block_t * block_m * itemsize  # noise tile (worst case)
    )
