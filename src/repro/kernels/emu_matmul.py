"""Fused kernel for the emu backend's DAC→ring→ADC hot path.

``hardware.channel.bank_product`` executes the emulated signal chain as a
sequence of jitted ops: one giant einsum materialising EVERY per-panel
partial sum p[t, i, r, q, j] — a tensor ⌈K/bank_cols⌉× the output size —
followed by full-size noise draws, the idle-slot mask, the per-pass ADC
fake-quant, and the digital accumulation.  This module fuses the bus-tiled
panel loop into one kernel invocation per GEMM: each (bus q, bus-cycle j)
slot's Lorentzian transfer, MAC, per-(bus,pass) BPD noise, and ADC
quantisation happen while the partial lives in registers/VMEM, and only
the accumulated (T, M) digital output is ever written back.

Two implementations share the schedule and the PRNG bit-stream:

* ``impl="pallas"`` — a Pallas TPU kernel (grid = row-blocks × output
  row-panels × bus-cycles, f32 VMEM accumulator).  On non-TPU backends it
  runs in the Pallas interpreter (slow — testing only; see ``kernels/ops``
  for the same convention).
* ``impl="xla"``    — the same fused slot loop lowered through
  ``lax.scan``: compiled on every backend, and the fast path for CPU/GPU
  hosts where Mosaic is unavailable.  This is what "compiled fused path"
  means off-TPU in BENCH_emu_kernel.json.

Noise: the unfused path draws per-(bus,pass) thermal and shot noise with
``jax.random.normal`` over the materialised partial tensor.  Here the
draws happen inside the kernel from an inlined threefry2x32 keyed by
(key, slot, element) counters — both impls use the *same* counters, so
pallas and xla noise is bit-identical — and idle padded slots are masked
exactly like the unfused path, keeping ``noise_sigma_total``'s real-panel
accounting (one draw per REAL contraction panel).  Against the unfused
path the noise is statistically identical but not bit-identical (different
PRNG stream); with noise off the two paths agree to f32 tolerance.

Physics boundary: weight *inscription* (heater-DAC quantisation and the
controller's Jacobi crosstalk pre-compensation) is control-plane work
shared verbatim with the unfused path (``channel.effective_deltas``); the
kernel takes the effective drift-perturbed detunings and applies the
photonic part — Lorentzian transfer, dead-ring masking, the MAC, BPD
noise, per-pass ADC — plus the digital accumulation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.lint.runtime import check_finite
from repro.utils import prng

# ---------------------------------------------------------------------------
# threefry2x32 — inlined so the same counter→bits map runs inside the Pallas
# kernel and in the XLA twin (plain uint32 vector ops, no pltpu PRNG needed,
# so interpret mode draws REAL noise too)
# ---------------------------------------------------------------------------

_ROTATIONS = ((13, 15, 26, 6), (17, 29, 16, 24))


def _rotl(x, r: int):
    return (x << jnp.uint32(r)) | (x >> jnp.uint32(32 - r))


def threefry2x32(k0, k1, c0, c1):
    """The Threefry-2x32 block cipher (20 rounds): (key, counter) -> two
    independent uint32 words per counter.  Elementwise over broadcastable
    uint32 inputs."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for i in range(5):
        for r in _ROTATIONS[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r)
            x1 = x1 ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


# Irwin–Hall(4) scale: sum of four 16-bit uniforms has variance
# 4·(65536²−1)/12; √3/65536 normalises it to 1 − 2.3e-10.
_IH4_SCALE = 3.0**0.5 / 65536.0
# counter tweak separating the shot-noise stream from the thermal stream:
# slot counters c0 stay far below 2³¹, so the top bit is free
_SHOT_STREAM = 0x80000000


def counter_gaussian(k0, k1, c0, c1):
    """One standard gaussian per counter: the four 16-bit lanes of the two
    threefry words summed (Irwin–Hall n=4) and rescaled to unit variance.

    Exact mean 0 and variance 1 − 2.3e-10; tails truncate at ±2√3 σ —
    far beyond anything the per-pass ADC resolves, and well inside the
    tolerance of ``noise_sigma_total``'s accounting.  Chosen over
    Box–Muller deliberately: no transcendentals, so it runs inside the
    Pallas kernel without lowering surprises and costs ~an order of
    magnitude less than ``log``+``cos`` over the full partial tensor on
    CPU hosts."""
    b0, b1 = threefry2x32(k0, k1, c0, c1)
    m = jnp.uint32(0xFFFF)
    s = ((b0 & m) + (b0 >> jnp.uint32(16))
         + (b1 & m) + (b1 >> jnp.uint32(16)))
    # s < 2**19, so the int32 hop is exact; Mosaic has no uint32->f32 cast
    return (s.astype(jnp.int32).astype(jnp.float32) - 131070.0) * _IH4_SCALE


def _adc(part, adc_bits: int | None, amax: float):
    """Per-pass ADC — op-for-op identical to photonics.fake_quant with a
    static amax (full scale = the bank's maximal inner product)."""
    if adc_bits is None:
        return part
    levels = max(2 ** (adc_bits - 1) - 1, 1)
    scaled = jnp.clip(part / amax, -1.0, 1.0) * levels
    return jnp.round(scaled) / levels * amax


def _slot_noise(part, k0, k1, c0, c1, valid, sigma: float, shot: float):
    """Per-(bus,pass) BPD noise for one slot's (..., rows) partials: the
    thermal/read floor + signal-dependent shot noise, masked on idle padded
    slots (``valid``) so accumulated noise counts REAL panels only.  The
    two draws come from disjoint counter streams (``_SHOT_STREAM``); each
    is skipped entirely when its amplitude is statically zero."""
    noise = jnp.zeros_like(part)
    if sigma > 0.0:
        noise = noise + sigma * counter_gaussian(k0, k1, c0, c1)
    if shot > 0.0:
        z_sh = counter_gaussian(k0, k1, c0 ^ jnp.uint32(_SHOT_STREAM), c1)
        noise = noise + shot * jnp.sqrt(jnp.abs(part)) * z_sh
    return part + noise * valid


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------


def _emu_kernel(a_ref, d_ref, *rest, q_buses: int, nj: int, n_panels: int,
                gamma: float, sigma: float, shot: float,
                adc_bits: int | None, amax: float, rows: int, block_t: int,
                has_mask: bool, noisy: bool):
    """rest = [mask_ref?], [seed_ref?], o_ref, acc_ref."""
    idx = 0
    mask_ref = None
    seed_ref = None
    if has_mask:
        mask_ref = rest[idx]
        idx += 1
    if noisy:
        seed_ref = rest[idx]
        idx += 1
    o_ref = rest[idx]
    acc_ref = rest[idx + 1]

    tb = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if noisy:
        k0 = seed_ref[0].astype(jnp.uint32)
        k1 = seed_ref[1].astype(jnp.uint32)
        # element id within the (T, rows) face of this slot: rows is the
        # full bank height, so (t_global, r) is globally unique per slot
        tt = jax.lax.broadcasted_iota(jnp.int32, (block_t, rows), 0)
        rr = jax.lax.broadcasted_iota(jnp.int32, (block_t, rows), 1)
        c1 = ((tb * block_t + tt) * rows + rr).astype(jnp.uint32)

    g2 = gamma * gamma
    for q in range(q_buses):
        a = a_ref[q, 0].astype(jnp.float32)  # (block_t, cols)
        delta = d_ref[0, q, 0].astype(jnp.float32)  # (rows, cols)
        d2 = delta * delta
        w = (d2 - g2) / (d2 + g2)  # Lorentzian BPD transfer
        if has_mask:
            w = w * mask_ref[q]  # fabrication-dead rings read 0
        part = jax.lax.dot_general(
            a, w, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        if noisy:
            slot = j * q_buses + q  # panel index this (bus, cycle) executes
            c0 = (i * (q_buses * nj) + slot).astype(jnp.uint32)
            valid = (slot < n_panels).astype(jnp.float32)
            part = _slot_noise(part, k0, k1, c0, c1, valid, sigma, shot)
        part = _adc(part, adc_bits, amax)
        acc_ref[...] += part

    @pl.when(j == nj - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def emu_bank_product_pallas(a_t, delta_eff, dead_mask, *, n_panels: int,
                            gamma: float, sigma: float, shot: float,
                            adc_bits: int | None, amax: float,
                            seed=None, block_t: int = 128,
                            interpret: bool = False):
    """One fused kernel invocation for a whole bus-tiled GEMM.

    a_t: (T, Q, NJ, C) tiled inputs; delta_eff: (nm, Q, rows, NJ, C)
    effective detunings; dead_mask: (Q, rows, C) survival mask or None.
    Returns the accumulated (T, nm*rows) digital output (caller slices M).
    """
    t, q_buses, nj, cols = a_t.shape
    nm, _q, rows, _nj, _c = delta_eff.shape
    noisy = sigma > 0.0 or shot > 0.0
    if noisy and seed is None:
        raise ValueError("noisy fused bank requires a PRNG seed")

    # TPU-friendly layouts: last two dims of every block are the big ones
    a_k = jnp.moveaxis(a_t, 0, 2)  # (Q, NJ, T, C)
    rem = (-t) % block_t
    if rem:
        a_k = jnp.pad(a_k, ((0, 0), (0, 0), (0, rem), (0, 0)))
    t_pad = t + rem
    bt = min(block_t, t_pad)
    d_k = jnp.moveaxis(delta_eff, 2, 3)  # (nm, Q, NJ, rows, C)

    in_specs = [
        pl.BlockSpec((q_buses, 1, bt, cols), lambda tb, i, j: (0, j, tb, 0)),
        pl.BlockSpec((1, q_buses, 1, rows, cols),
                     lambda tb, i, j: (i, 0, j, 0, 0)),
    ]
    operands = [a_k, d_k]
    if dead_mask is not None:
        in_specs.append(pl.BlockSpec((q_buses, rows, cols),
                                     lambda tb, i, j: (0, 0, 0)))
        operands.append(dead_mask)
    if noisy:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.asarray(seed, jnp.uint32).astype(jnp.int32))

    kern = functools.partial(
        _emu_kernel, q_buses=q_buses, nj=nj, n_panels=n_panels, gamma=gamma,
        sigma=sigma, shot=shot, adc_bits=adc_bits, amax=amax, rows=rows,
        block_t=bt, has_mask=dead_mask is not None, noisy=noisy)

    # output row-panel major: a (bt, rows) block then spans the array's
    # whole last dim, which Mosaic accepts for any bank height (a (bt, 50)
    # block over a (T, nm*50) array is refused: 50 is not a lane multiple)
    out = pl.pallas_call(
        kern,
        grid=(t_pad // bt, nm, nj),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bt, rows), lambda tb, i, j: (i, tb, 0)),
        out_shape=jax.ShapeDtypeStruct((nm, t_pad, rows), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, rows), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(*operands)
    return jnp.moveaxis(out[:, :t], 0, 1).reshape(t, nm * rows)


# ---------------------------------------------------------------------------
# XLA twin — the same slot decomposition, slot-major batched dot_general
# ---------------------------------------------------------------------------


def emu_bank_product_xla(a_t, delta_eff, dead_mask, *, n_panels: int,
                         gamma: float, sigma: float, shot: float,
                         adc_bits: int | None, amax: float, seed=None):
    """Compiled-everywhere realisation of the fused panel loop.

    Where the unfused path's ``einsum("tqjc,iqrjc->tirqj")`` decomposes
    into ⌈M/rows⌉·Q·NJ *tiny* (T×C)·(C×rows) products — pathological for
    XLA:CPU's GEMM — this lowers the identical math as ONE batched
    ``dot_general`` over the n_panels slot axis with (T, C, nm·rows)
    per-slot shapes, and the noise + ADC epilogue as a single vectorised
    pass XLA fuses into the consumer (one threefry draw per element,
    not one ``random.normal`` sub-launch per scan step).  Same counter
    scheme as the Pallas kernel ⇒ bit-identical noise."""
    t, q_buses, nj, cols = a_t.shape
    nm, _q, rows, _nj, _c = delta_eff.shape
    noisy = sigma > 0.0 or shot > 0.0
    if noisy and seed is None:
        raise ValueError("noisy fused bank requires a PRNG seed")

    g2 = gamma * gamma
    d2 = jnp.square(delta_eff)
    w = (d2 - g2) / (d2 + g2)
    if dead_mask is not None:
        w = w * dead_mask[None, :, :, None, :]
    n_slots = q_buses * nj
    m_pad = nm * rows
    # slot-major layouts: slot s = j·Q + q (cycle-major, matching the
    # emulator's panel→(bus, cycle) assignment and the kernel's counters)
    a_sl = a_t.transpose(2, 1, 0, 3).reshape(n_slots, t, cols)
    w_sl = w.transpose(3, 1, 0, 2, 4).reshape(n_slots, m_pad, cols)
    part = jax.lax.dot_general(
        a_sl, w_sl, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)  # (S, T, m_pad)

    if noisy:
        k0 = jnp.asarray(seed, jnp.uint32)[0]
        k1 = jnp.asarray(seed, jnp.uint32)[1]
        # counters off the (S, T, nm, rows) view: the kernel's (i, slot)
        # and (t_global, r) ids fall straight out of the iotas — no
        # integer div/mod, which XLA:CPU scalarises (no SIMD idiv) at
        # several× the cost of the threefry itself
        shape4 = (n_slots, t, nm, rows)
        ss = jax.lax.broadcasted_iota(jnp.int32, shape4, 0)
        tt = jax.lax.broadcasted_iota(jnp.int32, shape4, 1)
        ii = jax.lax.broadcasted_iota(jnp.int32, shape4, 2)
        rr = jax.lax.broadcasted_iota(jnp.int32, shape4, 3)
        c0 = (ii * n_slots + ss).astype(jnp.uint32)
        c1 = (tt * rows + rr).astype(jnp.uint32)
        valid = (ss < n_panels).astype(jnp.float32)
        part = _slot_noise(part.reshape(shape4), k0, k1, c0, c1, valid,
                           sigma, shot).reshape(n_slots, t, m_pad)
    part = _adc(part, adc_bits, amax)
    return jnp.sum(part, axis=0)  # digital accumulation over all slots


# ---------------------------------------------------------------------------
# bank_product drop-in
# ---------------------------------------------------------------------------


def fused_bank_product(a_n, b_n, cfg, key=None, *, residual=None,
                       impl: str = "xla", block_t: int = 128,
                       interpret: bool | None = None):
    """Drop-in for ``hardware.channel.bank_product`` on the fused path.

    a_n: (T, K), b_n: (M, K) normalised operands -> (T, M) in bank output
    units (the caller rescales by s_a·s_b, exactly as for the unfused
    path).  ``impl``: "pallas" (TPU kernel; interpret-mode fallback off
    TPU) or "xla" (the scan twin, compiled everywhere).
    """
    from repro.hardware import channel  # lazy: channel lazily imports us
    from repro.hardware import mrr

    device = cfg.mrr or mrr.MRRConfig()
    t = a_n.shape[0]
    m = b_n.shape[0]
    a_t, b_t, n_panels = channel.tile_operands(a_n, b_n, cfg)
    residual = channel.alive_residual(residual, cfg)
    delta_eff = channel.effective_deltas(b_t, cfg, residual)
    dead_mask = channel.alive_dead_ring_mask(cfg)

    sigma = channel._per_pass_sigma(cfg)
    shot = device.shot_noise
    noisy = sigma > 0.0 or shot > 0.0
    seed = None
    if noisy:
        if key is None:
            raise ValueError("noisy emulated bank requires a PRNG key")
        seed = (jax.random.key_data(prng.consume(key))
                .reshape(-1)[-2:].astype(jnp.uint32))

    kwargs = dict(n_panels=n_panels, gamma=float(device.gamma),
                  sigma=float(sigma), shot=float(shot),
                  adc_bits=device.adc_bits, amax=float(cfg.bank_cols),
                  seed=seed)
    if impl == "pallas":
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        out = emu_bank_product_pallas(a_t, delta_eff, dead_mask,
                                      block_t=block_t, interpret=interpret,
                                      **kwargs)
    elif impl == "xla":
        out = emu_bank_product_xla(a_t, delta_eff, dead_mask, **kwargs)
    else:
        raise ValueError(f"unknown fused impl {impl!r} (pallas | xla)")
    return check_finite(out[:t, :m], f"fused_bank_product[{impl}] output")
