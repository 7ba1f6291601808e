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

* ``impl="pallas"`` — a Pallas TPU kernel (``tile_plan``): a grid of
  token blocks × lane blocks × bus-cycle blocks, each step a lane-dense
  (token rows × every output row panel) tile, side by side along the
  lanes, accumulated in place over its cycles.  The epilogue (noise, ADC,
  accumulation) runs per slot in register-sized row strips.  At qwen's
  8192 × 1024 × 1024 projection that is 64 × 1 × 2 steps a call, where a
  (128-token, one 50-row panel, one cycle) grid took 69,888.  On non-TPU
  backends it runs in the Pallas interpreter (slow — testing only; see
  ``kernels/ops`` for the same convention).
* ``impl="xla"``    — the same fused slot loop lowered through
  ``lax.scan``: compiled on every backend, and the fast path for CPU/GPU
  hosts where Mosaic is unavailable.  This is what "compiled fused path"
  means off-TPU in BENCH_emu_kernel.json.

Noise: the unfused path draws per-(bus,pass) thermal and shot noise with
``jax.random.normal`` over the materialised partial tensor.  Here the
draws happen inside the kernel from an inlined threefry2x32 of 13 rounds
(``_NOISE_ROUNDS``; ``counter_gaussian`` says why 13) keyed by (key, slot,
element) counters — both impls use the *same* counters, so pallas and xla
noise is bit-identical — and idle padded slots are masked exactly like the
unfused path, keeping ``noise_sigma_total``'s real-panel accounting (one
draw per REAL contraction panel).  Against the unfused path the noise is
statistically identical but not bit-identical (different PRNG stream);
with noise off the two paths agree to f32 tolerance.

Physics boundary: weight *inscription* (heater-DAC quantisation and the
controller's Jacobi crosstalk pre-compensation) is control-plane work
shared verbatim with the unfused path (``channel.effective_deltas``); the
fused path takes the effective drift-perturbed detunings and applies the
photonic part — Lorentzian transfer and dead-ring masking (once a call,
ahead of the kernel), then the MAC, BPD noise and per-pass ADC — plus the
digital accumulation.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

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


def threefry2x32(k0, k1, c0, c1, rounds: int = 20):
    """The Threefry-2x32 block cipher: (key, counter) -> two independent
    uint32 words per counter.  Elementwise over broadcastable uint32
    inputs.

    ``rounds`` (static) stops the standard schedule early: round n rotates
    by ``_ROTATIONS[(n // 4) % 2][n % 4]`` and a key injection follows
    every 4th round.  The default 20 is the standard cipher (JAX's own
    ``threefry_2x32``); 13 runs the first 13 rounds, with injections after
    rounds 4, 8 and 12."""
    ks = (k0, k1, k0 ^ k1 ^ jnp.uint32(0x1BD11BDA))
    x0 = c0 + ks[0]
    x1 = c1 + ks[1]
    for n in range(rounds):
        x0 = x0 + x1
        x1 = _rotl(x1, _ROTATIONS[(n // 4) % 2][n % 4])
        x1 = x1 ^ x0
        if n % 4 == 3:
            i = n // 4
            x0 = x0 + ks[(i + 1) % 3]
            x1 = x1 + ks[(i + 2) % 3] + jnp.uint32(i + 1)
    return x0, x1


# threefry rounds behind the kernel's noise (counter_gaussian)
_NOISE_ROUNDS = 13


# Irwin–Hall(4) scale: sum of four 16-bit uniforms has variance
# 4·(65536²−1)/12; √3/65536 normalises it to 1 − 2.3e-10.
_IH4_SCALE = 3.0**0.5 / 65536.0
# counter tweak separating the shot-noise stream from the thermal stream:
# slot counters c0 stay far below 2³¹, so the top bit is free
_SHOT_STREAM = 0x80000000


def counter_gaussian(k0, k1, c0, c1):
    """One standard gaussian per counter: the four 16-bit lanes of the two
    words of ``_NOISE_ROUNDS``-round threefry summed (Irwin–Hall n=4) and
    rescaled to unit variance.

    Exact mean 0 and variance 1 − 2.3e-10; tails truncate at ±2√3 σ —
    far beyond anything the per-pass ADC resolves, and well inside the
    tolerance of ``noise_sigma_total``'s accounting.  Chosen over
    Box–Muller deliberately: no transcendentals, so it runs inside the
    Pallas kernel without lowering surprises and costs ~an order of
    magnitude less than ``log``+``cos`` over the full partial tensor on
    CPU hosts.

    The cipher runs ``_NOISE_ROUNDS`` = 13 rounds, not the standard 20:
    the reduced-round Threefry-2x32 that its authors report
    Crush-resistant (Salmon et al., "Parallel random numbers: as easy as
    1, 2, 3", SC'11, Table 2; 20 is their margin for general use), at 76
    integer ops a counter against 119, in a kernel whose pace the
    generator sets.  ``tests/test_emu_kernel.py`` checks the stream over
    a (slot × element) grid laid out as the kernel's counters:
    neighbouring-element, neighbouring-slot and thermal-vs-shot
    correlation and the top byte's χ², which 4, 6 and 8 rounds fail."""
    b0, b1 = threefry2x32(k0, k1, c0, c1, _NOISE_ROUNDS)
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


def _ring_transfer(delta_eff, dead_mask, gamma: float):
    """Lorentzian BPD transfer of the (nm, Q, rows, NJ, C) effective
    detunings; fabrication-dead rings read 0."""
    g2 = gamma * gamma
    d2 = jnp.square(delta_eff)
    w = (d2 - g2) / (d2 + g2)
    if dead_mask is not None:
        w = w * dead_mask[None, :, :, None, :]
    return w


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

# VMEM the kernel's blocks may take: the output block (double-buffered),
# the partial scratch and the double-buffered per-cycle input and transfer
# blocks, inside v5e's 16 MiB scoped default
_VMEM_BYTES = 14 << 20
# Epilogue strip: rows × lanes per quantity, so that threefry's words, the
# partial and the noise stay in the 64-entry vector register file (about
# eight f32 vregs each)
_STRIP_ELEMS = 8 * 1024


class TilePlan(NamedTuple):
    bt: int  # token rows per grid step
    bm: int  # output lanes per grid step: a multiple of 128
    nj_blk: int  # bus cycles per grid step
    m_pad: int  # output lanes in all: nm·rows rounded up to whole blocks
    grid: tuple[int, int, int]  # (token blocks, lane blocks, cycle blocks)
    strip: int  # epilogue rows per inner-loop iteration


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tile_plan(t: int, q_buses: int, nj: int, cols: int, m: int,
              block_t: int = 128) -> TilePlan:
    """The fused kernel's tiling for a (T, Q, NJ, C) × (C, M) bank product.

    A grid step owns a lane-dense (bt, bm) output tile and runs nj_blk bus
    cycles of all Q buses on it.  bt follows T (a short T is not padded to
    ``block_t``); bm spans every row panel of the output (nm·rows rounded up
    to 128 lanes) unless half the VMEM budget cannot hold that tile's
    buffers and one cycle's transfer block, when the lanes split into equal
    blocks; nj_blk is the most cycles whose double-buffered blocks fit the
    rest, spread evenly over the fewest cycle blocks."""
    bt = min(block_t, _round_up(t, 8))
    c_lanes, c_rows = _round_up(cols, 128), _round_up(cols, 8)
    lanes = _round_up(m, 128)
    lane_cap = (_VMEM_BYTES // 2) // (4 * (3 * bt + 2 * q_buses * c_rows))
    n_mb = -(-lanes // max(128, lane_cap // 128 * 128))
    bm = _round_up(-(-lanes // n_mb), 128)
    fixed = 3 * 4 * bt * bm  # output block ×2, partial scratch
    per_cycle = 2 * 4 * q_buses * (bt * c_lanes + c_rows * bm)
    n_jb = -(-nj // max(1, (_VMEM_BYTES - fixed) // per_cycle))
    nj_blk = -(-nj // n_jb)
    strip = 8
    while bt % (2 * strip) == 0 and 2 * strip * bm <= _STRIP_ELEMS:
        strip *= 2
    return TilePlan(bt, bm, nj_blk, n_mb * bm,
                    (-(-t // bt), n_mb, n_jb), strip)


def _emu_kernel(a_ref, w_ref, *rest, q_buses: int, nj: int, nj_blk: int,
                n_panels: int, sigma: float, shot: float,
                adc_bits: int | None, amax: float, rows: int, strip: int,
                noisy: bool):
    """rest = [ids_ref, seed_ref]?, o_ref, part_ref.

    One grid step: token block tb, lane block mb, bus cycles
    [jb·nj_blk, jb·nj_blk + nj_blk) ∩ [0, NJ).  Each slot's (bt, bm) partial
    is one MXU product into part_ref; its noise + ADC epilogue then runs
    strip by strip and adds into the output block, which stays resident
    across the cycle axis."""
    if noisy:
        ids_ref, seed_ref, o_ref, part_ref = rest
    else:
        o_ref, part_ref = rest
    tb = pl.program_id(0)
    jb = pl.program_id(2)
    bt = part_ref.shape[0]

    @pl.when(jb == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    if noisy:
        k0 = seed_ref[0].astype(jnp.uint32)
        k1 = seed_ref[1].astype(jnp.uint32)
        # lane m holds output row m = i·rows + r: panel i and bank row r
        panel = ids_ref[0:1, :]
        bank_row = ids_ref[1:2, :]

    def slot_epilogue(slot):
        if noisy:
            # counters as on the (T, rows) face of panel i's slot:
            # c0 = i·(Q·NJ) + slot, c1 = t_global·rows + r
            c0 = (panel * (q_buses * nj) + slot).astype(jnp.uint32)
            valid = (slot < n_panels).astype(jnp.float32)

        def body(s, carry):
            r0 = pl.multiple_of(s * strip, strip)
            part = part_ref[pl.ds(r0, strip), :]
            if noisy:
                tt = jax.lax.broadcasted_iota(jnp.int32, part.shape, 0)
                c1 = ((tb * bt + r0 + tt) * rows
                      + bank_row).astype(jnp.uint32)
                part = _slot_noise(part, k0, k1, c0, c1, valid, sigma, shot)
            part = _adc(part, adc_bits, amax)
            o_ref[pl.ds(r0, strip), :] += part
            return carry

        # unrolled, so that one strip's threefry overlaps the next one's
        jax.lax.fori_loop(0, bt // strip, body, 0, unroll=True)

    def cycle(jj, carry):
        j = jb * nj_blk + jj

        @pl.when(j < nj)
        def _run():
            # slots in cycle-major order j·Q + q, as the f32 sums always ran
            for q in range(q_buses):
                part_ref[...] = jax.lax.dot_general(
                    a_ref[q, jj], w_ref[jj, q], (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                slot_epilogue(j * q_buses + q)

        return carry

    jax.lax.fori_loop(0, nj_blk, cycle, 0)


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
    m = nm * rows
    noisy = sigma > 0.0 or shot > 0.0
    if noisy and seed is None:
        raise ValueError("noisy fused bank requires a PRNG seed")
    plan = tile_plan(t, q_buses, nj, cols, m, block_t)
    bt, bm, nj_blk, m_pad = plan.bt, plan.bm, plan.nj_blk, plan.m_pad
    t_pad = plan.grid[0] * bt
    nj_pad = plan.grid[2] * nj_blk

    # bus-tiled inputs (Q, NJ, T, C); the transfer per slot as (C, m_pad):
    # the ring weights of every row panel side by side along the lanes
    a_k = jnp.pad(jnp.moveaxis(a_t, 0, 2).astype(jnp.float32),
                  ((0, 0), (0, nj_pad - nj), (0, t_pad - t), (0, 0)))
    w = _ring_transfer(delta_eff.astype(jnp.float32), dead_mask, gamma)
    w_k = jnp.pad(w.transpose(3, 1, 4, 0, 2).reshape(nj, q_buses, cols, m),
                  ((0, nj_pad - nj), (0, 0), (0, 0), (0, m_pad - m)))

    in_specs = [
        pl.BlockSpec((q_buses, nj_blk, bt, cols),
                     lambda tb, mb, jb: (0, jb, tb, 0)),
        pl.BlockSpec((nj_blk, q_buses, cols, bm),
                     lambda tb, mb, jb: (jb, 0, 0, mb)),
    ]
    operands = [a_k, w_k]
    if noisy:
        lane = jnp.arange(m_pad, dtype=jnp.int32)
        in_specs += [pl.BlockSpec((2, bm), lambda tb, mb, jb: (0, mb)),
                     pl.BlockSpec(memory_space=pltpu.SMEM)]
        operands += [jnp.stack([lane // rows, lane % rows]),
                     jnp.asarray(seed, jnp.uint32).astype(jnp.int32)]

    kern = functools.partial(
        _emu_kernel, q_buses=q_buses, nj=nj, nj_blk=nj_blk,
        n_panels=n_panels, sigma=sigma, shot=shot, adc_bits=adc_bits,
        amax=amax, rows=rows, strip=plan.strip, noisy=noisy)

    # a 3-D (1, T, m_pad) result keeps the call's signature the profile
    # reader matches: (Q, NJ, T, C) in, (·, T, ·) out
    out = pl.pallas_call(
        kern,
        grid=plan.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bt, bm),
                               lambda tb, mb, jb: (0, tb, mb)),
        out_shape=jax.ShapeDtypeStruct((1, t_pad, m_pad), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bt, bm), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="emu_bank",
        metadata={"kernel": "emu_bank"},
    )(*operands)
    return out[0, :t, :m]


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

    w = _ring_transfer(delta_eff, dead_mask, gamma)
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
