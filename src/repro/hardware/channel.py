"""The emulated analog signal chain: DAC → modulator → MRR bank (with
crosstalk + drift) → balanced photodetector → ADC, tiled over bank panels.

This is the device-fidelity twin of ``core.photonics.photonic_matmul``.
Both share ``photonics.normalise_operands`` (per-tensor amplitude encoding
into the photonic [-1, 1] range plus the input/weight fake-quant), so the
"emu" backend drops into every call site of the ``ref``/``pallas``
backends unchanged.  What differs is everything between encode and rescale:

1.  The GeMM compiler's tiling (paper §3): A:(T,K)·B:(M,K)ᵀ is split into
    ⌈M/bank_rows⌉ × ⌈K/bank_cols⌉ panels.  With ``cfg.n_buses`` WDM buses
    the contraction panels are scheduled round-robin across the buses —
    each bus is a full physical (rows, cols) bank with its own
    modulator/DAC and BPD/ADC chain, so ⌈panels / n_buses⌉ parallel
    cycles replace the single-bus panel sequence.  Per-ring
    drift/crosstalk state has shape (n_buses, bank_rows, bank_cols) and
    is shared across the panels each bus executes.
2.  Weight inscription (``calibrate.command_deltas``): Lorentzian LUT
    inversion, crosstalk pre-compensation, heater-DAC quantization.
3.  The physical leak + drift residual perturb the commanded detunings;
    ``mrr.ring_weight`` maps them back to the *realized* weights.
4.  Per-pass BPD noise: the thermal/read floor (``cfg.noise_std``, same
    convention as the abstract model — per-pass "absolute" or bank
    full-scale) plus signal-dependent shot noise, then the per-pass ADC.
5.  Passes accumulate digitally and the result is rescaled.

With ``MRRConfig.ideal()`` and ``noise_std=0`` the chain is numerically the
plain matmul (inscription round-trips exactly); with nonzero ``noise_std``
and no device effects the accumulated noise is statistically identical to
the reference path's single draw — tests/test_hardware.py holds both.

Everything is pure jnp on tile-stacked arrays (the tile axes ride through
``einsum``, i.e. implicitly vmapped), so callers can jit/vmap/grad through
it; the Trainer jits it as part of the train step.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.core import photonics
from repro.hardware import calibrate
from repro.hardware import drift as drift_lib
from repro.hardware import mrr
from repro.lint.runtime import check_finite
from repro.utils import prng


def _pad_axis(x, mult: int, axis: int):
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def tile_operands(a_n, b_n, cfg):
    """Split normalised operands into bank-sized panels scheduled across
    the surviving parallel buses (``photonics.active_buses`` — failed
    buses carry no panels; the scheduler reroutes onto the alive ones).

    a_n: (T, K) -> (T, n_alive, nj, cols);
    b_n: (M, K) -> (nm, n_alive, rows, nj, cols);
    returns (a_t, b_t, n_panels) where n_panels = ⌈K/cols⌉ is the number
    of REAL contraction panels and nj = ⌈n_panels/n_alive⌉ the bus-cycle
    count — panel p runs as cycle p // n_alive on alive bus p % n_alive.
    Zero padding is harmless: padded K columns multiply zero inputs,
    padded M rows are sliced off the output, and bus-padded panels (idle
    buses in the last cycle) are noise-masked in ``bank_product``.
    """
    rows, cols = cfg.bank_rows, cfg.bank_cols
    n_buses = photonics.active_buses(cfg)
    t = a_n.shape[0]
    a_p = _pad_axis(a_n, cols, 1)
    nk = a_p.shape[1] // cols
    a_t = _pad_axis(a_p.reshape(t, nk, cols), n_buses, 1)
    nj = a_t.shape[1] // n_buses
    a_t = a_t.reshape(t, nj, n_buses, cols).transpose(0, 2, 1, 3)
    b_p = _pad_axis(_pad_axis(b_n, rows, 0), cols, 1)
    nm = b_p.shape[0] // rows
    b_t = _pad_axis(b_p.reshape(nm, rows, nk, cols), n_buses, 2)
    b_t = b_t.reshape(nm, rows, nj, n_buses, cols).transpose(0, 3, 1, 2, 4)
    return a_t, b_t, nk


def effective_deltas(w_target, cfg, residual=None):
    """The control-plane half of the inscription path: targets ->
    commanded heaters -> physical detunings (crosstalk leak + drift
    residual).  ``realized_weights`` maps these through the Lorentzian;
    the fused path (``kernels.emu_matmul``) takes them as-is and applies
    the transfer itself.

    ``w_target``: the bus-tiled (nm, n_alive, rows, nj, cols) layout, a
    bus-free (..., rows, nk, cols) panel stack, or a bare (rows, cols)
    grid; ``residual``: per-ring detuning error — (n_alive, rows, cols)
    for the bus-tiled layout, (rows, cols) for bare grids — broadcast
    over the (nm, nj) panel axes.
    """
    device = cfg.mrr or mrr.MRRConfig()
    if (cfg.failed_buses and device.bus_crosstalk != 0.0
            and w_target.ndim >= 5):
        # inter-bus thermal coupling follows the PHYSICAL bank stack, not
        # the compacted alive-bus schedule: a dead (undriven, δ=0) bank
        # between two survivors contributes no aggressor field but still
        # separates them, so both the Jacobi pre-compensation and the
        # leak must run on the physical bus axis
        delta_eff = _physical_bus_effective_deltas(w_target, cfg, device)
    else:
        delta_cmd = calibrate.command_deltas(w_target, device)
        delta_eff = delta_cmd + mrr.crosstalk_leak(delta_cmd, device)
    if residual is not None:
        if w_target.ndim >= 3:  # panel layout: broadcast over (nm, nj)
            delta_eff = delta_eff + residual[..., :, None, :]
        else:
            delta_eff = delta_eff + residual
    return delta_eff


def realized_weights(w_target, cfg, residual=None):
    """The full inscription path: targets -> commanded heaters -> physical
    detunings (leak + drift residual) -> realized Lorentzian weights.
    (See ``effective_deltas`` for the layout/residual conventions.)"""
    device = cfg.mrr or mrr.MRRConfig()
    return mrr.ring_weight(effective_deltas(w_target, cfg, residual),
                           device.gamma)


def _physical_bus_effective_deltas(w_target, cfg, device):
    """Effective (post-leak) detunings for a chip with failed buses and
    inter-bus crosstalk: the alive-layout targets are embedded into the
    physical (nm, n_buses, rows, nj, cols) stack with dead banks pinned
    undriven at δ=0, the controller's pre-compensation and the physical
    leak both act on that stack, and the alive slice is read back."""
    alive = jnp.asarray(photonics.alive_bus_indices(cfg))
    n_buses = max(cfg.n_buses, 1)

    def embed(x):
        shape = x.shape[:-4] + (n_buses,) + x.shape[-3:]
        return jnp.zeros(shape, x.dtype).at[..., alive, :, :, :].set(x)

    delta_target = embed(mrr.inscribe(w_target, device))
    delta_phys = delta_target
    if device.compensate_crosstalk and (
            device.crosstalk != 0.0 or device.bus_crosstalk != 0.0):
        # calibrate.compensate_crosstalk's Jacobi loop, with the dead
        # banks projected back to δ=0 each sweep — the controller never
        # drives them, so they must not accumulate phantom commands that
        # their alive neighbours would then pre-compensate against
        for _ in range(device.ct_iters):
            delta_phys = delta_target - mrr.crosstalk_leak(delta_phys, device)
            delta_phys = embed(jnp.take(delta_phys, alive, axis=-4))
    delta_phys = calibrate.quantize_command(
        jnp.clip(delta_phys, 0.0, device.delta_max), device)
    delta_eff = delta_phys + mrr.crosstalk_leak(delta_phys, device)
    return jnp.take(delta_eff, alive, axis=-4)


def _per_pass_sigma(cfg) -> float:
    """Per-bank-pass BPD read-noise σ in normalised units — the same
    convention switch as ``photonics.noise_sigma_total``."""
    if cfg.noise_convention == "absolute":
        return cfg.noise_std
    if cfg.noise_convention == "fullscale":
        return cfg.noise_std * cfg.bank_cols
    raise ValueError(cfg.noise_convention)


def alive_residual(residual, cfg):
    """Slice a carried drift/cal residual down to the panel schedule's
    alive buses: carried state spans the physical (n_buses, rows, cols)
    grid; the schedule only touches the surviving banks."""
    if residual is not None and cfg.failed_buses and residual.ndim == 3:
        residual = jnp.take(
            residual, jnp.asarray(photonics.alive_bus_indices(cfg)), axis=0)
    return residual


def alive_dead_ring_mask(cfg):
    """Fabrication yield: dead rings read 0 at the BPD whatever was
    commanded — a chip-fixed mask over the physical ring grid, sliced to
    the alive buses.  None when the device has no dead rings."""
    device = cfg.mrr or mrr.MRRConfig()
    if device.dead_ring_rate <= 0.0:
        return None
    phys = mrr.dead_ring_mask(
        device, (max(cfg.n_buses, 1), cfg.bank_rows, cfg.bank_cols))
    return jnp.take(phys, jnp.asarray(photonics.alive_bus_indices(cfg)), axis=0)


def bank_product(a_n, b_n, cfg, key=None, *, residual=None):
    """Noisy panel-accumulated product of normalised operands.

    a_n: (T, K), b_n: (M, K) in [-1, 1]  ->  (T, M) in bank output units.
    """
    device = cfg.mrr or mrr.MRRConfig()
    t, _k = a_n.shape
    m = b_n.shape[0]
    a_t, b_t, n_panels = tile_operands(a_n, b_n, cfg)
    residual = alive_residual(residual, cfg)
    w_eff = realized_weights(b_t, cfg, residual)
    dead = alive_dead_ring_mask(cfg)
    if dead is not None:
        w_eff = w_eff * dead[..., :, None, :]
    # one einsum over all (nm, bus, cycle) panels: p[t, i, r, q, j] is the
    # partial sum of output row block i, ring row r, bus q, bus-cycle j
    p = jnp.einsum("tqjc,iqrjc->tirqj", a_t, w_eff)
    n_buses, nj = a_t.shape[1], a_t.shape[2]
    sigma = _per_pass_sigma(cfg)
    if sigma > 0.0 or device.shot_noise > 0.0:
        if key is None:
            raise ValueError("noisy emulated bank requires a PRNG key")
        # final use of `key`: both physical noise sources draw from the
        # split halves; consume() makes any later reuse a lint error
        k_th, k_sh = jax.random.split(prng.consume(key))
        noise = jnp.zeros_like(p)
        if sigma > 0.0:
            # per-bus BPD/ADC chains: every (bus, cycle) element is an
            # independent draw of the same per-pass read-noise floor
            noise += sigma * jax.random.normal(k_th, p.shape, p.dtype)
        if device.shot_noise > 0.0:
            # shot noise scales with the *clean* per-pass optical signal —
            # independent of (not seeded by) the thermal/read draw
            noise += (device.shot_noise * jnp.sqrt(jnp.abs(p))
                      * jax.random.normal(k_sh, p.shape, p.dtype))
        if n_buses * nj != n_panels:
            # idle buses in the last parallel cycle never fire their BPD —
            # mask their draws so the accumulated noise counts the REAL
            # panels (matching ref's single draw), not the padded schedule
            valid = (jnp.arange(nj)[None, :] * n_buses
                     + jnp.arange(n_buses)[:, None]) < n_panels
            noise = noise * valid
        p = p + noise
    if device.adc_bits is not None:
        # each pass is digitised (per bus) before accumulating; ADC full
        # scale is the bank's maximal inner product, ±bank_cols normalised
        # (a config constant, not a tracer sync)
        p = photonics.fake_quant(p, device.adc_bits, amax=float(cfg.bank_cols))  # lint: disable=RL002
    out = jnp.sum(p, axis=(-2, -1))  # digital accumulation: buses × cycles
    return out.reshape(t, -1)[:, :m]


# ---------------------------------------------------------------------------
# Source-toggle seam (noise-budget attribution, ``repro.obs.attribution``).
# Each physical error source in the chain above can be isolated: a config
# twin with the SAME geometry (bank tiling, buses, failures — so panel
# schedules, padding and noise masks match the real run) but every other
# nonideality off.  Sole-source re-runs under the same PRNG key then see
# the same per-pass draws as the full chain, so their error powers are
# directly comparable.
# ---------------------------------------------------------------------------

NOISE_SOURCES: tuple[str, ...] = (
    "quantization",  # DAC/weight fake-quant + heater-DAC command quant
    "thermal",       # per-pass BPD read/thermal floor (cfg.noise_std)
    "shot",          # signal-dependent shot noise
    "adc",           # per-pass output ADC
    "drift",         # carried resonance-drift residual (needs `residual`)
    "crosstalk",     # intra-bank + inter-bus thermal crosstalk
    "dead_rings",    # fabrication-yield dead rings
)


def ideal_twin(cfg):
    """Nonideality-free twin of ``cfg``: identical geometry and schedule
    (bank_rows/cols, n_buses, failed_buses, f_s), every physical error
    source off.  The attribution probe's clean reference."""
    device = cfg.mrr or mrr.MRRConfig()
    return dataclasses.replace(
        cfg, noise_std=0.0, input_bits=None, weight_bits=None,
        mrr=dataclasses.replace(
            mrr.MRRConfig.ideal(), gamma=device.gamma,
            thermal_settle_s=device.thermal_settle_s))


def isolate_source(cfg, source: str):
    """``cfg`` with exactly one physical error source active.

    For "drift" the residual itself is the caller's to supply
    (``bank_product(..., residual=)``); the returned config only restores
    the device's command clipping so the perturbed detunings land where
    the real chain puts them.  Unknown names raise.
    """
    if source not in NOISE_SOURCES:
        raise ValueError(
            f"unknown noise source {source!r} (one of {NOISE_SOURCES})")
    device = cfg.mrr or mrr.MRRConfig()
    base = ideal_twin(cfg)
    ideal = base.mrr
    if source == "quantization":
        return dataclasses.replace(
            base, input_bits=cfg.input_bits, weight_bits=cfg.weight_bits,
            mrr=dataclasses.replace(ideal, heater_bits=device.heater_bits,
                                    delta_max=device.delta_max))
    if source == "thermal":
        return dataclasses.replace(base, noise_std=cfg.noise_std,
                                   noise_convention=cfg.noise_convention)
    if source == "shot":
        return dataclasses.replace(
            base, mrr=dataclasses.replace(ideal,
                                          shot_noise=device.shot_noise))
    if source == "adc":
        return dataclasses.replace(
            base, mrr=dataclasses.replace(ideal, adc_bits=device.adc_bits))
    if source == "drift":
        return dataclasses.replace(
            base, mrr=dataclasses.replace(ideal, delta_max=device.delta_max))
    if source == "crosstalk":
        return dataclasses.replace(
            base, mrr=dataclasses.replace(
                ideal, crosstalk=device.crosstalk,
                bus_crosstalk=device.bus_crosstalk,
                compensate_crosstalk=device.compensate_crosstalk,
                ct_iters=device.ct_iters, delta_max=device.delta_max))
    # dead_rings
    return dataclasses.replace(
        base, mrr=dataclasses.replace(ideal,
                                      dead_ring_rate=device.dead_ring_rate,
                                      yield_seed=device.yield_seed))


def resolve_emu_kernel(spec: str | None = None) -> str:
    """Resolve the emu execution kernel: an explicit "ref" | "pallas" |
    "xla" passes through; None/"auto" consults the ``REPRO_EMU_KERNEL``
    environment variable and then the platform default — the fused Pallas
    kernel on TPU, the unfused reference chain elsewhere (identical
    numerics to the pre-fusion emulator).  "xla" is the fused schedule
    compiled through lax.scan — the opt-in fast path off-TPU."""
    if spec in (None, "auto"):
        spec = os.environ.get("REPRO_EMU_KERNEL") or None
    if spec in (None, "auto"):
        spec = "pallas" if jax.default_backend() == "tpu" else "ref"
    if spec not in ("ref", "pallas", "xla"):
        raise ValueError(
            f"unknown emu kernel {spec!r} (auto | ref | pallas | xla)")
    return spec


def emulated_matmul(a, b, cfg, key=None, *, state=None,
                    kernel: str | None = None):
    """Device-emulated C = A @ Bᵀ — drop-in for
    ``photonics.photonic_matmul`` (the "emu" backend entry point).

    a: (T, K) amplitude-encoded inputs; b: (M, K) target weights.
    ``state`` overrides the drift state; by default the Trainer's active
    ``drift.use_state`` context is consulted, and with neither the bank is
    drift-free.
    ``kernel`` picks the execution path (``resolve_emu_kernel``): "ref"
    is the unfused chain above; "pallas"/"xla" run the fused panel loop
    of ``kernels.emu_matmul`` (same physics, one kernel per GEMM).
    """
    if not cfg.enabled:
        return jnp.einsum("tk,mk->tm", a, b)
    kernel = resolve_emu_kernel(kernel)
    a_n, b_n, s_a, s_b = photonics.normalise_operands(a, b, cfg)
    if state is None:
        state = drift_lib.active_state()
    residual = drift_lib.residual(state) if state is not None else None
    if kernel == "ref":
        out = bank_product(a_n, b_n, cfg, key, residual=residual)
    else:
        from repro.kernels import emu_matmul  # lazy: kernels import us

        out = emu_matmul.fused_bank_product(a_n, b_n, cfg, key,
                                            residual=residual, impl=kernel)
    out = check_finite(out * (s_a * s_b), "emulated_matmul output")
    return out.astype(jnp.result_type(a, b))
