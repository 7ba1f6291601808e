"""Fused emu kernel (kernels.emu_matmul) — drop-in equivalence with the
unfused ``channel.bank_product`` chain, the pallas↔xla bit-stream contract,
``noise_sigma_total`` accounting, the ``emu_kernel`` seam (env/flag/session
resolution), and the fused path through full training sessions."""

import dataclasses
import math
import os

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import photonics
from repro.hardware import channel, drift, mrr
from repro.kernels import emu_matmul

KEY = jax.random.PRNGKey(7)


def _operands(t, m, k, cfg, seed=0):
    ka, kb = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.normal(ka, (t, k), jnp.float32)
    b = jax.random.normal(kb, (m, k), jnp.float32)
    a_n, b_n, _sa, _sb = photonics.normalise_operands(a, b, cfg)
    return a_n, b_n


def _quiet(n_buses=1, failed_buses=(), dead=0.0, adc_bits=8):
    """A noiseless device config (drift off, σ=0): fused and unfused paths
    must agree to f32 tolerance, not just statistically."""
    return photonics.PhotonicConfig(
        noise_std=0.0, n_buses=n_buses, failed_buses=failed_buses,
        mrr=mrr.MRRConfig(adc_bits=adc_bits, drift_sigma=0.0,
                          dead_ring_rate=dead))


# ---------------------------------------------------------------------------
# noiseless bit-tolerance vs the unfused chain
# ---------------------------------------------------------------------------

# Pallas tilings that split the grid: a token block below T (T ragged to
# 8), and a VMEM budget small enough to split the lanes into blocks and the
# bus cycles into blocks that do not divide them (see test_tile_plan_*)
_T_ABOVE_BLOCK = {"block_t": 32}
_SPLIT_BLOCKS = {"block_t": 16, "vmem": 512 * 1024}


def _tiling(monkeypatch, tiling):
    """Apply a test tiling; -> the ``block_t`` keyword it asks for."""
    tiling = tiling or {}
    if "vmem" in tiling:
        monkeypatch.setattr(emu_matmul, "_VMEM_BYTES", tiling["vmem"])
    return {"block_t": tiling["block_t"]} if "block_t" in tiling else {}


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize(
    "t,m,k,n_buses,tiling", [
        # exactly one bank panel
        pytest.param(4, 50, 20, 1, None, id="4-50-20-1"),
        # ragged in every dimension
        pytest.param(7, 61, 83, 2, None, id="7-61-83-2"),
        # panels not divisible by buses (idle slots)
        pytest.param(5, 61, 83, 5, None, id="5-61-83-5"),
        # multi-tile rows and cycles
        pytest.param(16, 130, 260, 4, None, id="16-130-260-4"),
        # 500 lanes (not a multiple of 128); T = 37 over blocks of 32
        pytest.param(37, 500, 100, 1, _T_ABOVE_BLOCK, id="t-above-block"),
        # lane blocks; NJ = 5 in cycle blocks of 2; 13 panels on 15 slots
        pytest.param(37, 300, 260, 3, _SPLIT_BLOCKS, id="split-blocks"),
    ])
def test_fused_matches_unfused_noiseless(monkeypatch, impl, t, m, k, n_buses,
                                         tiling):
    cfg = _quiet(n_buses=n_buses)
    a_n, b_n = _operands(t, m, k, cfg)
    ref = channel.bank_product(a_n, b_n, cfg, None)
    out = emu_matmul.fused_bank_product(a_n, b_n, cfg, None, impl=impl,
                                        **_tiling(monkeypatch, tiling))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_tile_plan_lane_dense_at_qwen_shape():
    """qwen1.5-0.5b's DFA projection (8192 × 1024 × 1024, one bus, 50 × 20
    bank): every row panel in one lane-dense tile, a few hundred grid
    steps a call; MNIST's (64 × 10 × 800) runs as one step, unpadded."""
    plan = emu_matmul.tile_plan(8192, 1, 52, 20, 21 * 50)
    assert math.prod(plan.grid) < 1000
    assert 21 * 50 / plan.m_pad >= 0.9 and plan.bm == plan.m_pad
    mnist = emu_matmul.tile_plan(64, 1, 1, 20, 16 * 50)
    assert mnist.grid == (1, 1, 1) and mnist.bt == 64


def test_tile_plan_splits_what_the_budget_cannot_hold(monkeypatch):
    """The parity cases' tilings split the grid as they say: T ragged over
    token blocks, bank rows over lane blocks, NJ over cycle blocks that do
    not divide it, and the epilogue over several strips."""
    plan = emu_matmul.tile_plan(37, 1, 5, 20, 500, block_t=32)
    assert plan.grid[0] == 2 and plan.m_pad == 512 and plan.bt // plan.strip > 1
    monkeypatch.setattr(emu_matmul, "_VMEM_BYTES", _SPLIT_BLOCKS["vmem"])
    plan = emu_matmul.tile_plan(37, 3, 5, 20, 300, block_t=16)
    assert plan.grid[0] == 3 and plan.grid[1] > 1
    assert plan.grid[2] > 1 and 5 % plan.nj_blk
    # a decode step through a 151,936-wide head: lanes in blocks that fit
    monkeypatch.undo()
    plan = emu_matmul.tile_plan(8, 1, 52, 20, 3039 * 50)
    assert plan.grid[1] > 1 and plan.bm % 128 == 0


def test_fused_matches_unfused_failed_bus_and_dead_rings():
    cfg = _quiet(n_buses=3, failed_buses=(1,), dead=0.05)
    a_n, b_n = _operands(9, 120, 130, cfg)
    ref = channel.bank_product(a_n, b_n, cfg, None)
    for impl in ("xla", "pallas"):
        out = emu_matmul.fused_bank_product(a_n, b_n, cfg, None, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_fused_matches_unfused_with_carried_drift_state():
    """A nonzero carried residual perturbs the detunings identically on
    both paths (drift σ stays 0 so the comparison is deterministic)."""
    cfg = _quiet(n_buses=2)
    a_n, b_n = _operands(6, 77, 95, cfg)
    state = drift.init_state(cfg)
    state["drift"] = 0.08 * jax.random.normal(KEY, state["drift"].shape)
    residual = drift.residual(state)
    ref = channel.bank_product(a_n, b_n, cfg, None, residual=residual)
    for impl in ("xla", "pallas"):
        out = emu_matmul.fused_bank_product(a_n, b_n, cfg, None,
                                            residual=residual, impl=impl)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


def test_fused_no_adc_path():
    cfg = _quiet(n_buses=2, adc_bits=None)
    a_n, b_n = _operands(3, 55, 44, cfg)
    ref = channel.bank_product(a_n, b_n, cfg, None)
    out = emu_matmul.fused_bank_product(a_n, b_n, cfg, None, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@hypothesis.given(
    t=st.integers(1, 17), m=st.integers(1, 140), k=st.integers(1, 150),
    n_buses=st.integers(1, 5), adc_bits=st.sampled_from([None, 4, 8]),
    dead=st.sampled_from([0.0, 0.1]),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_fused_equivalence_fuzz(t, m, k, n_buses, adc_bits, dead):
    """Property: fused-xla ≡ unfused over random shapes, bus counts, ADC
    widths and dead-ring masks (noiseless)."""
    cfg = _quiet(n_buses=n_buses, adc_bits=adc_bits, dead=dead)
    a_n, b_n = _operands(t, m, k, cfg, seed=t * 977 + m * 31 + k)
    ref = channel.bank_product(a_n, b_n, cfg, None)
    out = emu_matmul.fused_bank_product(a_n, b_n, cfg, None, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# noise: pallas↔xla bit-stream contract + σ accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "shot,t,m,k,n_buses,dead,tiling", [
        pytest.param(0.0, 9, 73, 100, 2, 0.0, None, id="0.0"),
        pytest.param(0.05, 9, 73, 100, 2, 0.0, None, id="0.05"),
        # 500 lanes (not a multiple of 128); T = 37 over blocks of 32
        pytest.param(0.05, 37, 500, 100, 1, 0.0, _T_ABOVE_BLOCK,
                     id="t-above-block"),
        # lane blocks; NJ = 5 in cycle blocks of 2; 13 panels on 15 slots
        pytest.param(0.05, 37, 300, 260, 3, 0.0, _SPLIT_BLOCKS,
                     id="split-blocks"),
        # fabrication-dead rings under noise; 150 lanes
        pytest.param(0.05, 9, 130, 150, 2, 0.1, None, id="dead-rings"),
    ])
def test_pallas_and_xla_share_the_noise_stream(monkeypatch, shot, t, m, k,
                                               n_buses, dead, tiling):
    """Both impls draw from the same (key, slot, element) counters, so the
    noisy outputs agree to accumulation-order tolerance — not merely in
    distribution."""
    cfg = photonics.PhotonicConfig(
        noise_std=0.202, n_buses=n_buses,
        mrr=mrr.MRRConfig(adc_bits=8, drift_sigma=0.0, shot_noise=shot,
                          dead_ring_rate=dead))
    a_n, b_n = _operands(t, m, k, cfg)
    x = emu_matmul.fused_bank_product(a_n, b_n, cfg, KEY, impl="xla")
    p = emu_matmul.fused_bank_product(a_n, b_n, cfg, KEY, impl="pallas",
                                      **_tiling(monkeypatch, tiling))
    np.testing.assert_allclose(np.asarray(x), np.asarray(p),
                               rtol=1e-5, atol=1e-5)


def test_fused_noise_requires_key():
    cfg = photonics.PhotonicConfig(noise_std=0.1, n_buses=1,
                                   mrr=mrr.MRRConfig(drift_sigma=0.0))
    a_n, b_n = _operands(2, 10, 20, cfg)
    with pytest.raises(ValueError, match="PRNG key"):
        emu_matmul.fused_bank_product(a_n, b_n, cfg, None, impl="xla")


def test_fused_noise_matches_sigma_accounting():
    """Accumulated fused-path noise must follow ``noise_sigma_total``'s
    real-panel accounting (idle padded slots draw nothing)."""
    cfg = photonics.PhotonicConfig(
        noise_std=0.202, n_buses=4,
        mrr=mrr.MRRConfig(adc_bits=None, drift_sigma=0.0))
    k_dim = 1024
    a_n, b_n = _operands(16, 64, k_dim, cfg)
    clean = emu_matmul.fused_bank_product(
        a_n, b_n, dataclasses.replace(cfg, noise_std=0.0), None, impl="xla")
    f = jax.jit(lambda kk: emu_matmul.fused_bank_product(
        a_n, b_n, cfg, kk, impl="xla"))
    devs = jnp.stack([f(jax.random.fold_in(KEY, i)) - clean
                      for i in range(48)])
    # operands are normalised, so expected σ uses unit scales
    expected = photonics.noise_sigma_total(k_dim, 1.0, 1.0, cfg)
    assert abs(float(jnp.std(devs)) / expected - 1.0) < 0.05
    assert abs(float(jnp.mean(devs))) < 0.05 * expected


def test_counter_gaussian_moments():
    """The Irwin–Hall(4) draw: exact mean/unit variance, symmetric, and
    the designed mild kurtosis deficit (2.7 vs 3)."""
    c0 = jax.lax.broadcasted_iota(jnp.uint32, (1 << 19,), 0)
    z = emu_matmul.counter_gaussian(jnp.uint32(3), jnp.uint32(5), c0,
                                    jnp.uint32(11))
    assert abs(float(z.mean())) < 5e-3
    assert abs(float(z.std()) - 1.0) < 5e-3
    assert abs(float(jnp.mean(z ** 3))) < 2e-2
    assert abs(float(jnp.mean(z ** 4)) - 2.7) < 5e-2


def test_shot_stream_is_distinct():
    """Thermal and shot draws come from disjoint counter streams."""
    c0 = jax.lax.broadcasted_iota(jnp.uint32, (4096,), 0)
    z1 = emu_matmul.counter_gaussian(jnp.uint32(3), jnp.uint32(5), c0,
                                     jnp.uint32(0))
    z2 = emu_matmul.counter_gaussian(
        jnp.uint32(3), jnp.uint32(5),
        c0 ^ jnp.uint32(emu_matmul._SHOT_STREAM), jnp.uint32(0))
    corr = float(jnp.corrcoef(z1, z2)[0, 1])
    assert abs(corr) < 0.05


def test_threefry_at_20_rounds_is_jax_threefry():
    """The full-round schedule is the standard cipher: JAX's own
    Threefry-2x32 on the same keys and counters, word for word."""
    from jax._src import prng
    count = jnp.arange(4096, dtype=jnp.uint32)
    for k0, k1 in [(0, 0), (3, 5), (0x13198A2E, 0x03707344)]:
        key = jnp.array([k0, k1], jnp.uint32)
        x0, x1 = emu_matmul.threefry2x32(key[0], key[1], count[:2048],
                                         count[2048:], rounds=20)
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(x0), np.asarray(x1)]),
            np.asarray(prng.threefry_2x32(key, count)))


# 256-bin χ² (255 degrees of freedom) above this has p < 1e-4
_CHI2_LIMIT = 350.0
_STREAM_KEYS = [(0, 0), (3, 5), (0x13198A2E, 0x03707344),
                (0xDEADBEEF, 0x12345678)]


def _stream_defects(rounds):
    """Statistics of ``counter_gaussian`` at ``rounds`` over a 1024-slot ×
    1024-element counter grid, laid out as the kernel's (c0 = slot,
    c1 = element): -> the checks that fail, over a few keys."""
    defects = set()

    def corr(a, b):
        a, b = a - a.mean(), b - b.mean()
        return float((a * b).mean() / jnp.sqrt((a * a).mean()
                                               * (b * b).mean()))

    @jax.jit
    def draw(k0, k1):
        c0 = jax.lax.broadcasted_iota(jnp.uint32, (1024, 1024), 0)
        c1 = jax.lax.broadcasted_iota(jnp.uint32, (1024, 1024), 1)
        z = emu_matmul.counter_gaussian(k0, k1, c0, c1)
        z_shot = emu_matmul.counter_gaussian(
            k0, k1, c0 ^ jnp.uint32(emu_matmul._SHOT_STREAM), c1)
        top = jnp.stack(emu_matmul.threefry2x32(k0, k1, c0, c1, rounds))
        hist = jnp.bincount((top >> 24).astype(jnp.int32).ravel(),
                            length=256)
        return z, z_shot, hist

    for k0, k1 in _STREAM_KEYS:
        z, z_shot, hist = draw(jnp.uint32(k0), jnp.uint32(k1))
        if abs(corr(z[:, :-1], z[:, 1:])) >= 0.005:
            defects.add("neighbouring element")
        if abs(corr(z[:-1], z[1:])) >= 0.005:
            defects.add("neighbouring slot")
        if abs(corr(z, z_shot)) >= 0.005:
            defects.add("thermal vs shot")
        expected = float(hist.sum()) / 256
        if float(jnp.sum((hist - expected) ** 2) / expected) >= _CHI2_LIMIT:
            defects.add("top byte chi2")
    return defects


@pytest.mark.parametrize("rounds,sound", [
    pytest.param(emu_matmul._NOISE_ROUNDS, True, id="noise-rounds"),
    # negative controls: the same checks see reduced-round streams fail
    pytest.param(8, False, id="8-rounds"),
    pytest.param(6, False, id="6-rounds"),
    pytest.param(4, False, id="4-rounds"),
])
def test_noise_stream_statistics(monkeypatch, rounds, sound):
    """The kernel's reduced-round noise stream shows no correlation
    between neighbouring elements, neighbouring slots or the thermal and
    shot streams, and a flat top byte; fewer rounds fail these checks."""
    monkeypatch.setattr(emu_matmul, "_NOISE_ROUNDS", rounds)
    defects = _stream_defects(rounds)
    assert (not defects) if sound else defects, defects


# ---------------------------------------------------------------------------
# the emu_kernel seam: resolution, env override, session plumbing
# ---------------------------------------------------------------------------

def test_resolve_emu_kernel_specs():
    assert channel.resolve_emu_kernel("ref") == "ref"
    assert channel.resolve_emu_kernel("xla") == "xla"
    assert channel.resolve_emu_kernel("pallas") == "pallas"
    with pytest.raises(ValueError, match="unknown emu kernel"):
        channel.resolve_emu_kernel("cuda")


def test_resolve_emu_kernel_env(monkeypatch):
    monkeypatch.setenv("REPRO_EMU_KERNEL", "xla")
    assert channel.resolve_emu_kernel(None) == "xla"
    assert channel.resolve_emu_kernel("auto") == "xla"
    # explicit spec wins over the environment
    assert channel.resolve_emu_kernel("ref") == "ref"
    monkeypatch.setenv("REPRO_EMU_KERNEL", "")
    # empty string is "unset", not an unknown spec
    assert channel.resolve_emu_kernel(None) in ("ref", "pallas")


def test_emulated_matmul_kernel_seam():
    cfg = _quiet(n_buses=2)
    a = jax.random.normal(KEY, (5, 70), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (33, 70), jnp.float32)
    ref = channel.emulated_matmul(a, b, cfg, kernel="ref")
    out = channel.emulated_matmul(a, b, cfg, kernel="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_build_session_emu_kernel_requires_emu_backend():
    with pytest.raises(ValueError, match="requires backend='emu'"):
        api.build_session(arch="mnist_mlp", smoke=True, emu_kernel="xla")
    with pytest.raises(ValueError, match="unknown emu kernel"):
        api.build_session(arch="mnist_mlp", smoke=True, backend="emu",
                          hardware="emu_ideal", emu_kernel="bogus")


@pytest.mark.parametrize("algo", ["bp", "dfa", "dfa-layerwise"])
def test_session_fused_matches_ref_all_algorithms(algo):
    """One train step per algorithm on a noiseless emu device: the fused
    session must land on the same loss as the unfused one."""
    hw = _quiet(n_buses=2)
    losses = {}
    for kern in ("ref", "xla"):
        session = api.build_session(arch="mnist_mlp", algo=algo, smoke=True,
                                    backend="emu", hardware=hw,
                                    emu_kernel=kern, recalibrate_every=0,
                                    log_every=10 ** 9)
        key = jax.random.PRNGKey(0)
        batch = {
            "x": jax.random.normal(key, (8, session.model.in_dim)),
            "y": jax.random.randint(key, (8,), 0, session.model.n_classes),
        }
        _state, metrics = session.fit(lambda step: batch, total_steps=1,
                                      verbose=False)
        losses[kern] = float(metrics["loss"])
    assert losses["xla"] == pytest.approx(losses["ref"], rel=1e-4)


def test_trainer_fit_smoke_fused_drifting_device():
    """Two steps of the full drifting-device loop (noise + OU drift +
    in-situ recalibration) through the fused kernel: finite loss, carried
    hardware state."""
    session = api.build_session(arch="mnist_mlp", algo="dfa", smoke=True,
                                backend="emu", hardware="emu_onchip",
                                emu_kernel="xla", recalibrate_every=1,
                                log_every=10 ** 9)
    key = jax.random.PRNGKey(0)
    batch = {
        "x": jax.random.normal(key, (8, session.model.in_dim)),
        "y": jax.random.randint(key, (8,), 0, session.model.n_classes),
    }
    _state, metrics = session.fit(lambda step: batch, total_steps=2,
                                  verbose=False)
    assert np.isfinite(float(metrics["loss"]))


def test_backend_field_routes_kernel(monkeypatch):
    """EmulatedMRRBackend.emu_kernel reaches emulated_matmul: patching the
    fused entry point must intercept the projection."""
    calls = []
    real = emu_matmul.fused_bank_product

    def spy(*args, **kwargs):
        calls.append(kwargs.get("impl"))
        return real(*args, **kwargs)

    monkeypatch.setattr(emu_matmul, "fused_bank_product", spy)
    cfg = _quiet(n_buses=1)
    a = jax.random.normal(KEY, (3, 40), jnp.float32)
    b = jax.random.normal(jax.random.fold_in(KEY, 1), (21, 40), jnp.float32)
    backend = photonics.EmulatedMRRBackend(emu_kernel="xla")
    backend.matmul(a, b, cfg, key=None)
    assert calls == ["xla"]
