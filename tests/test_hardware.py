"""Device-level MRR emulation (repro.hardware): Lorentzian ring physics,
thermal crosstalk + compensation, OU resonance drift, in-situ calibration,
the "emu" PhotonicBackend, and the Trainer's carried hardware state."""

import dataclasses

import hypothesis
import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algos, api
from repro.core import photonics
from repro.hardware import calibrate, channel, drift, mrr

IDEAL = mrr.MRRConfig.ideal()


def _emu_ideal_cfg(**kw):
    return photonics.PhotonicConfig(noise_std=0.0, mrr=IDEAL, **kw)


# ---------------------------------------------------------------------------
# ring physics
# ---------------------------------------------------------------------------

def test_ring_weight_landmarks():
    """Lorentzian BPD transfer: -1 on resonance, 0 at one half-width,
    asymptotically +1, strictly monotone in |detuning|."""
    np.testing.assert_allclose(float(mrr.ring_weight(0.0, 1.0)), -1.0)
    np.testing.assert_allclose(float(mrr.ring_weight(1.0, 1.0)), 0.0, atol=1e-7)
    assert float(mrr.ring_weight(1e4, 1.0)) > 0.999999
    d = jnp.linspace(0.0, 50.0, 512)
    w = np.asarray(mrr.ring_weight(d, 1.3))
    assert np.all(np.diff(w) > 0)
    assert np.all((w >= -1.0) & (w < 1.0))


@hypothesis.given(w=st.floats(-1.0, 0.999), gamma=st.floats(0.1, 5.0))
@hypothesis.settings(max_examples=50, deadline=None)
def test_inscription_round_trip(w, gamma):
    """inscribe is the exact inverse of ring_weight on the reachable range."""
    cfg = dataclasses.replace(IDEAL, gamma=gamma)
    w2 = mrr.ring_weight(mrr.inscribe(jnp.float32(w), cfg), gamma)
    assert abs(float(w2) - w) < 1e-5  # f32 transport


def test_unreachable_weight_clips_to_ceiling():
    cfg = mrr.MRRConfig()  # delta_max = 100γ
    d = mrr.inscribe(jnp.float32(1.0), cfg)
    assert np.isfinite(float(d))
    w_back = float(mrr.ring_weight(d, cfg.gamma))
    assert abs(w_back - mrr.w_ceiling(cfg)) < 1e-6
    assert 1.0 - w_back < 3e-4  # ≥ ~12 bits of inscription range


def test_heater_dac_quantizes_commands():
    cfg = dataclasses.replace(IDEAL, heater_bits=6, delta_max=10.0)
    w = jax.random.uniform(jax.random.PRNGKey(0), (500,), minval=-1, maxval=0.9)
    d = np.asarray(calibrate.command_deltas(w, cfg))
    assert len(np.unique(d)) <= 2**6
    np.testing.assert_allclose(
        d, np.round(d / 10.0 * 63) / 63 * 10.0, rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# crosstalk
# ---------------------------------------------------------------------------

def test_crosstalk_perturbs_and_compensation_recovers():
    key = jax.random.PRNGKey(1)
    w = jax.random.uniform(key, (50, 20), minval=-0.95, maxval=0.95)
    xt = dataclasses.replace(IDEAL, crosstalk=0.01, compensate_crosstalk=False)
    xt_comp = dataclasses.replace(xt, compensate_crosstalk=True, ct_iters=3)

    def realized(cfg):
        d = calibrate.command_deltas(w, cfg, row_axis=-2, col_axis=-1)
        d = d + mrr.crosstalk_leak(d, cfg, row_axis=-2, col_axis=-1)
        return mrr.ring_weight(d, cfg.gamma)

    err_raw = float(jnp.abs(realized(xt) - w).max())
    err_comp = float(jnp.abs(realized(xt_comp) - w).max())
    assert err_raw > 1e-3  # the leak is a real perturbation
    assert err_comp < err_raw / 5  # Jacobi pre-inversion recovers it


def test_neighbor_sum_edges_are_zero_padded():
    x = jnp.ones((3, 4))
    n = np.asarray(mrr.neighbor_sum(x, row_axis=0, col_axis=1))
    assert n[0, 0] == 2.0 and n[1, 1] == 4.0 and n[0, 1] == 3.0


def test_grid_axes_infer_bare_and_tiled_layouts():
    """The documented bare (rows, cols) layout works with default axes all
    the way through the inscription path (crosstalk on)."""
    w = jax.random.uniform(jax.random.PRNGKey(8), (5, 4),
                           minval=-0.9, maxval=0.9)
    cfg = photonics.PhotonicConfig(mrr=mrr.MRRConfig())  # crosstalk != 0
    realized = channel.realized_weights(w, cfg)
    assert realized.shape == w.shape
    assert float(jnp.abs(realized - w).max()) < 0.05
    tiled = w.reshape(1, 5, 1, 4)
    np.testing.assert_allclose(
        np.asarray(channel.realized_weights(tiled, cfg))[0, :, 0, :],
        np.asarray(realized), rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# drift + calibration state
# ---------------------------------------------------------------------------

def test_ou_process_is_stationary():
    key = jax.random.PRNGKey(2)
    x = jnp.zeros((50, 20))
    for i in range(400):
        x = drift.ou_step(x, jax.random.fold_in(key, i), sigma=0.3, tau=40.0)
    assert 0.2 < float(jnp.std(x)) < 0.4  # relaxed to N(0, σ²)


def test_advance_recalibration_tracks_drift():
    cfg = photonics.PhotonicConfig(mrr=mrr.MRRConfig(
        drift_sigma=0.5, drift_tau=20.0, cal_noise=0.0))
    key = jax.random.PRNGKey(3)
    st_recal = drift.init_state(cfg)
    st_free = drift.init_state(cfg)
    for i in range(100):
        k = jax.random.fold_in(key, i)
        st_recal = calibrate.advance(st_recal, cfg, i, k, recalibrate_every=1)
        st_free = calibrate.advance(st_free, cfg, i, k, recalibrate_every=0)
    # same OU path in both; perfect every-step calibration zeroes the
    # residual while the free-running bank carries the full drift
    np.testing.assert_allclose(np.asarray(st_recal["drift"]),
                               np.asarray(st_free["drift"]), rtol=1e-6)
    assert float(jnp.abs(drift.residual(st_recal)).max()) < 1e-6
    assert float(jnp.std(drift.residual(st_free))) > 0.2


def test_active_state_context_scopes_the_drift():
    cfg = photonics.PhotonicConfig(mrr=mrr.MRRConfig())
    state = drift.init_state(cfg)
    state["drift"] = state["drift"] + 1.0
    key = jax.random.PRNGKey(4)
    a = jax.random.normal(key, (4, 10))
    b = jax.random.normal(jax.random.fold_in(key, 1), (8, 10))
    clean = channel.emulated_matmul(a, b, _emu_ideal_cfg())
    with drift.use_state(state):
        drifted = channel.emulated_matmul(a, b, _emu_ideal_cfg())
    after = channel.emulated_matmul(a, b, _emu_ideal_cfg())
    assert drift.active_state() is None
    assert float(jnp.abs(drifted - clean).max()) > 1e-3
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(after))


# ---------------------------------------------------------------------------
# the emu backend: registration + equivalence with ref
# ---------------------------------------------------------------------------

def test_emu_backend_registered_and_stateful():
    be = photonics.get_backend("emu")
    assert be.name == "emu"
    assert be.stateful_hardware
    assert not photonics.get_backend("ref").stateful_hardware
    for name in ("emu_ideal", "emu_offchip", "emu_onchip"):
        assert photonics.preset(name).mrr is not None


def test_emu_noise_statistics_match_ref():
    """Per-pass BPD noise accumulated over panels == the reference path's
    single draw, statistically (documented noise tolerance: 3%)."""
    cfg = photonics.PhotonicConfig(noise_std=0.098, mrr=IDEAL)
    key = jax.random.PRNGKey(6)
    a = jax.random.uniform(key, (512, 10), minval=-1, maxval=1)
    b = jax.random.uniform(jax.random.fold_in(key, 1), (800, 10),
                           minval=-1, maxval=1)
    out = channel.emulated_matmul(a, b, cfg, key=jax.random.fold_in(key, 2))
    err = np.asarray(out - a @ b.T)
    s = float(jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(b)))
    assert abs(err.std() / (0.098 * s) - 1.0) < 0.03


def test_emu_noise_accumulates_over_contraction_passes():
    cfg = photonics.PhotonicConfig(noise_std=0.1, mrr=IDEAL)
    key = jax.random.PRNGKey(7)
    a = jax.random.uniform(key, (256, 80), minval=-1, maxval=1)  # 4 passes
    b = jax.random.uniform(jax.random.fold_in(key, 1), (100, 80),
                           minval=-1, maxval=1)
    out = channel.emulated_matmul(a, b, cfg, key=jax.random.fold_in(key, 2))
    err = np.asarray(out - a @ b.T)
    s = float(jnp.max(jnp.abs(a)) * jnp.max(jnp.abs(b)))
    expect = photonics.noise_sigma_total(80, 1.0, 1.0, cfg) * s
    assert abs(err.std() / expect - 1.0) < 0.05


@pytest.mark.parametrize("algo", algos.list_algos())
def test_emu_equivalent_to_ref_for_every_algorithm(algo):
    """Satellite: zero drift/crosstalk emu == ref for every registered
    algorithm's value_and_grad (losses identical, grads to f32 tolerance)."""
    s_ref = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                              hardware="ideal", backend="ref", log_every=10**9)
    s_emu = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                              hardware=_emu_ideal_cfg(), backend="emu",
                              log_every=10**9)
    key = jax.random.PRNGKey(0)
    state = s_ref.init_state(key)
    batch = {"x": jax.random.normal(key, (16, 64)),
             "y": jax.random.randint(key, (16,), 0, 10)}
    (l_ref, _), g_ref = s_ref.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    (l_emu, _), g_emu = s_emu.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    np.testing.assert_allclose(float(l_ref), float(l_emu), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_emu)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# Trainer integration: carried hardware state
# ---------------------------------------------------------------------------

def _batch(model, key, n=16):
    return {"x": jax.random.normal(key, (n, model.in_dim)),
            "y": jax.random.randint(key, (n,), 0, model.n_classes)}


def test_fit_threads_and_advances_hardware_state():
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                hardware="emu_onchip", backend="emu",
                                recalibrate_every=2, log_every=10**9)
    cfg = session.config
    assert cfg.recalibrate_every == 2
    batch = _batch(session.model, jax.random.PRNGKey(0))
    init = session.init_state()
    assert set(init["hw"]) == {"drift", "cal"}
    # the paper's physical bank, one bus: (n_buses, rows, cols)
    assert init["hw"]["drift"].shape == (1, 50, 20)
    state, metrics = session.fit(lambda step: batch, total_steps=4,
                                 verbose=False)
    assert float(jnp.abs(state["hw"]["drift"]).max()) > 0.0
    assert np.isfinite(float(metrics["loss"]))
    assert metrics["hw_drift_rms"] > 0.0
    # recalibrated 2 steps ago at most: residual ≤ drift magnitude
    assert metrics["hw_residual_rms"] <= metrics["hw_drift_rms"] * 2.0


def test_default_session_enables_drift_and_recalibration_for_emu():
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                backend="emu", log_every=10**9)
    assert session.config.dfa.photonics.mrr is not None
    assert session.config.dfa.photonics.mrr.stateful
    assert session.config.recalibrate_every == 500


def test_non_stateful_backends_carry_no_hw_state():
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                hardware="emu_onchip", backend="ref",
                                log_every=10**9)
    assert "hw" not in session.init_state()
    assert session.config.recalibrate_every == 0


def test_fit_replay_is_deterministic_with_hardware_state():
    """(seed, step)-derived drift: two identical fits agree bit-for-bit —
    the restart-safety contract extends to the hardware state."""
    def fit_once():
        session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                    hardware="emu_onchip", backend="emu",
                                    recalibrate_every=2, log_every=10**9)
        batch = _batch(session.model, jax.random.PRNGKey(3))
        return session.fit(lambda step: batch, total_steps=3, verbose=False)

    s1, m1 = fit_once()
    s2, m2 = fit_once()
    np.testing.assert_array_equal(np.asarray(s1["hw"]["drift"]),
                                  np.asarray(s2["hw"]["drift"]))
    assert float(m1["loss"]) == float(m2["loss"])


# ---------------------------------------------------------------------------
# the drift-recovery study + BENCH_hardware schema
# ---------------------------------------------------------------------------

def test_drift_recovery_bench_schema(tmp_path):
    from benchmarks import drift_recovery

    rows = drift_recovery.run(steps=4, train_n=256, test_n=128, hidden=(16,))
    assert {r["variant"] for r in rows} == {
        "ref", "emu_static", "emu_drift", "emu_drift_recal"}
    path = drift_recovery.write_report(rows, str(tmp_path))
    assert path.endswith("BENCH_hardware.json")
    from repro.bench import load_bench

    report = load_bench(path)  # raises on schema drift
    assert "acc_emu_drift_recal" in report["metrics"]


@pytest.mark.slow
def test_emu_dfa_trains_mnist_within_2pct_of_ref():
    """Acceptance: build_session(algo="dfa", backend="emu") — default
    drift + in-situ calibration — within 2 accuracy points of "ref"."""
    from repro.data import mnist, pipeline
    from repro.models.mlp import MLPClassifier

    data = mnist.load((8192, 2048), seed=0)
    xtr, ytr = data["train"]
    xte, yte = data["test"]
    acc = {}
    for backend in ("ref", "emu"):
        pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
        session = api.build_session(arch=MLPClassifier(hidden=(128, 128)),
                                    algo="dfa", backend=backend,
                                    log_every=10**9)
        state, _ = session.fit(pipe.batch, total_steps=512, verbose=False)
        ev = session.evaluate(state, pipe.eval_batches(xte, yte, 256))
        acc[backend] = 100 * ev["accuracy"]
    assert abs(acc["emu"] - acc["ref"]) < 2.0, acc
