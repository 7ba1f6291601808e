"""The pluggable API surface: algorithm registry round-trips, the
repro.api facade, and the PhotonicBackend execution seam."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algos, api
from repro.algos.dfa import grad_alignment
from repro.core import photonics
from repro.hardware import mrr


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_exposes_builtin_algorithms():
    names = algos.list_algos()
    for required in ("bp", "dfa", "dfa-layerwise"):
        assert required in names


def test_registry_round_trip():
    for name in algos.list_algos():
        algo = algos.get(name)
        assert isinstance(algo, algos.Algorithm)
        assert algo.name == name


def test_registry_unknown_name_raises_keyerror():
    with pytest.raises(KeyError):
        algos.get("equilibrium-propagation")


def test_register_custom_algorithm_and_session():
    class Custom(algos.Algorithm):
        name = "test-custom-zero"

        def value_and_grad(self, model, cfg):
            def fn(params, fb, batch, rng):
                loss, metrics = model.loss(params, batch)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
                return (loss, {**metrics, "loss": loss}), zeros

            return fn

    algos.register(Custom())
    try:
        assert "test-custom-zero" in algos.list_algos()
        session = api.build_session(arch="mnist_mlp", smoke=True,
                                    algo="test-custom-zero")
        state = session.init_state()
        batch = {"x": jnp.zeros((4, 64)), "y": jnp.zeros((4,), jnp.int32)}
        state2, metrics = session.step(state, batch)
        assert np.isfinite(float(metrics["loss"]))
    finally:
        algos.base._REGISTRY.pop("test-custom-zero", None)


# ---------------------------------------------------------------------------
# bp vs dfa through the facade (ideal hardware)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mlp_setup():
    session_dfa = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                    hardware="ideal")
    session_bp = api.build_session(arch="mnist_mlp", smoke=True, algo="bp",
                                   hardware="ideal")
    key = jax.random.PRNGKey(0)
    state = session_dfa.init_state(key)
    batch = {"x": jax.random.normal(key, (16, 64)),
             "y": jax.random.randint(key, (16,), 0, 10)}
    return session_dfa, session_bp, state, batch


def test_dfa_vs_bp_loss_identical_under_ideal(mlp_setup):
    s_dfa, s_bp, state, batch = mlp_setup
    (ld, _), _ = s_dfa.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    (lb, _), _ = s_bp.value_and_grad()(
        state["params"], state["fb"], batch, None)
    np.testing.assert_allclose(float(ld), float(lb), rtol=1e-6)


def test_dfa_vs_bp_head_gradients_agree_under_ideal(mlp_setup):
    """Head grads are exact in DFA — cosine(head) == 1 vs backprop."""
    s_dfa, s_bp, state, batch = mlp_setup
    (_, _), gd = s_dfa.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    (_, _), gb = s_bp.value_and_grad()(
        state["params"], state["fb"], batch, None)
    align = grad_alignment(gd, gb)
    np.testing.assert_allclose(float(align["head"]), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(gd["head"]["w"]), np.asarray(gb["head"]["w"]),
        rtol=1e-5, atol=1e-6)


def test_layerwise_differs_from_dfa_but_trains_head_exactly(mlp_setup):
    s_dfa, _, state, batch = mlp_setup
    s_lw = api.build_session(arch="mnist_mlp", smoke=True,
                             algo="dfa-layerwise", hardware="ideal")
    (_, _), gd = s_dfa.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    (_, _), gl = s_lw.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(1))
    # same head path (exact), different hidden-layer credit assignment
    np.testing.assert_allclose(np.asarray(gl["head"]["w"]),
                               np.asarray(gd["head"]["w"]), rtol=1e-6)
    assert np.abs(np.asarray(gl["h0"]["w"] - gd["h0"]["w"])).max() > 1e-6


# ---------------------------------------------------------------------------
# PhotonicBackend seam
# ---------------------------------------------------------------------------

def test_backend_registry_and_unknown_name():
    assert photonics.get_backend("ref").name == "ref"
    assert photonics.get_backend("pallas").name == "pallas"
    inst = photonics.PallasBackend(interpret=True)
    assert photonics.get_backend(inst) is inst
    with pytest.raises(KeyError):
        photonics.get_backend("interferometer")


# e's shape -> B's rows: sub-block, leading batch dims, ragged contraction
# (33 over 20-column bank panels), several padded row and column blocks
PROJECT_SHAPES = [((4, 8), 16), ((5, 7, 10), 64), ((3, 7, 33), 61),
                  ((200, 30), 257)]


@pytest.mark.parametrize("shape,rows", PROJECT_SHAPES)
@pytest.mark.parametrize(
    "hardware", [dict(), dict(weight_bits=6, input_bits=8), dict(enabled=False)],
    ids=["noiseless", "quantized", "digital"])
@pytest.mark.parametrize("backend", ["pallas", "emu"])
def test_photonic_project_backends_agree_with_ref(backend, hardware, shape,
                                                  rows):
    """Noiseless, quantised (normalise/fake-quant/rescale is shared, so it
    is identical through every backend) and photonics off (the plain
    product); the emu bank on the ideal device."""
    cfg = photonics.PhotonicConfig(noise_std=0.0, mrr=mrr.MRRConfig.ideal(),
                                   **hardware)
    be = photonics.PallasBackend(interpret=True) if backend == "pallas" else backend
    key = jax.random.PRNGKey(rows)
    e = jax.random.normal(key, shape)
    b = jax.random.normal(jax.random.fold_in(key, 1), (rows, shape[-1]))
    out_ref = photonics.photonic_project(e, b, cfg, backend="ref")
    out = photonics.photonic_project(e, b, cfg, backend=be)
    assert out_ref.shape == out.shape == shape[:-1] + (rows,)
    np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)


def test_dfa_engine_through_explicit_backend():
    """cfg.backend threads through the engine to the projection."""
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                hardware="offchip_bpd", backend="ref")
    key = jax.random.PRNGKey(0)
    state = session.init_state(key)
    batch = {"x": jax.random.normal(key, (8, 64)),
             "y": jax.random.randint(key, (8,), 0, 10)}
    (loss, _), grads = session.value_and_grad()(
        state["params"], state["fb"], batch, jax.random.PRNGKey(2))
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))
