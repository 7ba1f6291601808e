"""DFA engine correctness: exact Eq. 1 reproduction, exact head grads,
alignment diagnostics, error compression."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algos import dfa
from repro.algos.bp import bp_value_and_grad
from repro.core import photonics
from repro.core.feedback import FeedbackConfig, make_feedback
from repro.models.mlp import MLPClassifier


@pytest.fixture(scope="module")
def setup():
    model = MLPClassifier(in_dim=20, hidden=(32, 24), n_classes=5)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    cfg = dfa.DFAConfig()
    fb = dfa.init_feedback(model, jax.random.PRNGKey(7), cfg)
    batch = {
        "x": jax.random.normal(key, (16, 20)),
        "y": jax.random.randint(key, (16,), 0, 5),
    }
    return model, params, cfg, fb, batch


def test_dfa_matches_paper_eq1(setup):
    """Engine gradients == hand-derived δ(k) = B(k)e ⊙ g'(a(k)) (Eq. 1)."""
    model, params, cfg, fb, batch = setup
    (loss, _), grads = dfa.value_and_grad(model, cfg)(
        params, fb, batch, jax.random.PRNGKey(1))

    W1, b1 = params["h0"]["w"][0], params["h0"]["b"][0]
    W2, b2 = params["h1"]["w"][0], params["h1"]["b"][0]
    Wo, bo = params["head"]["w"], params["head"]["b"]
    x = batch["x"]
    a1 = x @ W1 + b1
    h1 = jnp.maximum(a1, 0)
    a2 = h1 @ W2 + b2
    h2 = jnp.maximum(a2, 0)
    p = jax.nn.softmax(h2 @ Wo + bo)
    e = (p - jax.nn.one_hot(batch["y"], 5)) / x.shape[0]
    d1 = (e @ fb["h0"][0].T) * (a1 > 0)
    d2 = (e @ fb["h1"][0].T) * (a2 > 0)

    np.testing.assert_allclose(grads["h0"]["w"][0], x.T @ d1, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads["h0"]["b"][0], d1.sum(0), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads["h1"]["w"][0], h1.T @ d2, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads["h1"]["b"][0], d2.sum(0), rtol=1e-5, atol=1e-6)
    # output layer: exact update with e (paper: "W(l) is updated using e")
    np.testing.assert_allclose(grads["head"]["w"], h2.T @ e, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads["head"]["b"], e.sum(0), rtol=1e-5, atol=1e-6)


def test_head_gradient_exactly_matches_backprop(setup):
    model, params, cfg, fb, batch = setup
    (_, _), dfa_g = dfa.value_and_grad(model, cfg)(params, fb, batch, jax.random.PRNGKey(1))
    (_, _), bp_g = bp_value_and_grad(model)(params, fb, batch, None)
    align = dfa.grad_alignment(dfa_g, bp_g)
    np.testing.assert_allclose(float(align["head"]), 1.0, atol=1e-5)


def test_loss_value_identical_dfa_vs_bp(setup):
    model, params, cfg, fb, batch = setup
    (ld, _), _ = dfa.value_and_grad(model, cfg)(params, fb, batch, jax.random.PRNGKey(1))
    (lb, _), _ = bp_value_and_grad(model)(params, fb, batch, None)
    np.testing.assert_allclose(float(ld), float(lb), rtol=1e-6)


def test_photonic_noise_perturbs_hidden_but_not_head(setup):
    model, params, _, fb, batch = setup
    noisy = dfa.DFAConfig(photonics=photonics.preset("onchip_bpd"))
    clean = dfa.DFAConfig()
    (_, _), gn = dfa.value_and_grad(model, noisy)(params, fb, batch, jax.random.PRNGKey(2))
    (_, _), gc = dfa.value_and_grad(model, clean)(params, fb, batch, jax.random.PRNGKey(2))
    assert np.abs(np.asarray(gn["h0"]["w"] - gc["h0"]["w"])).max() > 1e-4
    # head path is digital (exact) in the architecture
    np.testing.assert_allclose(gn["head"]["w"], gc["head"]["w"], rtol=1e-6)


def test_alignment_improves_with_training(setup):
    """Feedback-alignment signature: cos(DFA, BP) of hidden layers grows
    during early training (align-then-memorise, paper ref [29])."""
    model, params, cfg, fb, batch = setup
    vg = jax.jit(dfa.value_and_grad(model, cfg))
    bp = jax.jit(bp_value_and_grad(model))

    def cos_now(p):
        (_, _), gd = vg(p, fb, batch, jax.random.PRNGKey(0))
        (_, _), gb = bp(p, fb, batch, None)
        a = dfa.grad_alignment(gd, gb)
        return float(a["h1"])

    before = cos_now(params)
    p = params
    for i in range(60):
        (_, _), g = vg(p, fb, batch, jax.random.PRNGKey(i))
        p = jax.tree_util.tree_map(lambda w, gw: w - 0.05 * gw, p, g)
    after = cos_now(p)
    assert after > before
    assert after > 0.05  # positively aligned (random would be ~0 ± 1/√n)


def test_error_compression_modes():
    e = jax.random.normal(jax.random.PRNGKey(0), (64, 10))
    t = dfa.compress_error(e, "ternary")
    vals = np.unique(np.round(np.asarray(jnp.abs(t)), 6))
    assert len(vals) <= 2  # {0, scale}
    q = dfa.compress_error(e, "int8")
    assert np.abs(np.asarray(q - e)).max() < np.abs(np.asarray(e)).max() / 64
    np.testing.assert_array_equal(np.asarray(dfa.compress_error(e, "none")), np.asarray(e))


def test_ternary_error_still_trains(setup):
    """Paper ref [48]: ternarised error gives competitive training signal."""
    model, params, _, fb, batch = setup
    cfg = dfa.DFAConfig(error_compress="ternary")
    vg = jax.jit(dfa.value_and_grad(model, cfg))
    p = params
    (l0, _), _ = vg(p, fb, batch, jax.random.PRNGKey(0))
    for i in range(80):
        (_, _), g = vg(p, fb, batch, jax.random.PRNGKey(i))
        p = jax.tree_util.tree_map(lambda w, gw: w - 0.05 * gw, p, g)
    (l1, _), _ = vg(p, fb, batch, jax.random.PRNGKey(99))
    assert float(l1) < float(l0) * 0.7


def test_feedback_shapes_and_scaling():
    cfg = FeedbackConfig()
    b = make_feedback(jax.random.PRNGKey(0), 4, 256, 32, cfg)
    assert b.shape == (4, 256, 32)
    # default scale 1/sqrt(d_out): ||B e|| ≈ ||e||
    e = jax.random.normal(jax.random.PRNGKey(1), (32,))
    ratio = float(jnp.linalg.norm(b[0] @ e) / jnp.linalg.norm(e))
    assert 0.5 < ratio < 2.0
    shared = make_feedback(jax.random.PRNGKey(0), 4, 256, 32, FeedbackConfig(shared=True))
    assert shared.shape == (1, 256, 32)
    tern = make_feedback(jax.random.PRNGKey(0), 1, 64, 16, FeedbackConfig(ternary=True))
    assert set(np.unique(np.sign(np.asarray(tern)))) <= {-1.0, 0.0, 1.0}
