"""Paper §6 claim: "the DFA algorithm is particularly well suited for
implementations with analog hardware as the gradient vector is calculated by
propagating the error through fixed random feedback connections directly
from the output layer to each hidden layer, which is advantageous as noise
does not accumulate between layers — unlike the backpropagation algorithm,
where the error is back-propagated layer by layer."

Test: per-layer gradient SNR under analog noise is depth-INDEPENDENT for
DFA (each layer gets one noisy B(k)e product), whereas a noisy chained
backward accumulates noise with depth.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.algos import dfa
from repro.core import photonics
from repro.models.mlp import MLPClassifier

DEPTH = 6


def _grad_snr_per_layer(noise_std: float, n_trials: int = 8):
    """SNR of DFA hidden-layer grads vs the noiseless DFA grads."""
    model = MLPClassifier(in_dim=16, hidden=(32,) * DEPTH, n_classes=5)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    clean_cfg = dfa.DFAConfig()
    fb = dfa.init_feedback(model, key, clean_cfg)
    batch = {"x": jax.random.normal(key, (32, 16)),
             "y": jax.random.randint(key, (32,), 0, 5)}
    (_, _), g_clean = dfa.value_and_grad(model, clean_cfg)(params, fb, batch, key)

    noisy_cfg = dfa.DFAConfig(
        photonics=photonics.PhotonicConfig(noise_std=noise_std))
    vg = jax.jit(dfa.value_and_grad(model, noisy_cfg))
    err_power = {f"h{i}": 0.0 for i in range(DEPTH)}
    sig_power = {f"h{i}": float(jnp.sum(jnp.square(g_clean[f"h{i}"]["w"])))
                 for i in range(DEPTH)}
    for t in range(n_trials):
        (_, _), g = vg(params, fb, batch, jax.random.PRNGKey(100 + t))
        for i in range(DEPTH):
            d = g[f"h{i}"]["w"] - g_clean[f"h{i}"]["w"]
            err_power[f"h{i}"] += float(jnp.sum(jnp.square(d))) / n_trials
    return [sig_power[f"h{i}"] / max(err_power[f"h{i}"], 1e-30)
            for i in range(DEPTH)]


def test_dfa_gradient_snr_depth_independent():
    snrs = _grad_snr_per_layer(noise_std=0.098)
    # exclude the first layer (different fan-in) and compare the rest:
    # depth-independence ⇒ max/min SNR ratio stays O(1) across 5 layers
    rest = snrs[1:]
    ratio = max(rest) / min(rest)
    assert ratio < 8.0, f"SNR varies {ratio:.1f}x across depth: {snrs}"
    # and every layer retains usable signal
    assert min(snrs) > 0.5


def test_chained_noise_accumulates_with_depth():
    """Contrast case: inject the same per-product noise into a CHAINED
    (backprop-style) error propagation — SNR degrades with depth."""
    key = jax.random.PRNGKey(1)
    d, depth = 32, DEPTH
    ws = [jax.random.normal(jax.random.fold_in(key, i), (d, d)) / np.sqrt(d)
          for i in range(depth)]
    e0 = jax.random.normal(jax.random.fold_in(key, 99), (d,))

    def chain(noise_key, sigma):
        outs = []
        e = e0
        for i, w in enumerate(ws):
            e = w @ e
            e = e + sigma * float(jnp.max(jnp.abs(e))) * jax.random.normal(
                jax.random.fold_in(noise_key, i), e.shape)
            outs.append(e)
        return outs

    clean = chain(jax.random.PRNGKey(0), 0.0)
    snrs = []
    for layer in range(depth):
        sig = float(jnp.sum(jnp.square(clean[layer])))
        errp = 0.0
        for t in range(8):
            noisy = chain(jax.random.PRNGKey(10 + t), 0.098)
            errp += float(jnp.sum(jnp.square(noisy[layer] - clean[layer]))) / 8
        snrs.append(sig / max(errp, 1e-30))
    # noise accumulates: deepest layer is markedly worse than the first
    assert snrs[-1] < snrs[0] / 2, snrs
