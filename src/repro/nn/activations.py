"""Activation functions and their derivatives.

The DFA gradient (paper Eq. 1) multiplies the projected error by g'(a) — on
the photonic chip the per-row TIA gain.  Here g'(a) enters through each
block's local vjp (algos/dfa.py), which differentiates the activation in
place.  For ReLU it is binary, exactly as the paper notes ("the elements in
the vector g'(a) are binary (0 or 1) when the ReLU function is used").
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def relu(x):
    return jnp.maximum(x, 0)


def relu_deriv(a):
    return (a > 0).astype(a.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def gelu_deriv(a):
    # d/da of tanh-approximate gelu
    c = jnp.sqrt(2.0 / jnp.pi).astype(a.dtype)
    u = c * (a + 0.044715 * a**3)
    t = jnp.tanh(u)
    du = c * (1 + 3 * 0.044715 * a**2)
    return 0.5 * (1 + t) + 0.5 * a * (1 - t**2) * du


def silu(x):
    return x * jax.nn.sigmoid(x)


def silu_deriv(a):
    s = jax.nn.sigmoid(a)
    return s * (1 + a * (1 - s))


def tanh(x):
    return jnp.tanh(x)


def tanh_deriv(a):
    return 1 - jnp.tanh(a) ** 2


def identity(x):
    return x


def identity_deriv(a):
    return jnp.ones_like(a)


ACTIVATIONS = {
    "relu": (relu, relu_deriv),
    "gelu": (gelu, gelu_deriv),
    "silu": (silu, silu_deriv),
    "tanh": (tanh, tanh_deriv),
    "identity": (identity, identity_deriv),
}


def get(name: str):
    """Return (g, g') for a named activation."""
    return ACTIVATIONS[name]
