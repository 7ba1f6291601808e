"""Plain reference of DFA training of the paper's 784-800-800-10 MLP.

Independent of the program: it imports nothing of ``repro`` and takes
nothing the program made.  Weights and feedback matrices come from the
seed by the initialisers the program documents.  Paper Eq. 1: the error
e = ∂L/∂logits = (softmax − onehot)/batch; the output layer gets its exact
gradient; hidden layer k gets δ_k = (e·B_kᵀ) ⊙ relu'(a_k) and
ΔW_k = h_{k−1}ᵀ δ_k, with the projection e·B_kᵀ exact and noise-free.
Three steps of SGD with momentum; matmuls at ``highest`` precision.
"""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import refkit  # noqa: E402


def _dims(c):
    return (c["input_dim"], *c["hidden_sizes"]), c["num_classes"]


@functools.partial(jax.jit, static_argnames=("dims", "n_cls", "dtype"))
def _init(root, dims, n_cls, dtype):
    fn = refkit.fold_name

    def linear(key, i, o):
        w = jax.random.normal(fn(key, "w"), (i, o)) * (1.0 / np.sqrt(i))
        return {"w": w, "b": jnp.zeros((o,))}

    params = {"embed": {}}
    for k in range(len(dims) - 1):
        p = linear(fn(root, f"h{k}"), dims[k], dims[k + 1])
        params[f"h{k}"] = jax.tree_util.tree_map(lambda x: x[None], p)
    params["head"] = linear(fn(root, "head"), dims[-1], n_cls)
    fk = fn(root, "feedback")
    fb = {}
    for k in range(len(dims) - 1):
        d_out = dims[k + 1]
        key = jax.random.split(fn(fn(fk, f"h{k}"), "layers"), 1)[0]
        fb[f"h{k}"] = jax.random.normal(key, (d_out, n_cls)) * (1.0 / jnp.sqrt(d_out))
    cast = lambda t: jax.tree_util.tree_map(lambda x: x.astype(dtype), t)  # noqa: E731
    return cast(params), cast(fb)


@functools.partial(jax.jit, static_argnames=("n_hidden",))
def _grads(params, fb, x, y, n_hidden):
    h = x.astype(params["h0"]["w"].dtype)
    inputs, pre = [], []
    for k in range(n_hidden):
        p = params[f"h{k}"]
        inputs.append(h)
        a = h @ p["w"][0] + p["b"][0]
        pre.append(a)
        h = jnp.maximum(a, 0)
    head = params["head"]
    logits = (h @ head["w"] + head["b"]).astype(jnp.float32)
    n = x.shape[0]
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, y[:, None], -1)[:, 0]
    loss = jnp.mean(nll)
    e = (jax.nn.softmax(logits, -1) - jax.nn.one_hot(y, logits.shape[-1])) / n
    e = e.astype(h.dtype)
    grads = {"embed": {}, "head": {"w": h.T @ e, "b": jnp.sum(e, 0)}}
    for k in range(n_hidden):
        delta = (e @ fb[f"h{k}"].T) * (pre[k] > 0).astype(h.dtype)
        grads[f"h{k}"] = {"w": (inputs[k].T @ delta)[None],
                          "b": jnp.sum(delta, 0)[None]}
    return loss, grads


def train_reference(config: dict, batches: list, seed: int, prog: dict, *,
                    dtype: str = "float32", steps: int = 3) -> dict:
    dims, n_cls = _dims(config)
    dt = jnp.dtype(dtype)
    with jax.default_matmul_precision("highest"):
        params, fb = _init(jax.random.PRNGKey(seed), dims, n_cls, dt)

        def grads_fn(p, batch):
            return _grads(p, fb, jnp.asarray(batch["x"]),
                          jnp.asarray(batch["y"]), len(dims) - 1)

        out = refkit.sgdm_reference(params, grads_fn, batches, prog, steps)
    return out
