"""Pieces the plain references and the comparison share.  Nothing here
imports the program.

* ``fold_name`` — a string folded into a PRNG key as the program's
  initialisers document it (the first four bytes of its SHA-256, little
  endian), so a reference draws the same random weights from the same
  seed by its own code.
* ``sgdm_reference`` — three steps of SGD with momentum (lr 0.01,
  momentum 0.9, no weight decay: the paper's optimizer and the session's
  default) over a ``grads_fn``, held against the program's.
* ``flat`` / ``leaf_norms`` / ``project`` — a pytree as ``{"a/b/c":
  leaf}``, the float32 norm of each leaf, and the program's leaves
  projected on the reference's and their distance from them, computed
  on the device.
* ``train_gaps`` — the numbers a training cell compares.
"""

from __future__ import annotations

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

LR = 0.01
MOMENTUM = 0.9


def fold_name(key, name: str):
    n = int.from_bytes(hashlib.sha256(name.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, n)


def flat(tree) -> dict:
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)] = leaf
    return out


@jax.jit
def _norms(tree):
    return jax.tree_util.tree_map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def leaf_norms(tree) -> dict:
    return {k: float(v) for k, v in flat(jax.device_get(_norms(tree))).items()}


@jax.jit
def _dots(a, b):
    """(⟨a, b⟩, ⟨b, b⟩, ‖a − b‖², ‖b‖²) of one leaf, in float32; the two
    squared norms elementwise, with no matrix unit pass."""
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return jnp.vdot(a, b), jnp.vdot(b, b), jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))


def project(prog: dict, ref_tree) -> tuple[dict, dict]:
    """Per leaf, the program's leaf against the reference's: ⟨p, r⟩ /
    ⟨r, r⟩ (1 where they agree; zero-mean noise in ``p`` leaves it at 1,
    a missing or scaled update does not), and ‖p − r‖ / ‖r‖ (0 where they
    agree; the emulated bank's noise reads as its energy against the
    signal's).  ``prog`` holds host arrays by leaf path; each goes to the
    device alone."""
    ratio, dev = {}, {}
    for k, r in flat(ref_tree).items():
        pr, rr, dd, rr2 = (float(x) for x in jax.device_get(_dots(jnp.asarray(prog[k]), r)))
        ratio[k] = pr / rr if rr > 0 else float("nan")
        dev[k] = (dd / rr2) ** 0.5 if rr2 > 0 else float("nan")
    return ratio, dev


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _sgdm(params, mom, grads):
    mom = jax.tree_util.tree_map(
        lambda m, g: MOMENTUM * m + g.astype(m.dtype), mom, grads)
    params = jax.tree_util.tree_map(
        lambda p, m: (p.astype(jnp.float32) - LR * m.astype(jnp.float32)).astype(p.dtype),
        params, mom)
    return params, mom


def sgdm_reference(params, grads_fn, batches, prog: dict, steps: int = 3) -> dict:
    """Run ``steps`` of SGD with momentum with ``grads_fn(params, batch)
    -> (loss, grads)`` and hold the program's first gradient and change
    of the parameters (``prog["grad1"]``, ``prog["change"]``: host arrays
    by leaf path) against the reference's.  -> {"losses": [L1..Ln],
    "grad1_norm": {leaf: ‖g1‖}, "grad1": {leaf: ratio}, "grad1_dev":
    {leaf: ‖p − r‖ / ‖r‖}, "change": {leaf: ratio}}."""
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    out = {"losses": []}
    for s in range(steps):
        loss, grads = grads_fn(params, batches[s])
        out["losses"].append(float(loss))
        if s == 0:
            out["grad1_norm"] = leaf_norms(grads)
            out["grad1"], out["grad1_dev"] = project(prog["grad1"], grads)
        params, mom = _sgdm(params, mom, grads)
        del grads
    del mom
    change = jax.tree_util.tree_map(jnp.subtract, params, p0)
    del params, p0
    out["change"], _ = project(prog["change"], change)
    return out


def train_gaps(prog: dict, ref: dict, centers: dict) -> dict:
    """The numbers a training cell compares:

    * ``loss_gap``: the largest relative gap of the first steps' losses;
    * ``grad1_gap``: the worst leaf's |ratio − 1| of the first gradient;
    * ``change_gap``: the worst leaf's |ratio − 1| of the parameters'
      change over the steps;
    * ``grad1_noise_gap``: how far d, the median leaf's ‖g₁ program −
      g₁ reference‖ / ‖g₁ reference‖, lies from c (``centers``), what
      the configuration's hardware gives: |ln(d / c)| where it adds
      noise, d itself where it adds none.  The two ratios above cancel
      the emulated bank's zero-mean noise and drift; d is their energy
      against the signal's.  A program that draws no noise reads d near
      0, one that draws too much or too little moves d off c by a
      factor, and how far the signal lies above a fixed noise floor
      changes from seed to seed by a factor too, hence the logarithm.
      ``grad1_noise`` (d itself) is printed beside it, with no limit.

    Every number is taken over the leaves whose reference gradient is at
    least a thousandth of the median leaf's: a gradient nought to
    rounding moves a leaf by round-off alone."""
    norms = ref["grad1_norm"]
    med = float(np.median(list(norms.values())))
    moving = [k for k, v in norms.items() if v >= 1e-3 * med]
    d = float(np.median([ref["grad1_dev"][k] for k in moving]))
    c = centers.get("grad1_noise", 0.0)
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])),
        "grad1_gap": max(abs(ref["grad1"][k] - 1.0) for k in moving),
        "change_gap": max(abs(ref["change"][k] - 1.0) for k in moving),
        "grad1_noise_gap": (abs(math.log(d / c)) if d > 0 else math.inf) if c > 0 else d,
        "grad1_noise": d,
    }
