"""Sharding policy: mesh axes, parameter/batch placement rules, and the
activation annotations the models sprinkle through their forward passes.

Mesh axes (launch/mesh.py):

* ``data``  — FSDP axis: parameters are sharded along their first dim
  (ZeRO-3), gathered per-layer inside the scan by ``unshard_fsdp``.
* ``model`` — tensor-parallel axis: matmul output dims, embed vocab,
  expert dim, and the DFA tape's feature dim.
* ``pod``   — optional leading DCI axis (multi-pod); joins ``data`` for
  batch sharding only.

Single-host contract: every helper here is a **no-op without an active
mesh** — ``annotate``/``unshard_fsdp`` return their argument unchanged
(identity, not a copy) so the small-scale CPU paths trace exactly the same
HLO they did before sharding existed.  A mesh is activated with
``use_mesh(mesh)`` (a context manager), which is what the dry-run and the
subprocess tests do around ``jit``/``lower``.
"""

from __future__ import annotations

import contextlib

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.utils.tree import path_map

MODEL = "model"
FSDP = "data"
POD = "pod"

# ---------------------------------------------------------------------------
# active mesh
# ---------------------------------------------------------------------------

_ACTIVE: list = []


@contextlib.contextmanager
def use_mesh(mesh):
    """Activate ``mesh`` for annotate/unshard within the block."""
    _ACTIVE.append(mesh)
    try:
        yield mesh
    finally:
        _ACTIVE.pop()


def current_mesh():
    return _ACTIVE[-1] if _ACTIVE else None


def batch_axes(mesh) -> tuple:
    """Mesh axes the batch dim is sharded over (pod joins data if present)."""
    return (POD, FSDP) if POD in mesh.shape else (FSDP,)


# ---------------------------------------------------------------------------
# parameter placement rules
# ---------------------------------------------------------------------------
# Each rule is (substring, PartitionSpec); first match wins, "" is the
# catch-all.  Specs are written for the *trailing* dims of a leaf —
# ``_fit_spec`` right-aligns them (stacked layer axes get leading None) and
# the divisibility fallback drops any axis that does not divide the dim.

PARAM_RULES: tuple = (
    ("experts", P(MODEL, FSDP, None)),   # (E, d_in, d_out): expert parallel
    ("embed", P(MODEL, FSDP)),           # (V, d): vocab on model
    ("norm", P()),                       # tiny scale vectors: replicate
    ("/ln", P()),
    ("ln1", P()), ("ln2", P()), ("ln3", P()), ("ln_enc", P()),
    ("", P(FSDP, MODEL)),                # default 2D weight (d_in, d_out)
)

# Feedback matrices are (L, d_inject, d_tap): shard the injection dim on
# model (it is the photonic projection's output dim), replicate d_tap.
FEEDBACK_RULES: tuple = (
    ("", P(None, MODEL, None)),
)


def spec_for_path(path: str, rules: tuple = PARAM_RULES):
    """-> (PartitionSpec, rule_substring) for a "a/b/c" parameter path."""
    for pat, spec in rules:
        if pat in path:
            return spec, pat
    return P(), ""


def _fit_spec(spec, ndim: int):
    """Right-align ``spec`` to an ndim-rank leaf: pad leading None for
    stacked layer axes, drop leading entries when the leaf has fewer dims
    (a (d_out,) bias keeps the weight spec's trailing MODEL entry)."""
    entries = tuple(spec)
    if len(entries) > ndim:
        entries = entries[len(entries) - ndim:]
    elif len(entries) < ndim:
        entries = (None,) * (ndim - len(entries)) + entries
    return P(*entries)


def _axis_size(mesh, entry) -> int:
    axes = entry if isinstance(entry, tuple) else (entry,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def _divisible(spec, shape, mesh):
    """Drop spec entries whose mesh-axis product does not divide the dim —
    the odd-vocab fallback (73448 is not 16-way shardable)."""
    out = []
    for dim, entry in zip(shape, tuple(spec)):
        if entry is None:
            out.append(None)
        elif any(a not in mesh.shape for a in (entry if isinstance(entry, tuple) else (entry,))):
            out.append(None)
        elif dim % _axis_size(mesh, entry) != 0:
            out.append(None)
        else:
            out.append(entry)
    return P(*out)


def make_param_shardings(mesh, tree, rules: tuple = PARAM_RULES):
    """NamedSharding pytree for a parameter pytree (arrays or SDS leaves)."""

    def assign(path, leaf):
        spec, _ = spec_for_path(path, rules)
        spec = _fit_spec(spec, len(leaf.shape))
        spec = _divisible(spec, leaf.shape, mesh)
        return NamedSharding(mesh, spec)

    return path_map(assign, tree)


def make_batch_shardings(mesh, tree):
    """Batch inputs: dim 0 over (pod, data) when divisible, rest replicated."""
    b = batch_axes(mesh)
    n = 1
    for a in b:
        n *= mesh.shape[a]

    def assign(path, leaf):
        del path
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and leaf.shape[0] % n == 0:
            spec[0] = b if len(b) > 1 else b[0]
        return NamedSharding(mesh, P(*spec))

    return path_map(assign, tree)


def replicated(mesh):
    return NamedSharding(mesh, P())


def replicate(mesh, tree):
    """device_put every leaf of ``tree`` fully replicated over ``mesh`` —
    the parameter/optimizer placement for pure data-parallel training."""
    return jax.device_put(tree, replicated(mesh))


def put_batch(mesh, batch):
    """Host→device transfer of one batch, dim 0 split over the data axes
    (``make_batch_shardings`` falls back to replication when the batch size
    does not divide the axis).  ``jax.device_put`` dispatch is async, so the
    Trainer's prefetcher uses this to overlap the next batch's transfer with
    the current step's compute."""
    return jax.device_put(batch, make_batch_shardings(mesh, batch))


# ---------------------------------------------------------------------------
# activation annotations
# ---------------------------------------------------------------------------
# Named constraint points used by the models.  _B marks the batch dim
# (bound to batch_axes(mesh) at call time).

_B = "__batch__"

ACT_RULES: dict[str, tuple] = {
    "act_btd": (_B, None, None),          # residual stream (B, S, D)
    "tape_lbsd": (None, _B, None, MODEL), # DFA tape: model-sharded feature
    "logits": (_B, None, MODEL),          # (B, S, V): vocab on model
    "delta_tm": (_B, MODEL),              # projected error (T, M)
}


def annotate(x, name: str):
    """with_sharding_constraint by rule name; identity without a mesh."""
    mesh = current_mesh()
    if mesh is None or name not in ACT_RULES:
        return x
    b = batch_axes(mesh)
    entries = tuple(
        (b if len(b) > 1 else b[0]) if e is _B else e for e in ACT_RULES[name]
    )
    spec = _divisible(_fit_spec(P(*entries), x.ndim), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _strip_fsdp(entry):
    if entry == FSDP:
        return None
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a != FSDP)
        return kept if kept else None
    return entry


def unshard_fsdp(tree):
    """ZeRO-3 gather: constrain param leaves to their rule spec with the
    FSDP axis removed (replicated over data, still split over model).
    Identity without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return tree

    def gather(path, x):
        spec, _ = spec_for_path(path)
        entries = tuple(_strip_fsdp(e) for e in tuple(spec))
        fit = _divisible(_fit_spec(P(*entries), x.ndim), x.shape, mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fit))

    return path_map(gather, tree)
