"""The Algorithm protocol: what a training algorithm must provide to plug
into the trainer, the launchers, and the benchmarks.

An Algorithm is a *strategy object* over the DFAModel protocol
(models/base.py): it decides how gradients are produced, while the model
decides what the forward computation is and the PhotonicBackend decides how
feedback projections execute.  The three axes — algorithm × hardware preset
× execution backend — are the paper's experiment matrix, and each is now an
independent registry (algos.register / photonics.PRESETS /
photonics.register_backend).

Contract:

* ``init_extra_state(model, key, cfg)`` — algorithm-owned state that is not
  a parameter and not optimizer state (DFA: the fixed feedback matrices).
  Must be deterministic in ``key``.  Returned pytree is threaded through
  ``value_and_grad`` unchanged and checkpointed alongside params.
* ``value_and_grad(model, cfg)`` — returns
  ``fn(params, extra, batch, rng) -> ((loss, metrics), grads)`` with
  ``grads`` matching ``params``'s structure.  Pure; jit-able.

``cfg`` is the algorithm config (algos.dfa.DFAConfig for the whole DFA
family; BP ignores it).  Keeping one config type across the family lets the
trainer switch algorithms without reshaping its own config.
"""

from __future__ import annotations


class Algorithm:
    """Base class: subclasses override value_and_grad (and optionally
    init_extra_state); instances are registered by name in repro.algos."""

    name = "base"

    def init_extra_state(self, model, key, cfg):
        """Algorithm-owned non-parameter state (default: none)."""
        del model, key, cfg
        return {}

    def value_and_grad(self, model, cfg):
        raise NotImplementedError


_REGISTRY: dict[str, Algorithm] = {}


def register(algo: Algorithm) -> Algorithm:
    """Register an Algorithm instance under its ``name``."""
    if not isinstance(algo, Algorithm):
        raise TypeError(f"expected an Algorithm instance, got {type(algo)!r}")
    _REGISTRY[algo.name] = algo
    return algo


def get(name: str) -> Algorithm:
    if name not in _REGISTRY:
        raise KeyError(
            f"unknown algorithm {name!r}; registered: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_algos() -> list[str]:
    return sorted(_REGISTRY)
