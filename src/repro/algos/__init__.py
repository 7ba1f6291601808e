"""Pluggable training algorithms.

The paper's contribution is an *algorithm × hardware* matrix; this package
is the algorithm axis.  Adding an algorithm is a registration::

    from repro.algos import Algorithm, register

    class MyAlgo(Algorithm):
        name = "my-algo"
        def value_and_grad(self, model, cfg): ...

    register(MyAlgo())

Built-in registrations (import side effect of the submodules below):

* ``bp``            — exact backprop baseline (algos/bp.py)
* ``dfa``           — the paper's Eq. 1 engine (algos/dfa.py)
* ``dfa-layerwise`` — per-layer error tap, the shallow-DFA ablation

The hardware axis is ``core.photonics.PRESETS`` and the execution axis is
``core.photonics`` backends (``ref`` | ``pallas``); ``repro.api`` composes
all three into a Session.
"""

from repro.algos.base import Algorithm, get, list_algos, register
from repro.algos import bp, dfa, layerwise  # noqa: F401  (register built-ins)
from repro.algos.dfa import DFAConfig

__all__ = ["Algorithm", "DFAConfig", "get", "list_algos", "register",
           "bp", "dfa", "layerwise"]
