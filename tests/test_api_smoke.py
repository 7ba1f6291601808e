"""Registry rot check: every registered algorithm must complete a real
``build_session(...).fit`` step on the smoke mnist_mlp arch.  A new
registry entry that can't train fails here the moment it is registered
(benchmarks/run.py --smoke is the CLI twin of this test)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algos, api


def _batch(model, key, n=16):
    return {"x": jax.random.normal(key, (n, model.in_dim)),
            "y": jax.random.randint(key, (n,), 0, model.n_classes)}


@pytest.mark.parametrize("algo", algos.list_algos())
def test_every_registered_algorithm_fits_one_step(algo):
    session = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                                hardware="ideal", log_every=10**9)
    batch = _batch(session.model, jax.random.PRNGKey(0))
    state, metrics = session.fit(lambda step: batch, total_steps=1,
                                 verbose=False)
    assert int(state["step"]) == 1
    assert np.isfinite(float(metrics["loss"]))
    # the step actually moved the parameters
    init = session.init_state()
    moved = sum(
        float(jnp.sum(jnp.abs(a - b)))
        for a, b in zip(jax.tree_util.tree_leaves(state["params"]),
                        jax.tree_util.tree_leaves(init["params"])))
    assert moved > 0.0


@pytest.mark.parametrize("algo", ["dfa", "dfa-layerwise"])
def test_dfa_family_reduces_loss_over_a_few_steps(algo):
    session = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                                hardware="ideal", log_every=10**9)
    key = jax.random.PRNGKey(1)
    batch = _batch(session.model, key, n=64)
    state = session.init_state()
    _, m0 = session.step(state, batch)
    for _ in range(30):
        state, metrics = session.step(state, batch)
    assert float(metrics["loss"]) < float(m0["loss"])
