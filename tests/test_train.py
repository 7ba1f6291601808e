"""Training substrate: optimizers vs hand math, schedules, trainer loop,
noise-robustness ordering (paper Fig. 5 claim, reduced scale)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import algos
from repro.algos import dfa
from repro.core import photonics
from repro.data import mnist, pipeline
from repro.models.mlp import MLPClassifier
from repro.train import SGDM, AdamW, Trainer, TrainerConfig, schedule
from repro.utils import prng
from repro.utils.tree import tree_allclose


def test_sgdm_matches_manual():
    opt = SGDM(lr=0.1, momentum=0.9)
    p = {"w": jnp.array([1.0, 2.0])}
    g = {"w": jnp.array([0.5, -1.0])}
    s = opt.init(p)
    p1, s1, _ = opt.update(g, s, p)
    np.testing.assert_allclose(np.asarray(p1["w"]), [1 - 0.05, 2 + 0.1])
    p2, s2, _ = opt.update(g, s1, p1)
    # m2 = 0.9*m1 + g
    m2 = 0.9 * np.array([0.5, -1.0]) + np.array([0.5, -1.0])
    np.testing.assert_allclose(np.asarray(p2["w"]), np.asarray(p1["w"]) - 0.1 * m2,
                               rtol=1e-6)


def test_adamw_first_step_is_lr_sized():
    opt = AdamW(lr=1e-3, weight_decay=0.0, clip_norm=None)
    p = {"w": jnp.array([0.0])}
    g = {"w": jnp.array([10.0])}
    s = opt.init(p)
    p1, _, _ = opt.update(g, s, p)
    np.testing.assert_allclose(np.asarray(p1["w"]), [-1e-3], rtol=1e-3)


def test_clip_by_global_norm():
    from repro.train.optimizer import clip_by_global_norm

    g = {"a": jnp.array([3.0]), "b": jnp.array([4.0])}
    clipped, norm = clip_by_global_norm(g, 1.0)
    assert float(norm) == pytest.approx(5.0)
    total = jnp.sqrt(clipped["a"] ** 2 + clipped["b"] ** 2)
    assert float(total[0]) == pytest.approx(1.0)


def test_schedules():
    s = schedule.warmup_cosine(1.0, 10, 110, final_frac=0.1)
    assert float(s(jnp.int32(0))) == 0.0
    assert float(s(jnp.int32(10))) == pytest.approx(1.0)
    assert float(s(jnp.int32(110))) == pytest.approx(0.1, abs=1e-3)
    lin = schedule.linear_decay(2.0, 100)
    assert float(lin(jnp.int32(50))) == pytest.approx(1.0)


@pytest.fixture(scope="module")
def digits():
    data = mnist.load((2048, 512), seed=0)
    return data


@pytest.mark.slow
def test_dfa_training_improves_accuracy(digits):
    xtr, ytr = digits["train"]
    xte, yte = digits["test"]
    pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
    model = MLPClassifier(hidden=(128, 128))
    tr = Trainer(model, TrainerConfig(
        algo="dfa", optimizer=SGDM(lr=0.01, momentum=0.9), log_every=10**9))
    state, _ = tr.fit(pipe.batch, total_steps=96, verbose=False)
    ev = tr.evaluate(state, pipe.eval_batches(xte, yte, 256))
    assert ev["accuracy"] > 0.6  # far above 10% chance after 3 epochs


@pytest.mark.slow
def test_noise_robustness_ordering(digits):
    """Paper Fig. 5: clean >= off-chip-BPD >= on-chip-BPD (with slack for
    short-run variance)."""
    xtr, ytr = digits["train"]
    xte, yte = digits["test"]
    pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
    accs = {}
    for preset in ["ideal", "onchip_bpd"]:
        model = MLPClassifier(hidden=(128, 128))
        tr = Trainer(model, TrainerConfig(
            algo="dfa", dfa=dfa.DFAConfig(photonics=photonics.preset(preset)),
            optimizer=SGDM(lr=0.01, momentum=0.9), log_every=10**9))
        state, _ = tr.fit(pipe.batch, total_steps=96, verbose=False)
        accs[preset] = tr.evaluate(state, pipe.eval_batches(xte, yte, 256))["accuracy"]
    assert accs["ideal"] >= accs["onchip_bpd"] - 0.02
    assert accs["onchip_bpd"] > 0.5  # noisy hardware still trains


@pytest.mark.slow
def test_bp_baseline_trains(digits):
    xtr, ytr = digits["train"]
    pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
    model = MLPClassifier(hidden=(64,))
    tr = Trainer(model, TrainerConfig(algo="bp", optimizer=SGDM(lr=0.05), log_every=10**9))
    state0 = tr.init_state()
    _, m0 = tr.step(state0, pipe.batch(0))
    state, m = tr.fit(pipe.batch, total_steps=64, verbose=False)
    assert float(m["loss"]) < float(m0["loss"])


def test_microbatch_accumulation_matches_full_batch():
    """grad(batch) == mean of grads(microbatches) for DFA with fixed rng."""
    model = MLPClassifier(in_dim=8, hidden=(16,), n_classes=4)
    key = jax.random.PRNGKey(0)
    params = model.init(key)
    cfg_t1 = TrainerConfig(algo="dfa", optimizer=SGDM(lr=0.0), microbatches=1, seed=3)
    cfg_t4 = TrainerConfig(algo="dfa", optimizer=SGDM(lr=0.0), microbatches=4, seed=3)
    batch = {"x": jax.random.normal(key, (32, 8)),
             "y": jax.random.randint(key, (32,), 0, 4)}
    t1, t4 = Trainer(model, cfg_t1), Trainer(model, cfg_t4)
    s1, s4 = t1.init_state(), t4.init_state()
    _, m1 = t1.step(s1, batch)
    _, m4 = t4.step(s4, batch)
    # CE means over different partitions agree
    assert abs(float(m1["ce_loss"]) - float(m4["ce_loss"])) < 1e-5


@pytest.mark.parametrize("algo", algos.list_algos())
def test_trainer_step_is_value_and_grad_then_optimizer(algo):
    """One ``Trainer.step`` is the algorithm's ``value_and_grad`` under the
    step's noise key, then ``optimizer.update``: parameters and momentum
    match the two composed by hand.  The projections are noisy
    (offchip_bpd), so the key the step draws from is pinned too."""
    model = MLPClassifier(in_dim=12, hidden=(24, 16), n_classes=5)
    opt = SGDM(lr=0.05, momentum=0.9)
    cfg = TrainerConfig(
        algo=algo, optimizer=opt, seed=3,
        dfa=dfa.DFAConfig(photonics=photonics.preset("offchip_bpd"),
                          backend="ref"))
    tr = Trainer(model, cfg)
    state = tr.init_state()
    kx, ky = jax.random.split(jax.random.PRNGKey(0))
    batch = {"x": jax.random.normal(kx, (8, 12)),
             "y": jax.random.randint(ky, (8,), 0, 5)}
    stepped, _ = tr.step(state, batch)

    vg = algos.get(algo).value_and_grad(model, cfg.dfa)
    (_, _), grads = vg(state["params"], state["fb"], batch,
                       prng.step_key(cfg.seed, 0, "noise"))
    params, opt_state, _ = opt.update(grads, state["opt"], state["params"])
    assert tree_allclose(stepped["params"], params, rtol=1e-5, atol=1e-7)
    assert tree_allclose(stepped["opt"]["mom"], opt_state["mom"],
                         rtol=1e-5, atol=1e-7)
    if algo != "bp":  # backprop runs no photonic projection
        (_, _), other = vg(state["params"], state["fb"], batch,
                           prng.step_key(cfg.seed, 1, "noise"))
        assert not tree_allclose(other, grads, rtol=1e-5, atol=1e-7)


def test_straggler_deadline_raises():
    model = MLPClassifier(in_dim=8, hidden=(16,), n_classes=4)
    tr = Trainer(model, TrainerConfig(step_deadline_s=0.0))
    state = tr.init_state()
    batch = {"x": jnp.zeros((4, 8)), "y": jnp.zeros((4,), jnp.int32)}
    with pytest.raises(TimeoutError):
        tr.step(state, batch)
