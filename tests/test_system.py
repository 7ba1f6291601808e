"""End-to-end behaviour tests for the paper's system: the full photonic-DFA
pipeline (train with measured hardware noise → evaluate → serve)."""

import pytest

from repro import configs
from repro.algos import dfa
from repro.core import energy, photonics
from repro.data import mnist, pipeline, tokens
from repro.models.mlp import MLPClassifier
from repro.train import SGDM, Trainer, TrainerConfig


@pytest.mark.slow
def test_paper_pipeline_end_to_end(tmp_path):
    """The paper's experiment at reduced scale: train the MLP with off-chip
    BPD noise injected into every B(k)e product, checkpoint, resume, eval."""
    data = mnist.load((2048, 256), seed=0)
    xtr, ytr = data["train"]
    xte, yte = data["test"]
    pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
    model = MLPClassifier(hidden=(128, 128))
    tr = Trainer(model, TrainerConfig(
        algo="dfa",
        dfa=dfa.DFAConfig(photonics=photonics.preset("offchip_bpd")),
        optimizer=SGDM(lr=0.01, momentum=0.9),
        ckpt_dir=str(tmp_path), ckpt_every=32, log_every=10**9))
    state, _ = tr.fit(pipe.batch, total_steps=128, verbose=False)
    ev = tr.evaluate(state, pipe.eval_batches(xte, yte, 128))
    assert ev["accuracy"] > 0.4
    # the checkpoint directory holds a usable snapshot
    assert tr.ckpt.latest_step() == 128


@pytest.mark.slow
def test_lm_dfa_reduces_loss_on_markov_stream():
    """A reduced LM (qwen-family smoke) learns the synthetic successor
    structure with DFA — the 'beyond-paper' training path."""
    model = configs.get("qwen1.5-0.5b").make_smoke()
    gen = tokens.MarkovTokens(vocab_size=128, seq_len=32, batch_size=8, seed=0)
    tr = Trainer(model, TrainerConfig(
        algo="dfa", optimizer=SGDM(lr=0.1, momentum=0.9), log_every=10**9))
    state = tr.init_state()
    _, m0 = tr.step(state, gen.batch(0))
    state, _ = tr.fit(gen.batch, total_steps=30, verbose=False)
    _, m1 = tr.step(state, gen.batch(99))
    assert float(m1["ce_loss"]) < float(m0["ce_loss"])


@pytest.mark.slow
def test_dfa_vs_bp_comparable_at_small_scale():
    """Paper §1: DFA yields performance comparable to backprop."""
    data = mnist.load((1024, 256), seed=1)
    xtr, ytr = data["train"]
    xte, yte = data["test"]
    pipe = pipeline.ArrayClassification(xtr, ytr, batch_size=64, seed=0)
    accs = {}
    for algo in ["dfa", "bp"]:
        model = MLPClassifier(hidden=(128,))
        tr = Trainer(model, TrainerConfig(
            algo=algo, optimizer=SGDM(lr=0.02, momentum=0.9), log_every=10**9))
        state, _ = tr.fit(pipe.batch, total_steps=64, verbose=False)
        accs[algo] = tr.evaluate(state, pipe.eval_batches(xte, yte, 128))["accuracy"]
    assert accs["dfa"] > accs["bp"] - 0.15


def test_energy_model_consistent_with_gemm_compiler():
    """OPS from Eq. 2 at full utilisation bounds the GeMM-scheduled rate."""
    cfg = energy.EnergyConfig()
    r = energy.dfa_backward_cost([800, 800], 10, cfg)
    peak = energy.ops_per_second(50, 20, cfg)
    assert r["tops"] * 1e12 <= peak + 1e-9


def test_serving_after_training_roundtrip():
    from repro.serve import Engine, Request

    model = configs.get("mamba2-130m").make_smoke()
    gen = tokens.MarkovTokens(vocab_size=128, seq_len=32, batch_size=8, seed=0)
    tr = Trainer(model, TrainerConfig(algo="dfa", optimizer=SGDM(lr=0.05), log_every=10**9))
    state, _ = tr.fit(gen.batch, total_steps=20, verbose=False)
    eng = Engine(model, state["params"], batch_slots=2, max_len=48)
    reqs = [Request(prompt=[5, (5 * 31 + 7) % 128], max_new=8)]
    eng.run(reqs)
    assert len(reqs[0].out) == 8
