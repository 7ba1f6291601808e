"""``correct`` at sizes a test run holds, on the CPU: a sound run passes,
and the control and each fault a cell can have make it come out false.

The run is the benchmark's own (``run.main``) with the look for a chip
skipped; the cells are small twins of the benchmark's (the paper's MLP at
full width on 1,024 digits, the smoke-sized qwen1.5 for training and
serving), on noiseless emulated hardware so that a sound run agrees with
the reference to rounding.  The limits here are for these twins; the
benchmark's own are in ``limits/``.
"""

import dataclasses
import os

import pytest

import harness
import run

SPEC = harness.load_json(os.path.join(os.path.dirname(__file__), "data", "bench-small.json"))
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad1_gap": 1e-3, "change_gap": 1e-3,
                "grad1_noise_gap": 1e-3}
# on the CPU a float32 matmul at the default precision is exact float32,
# so a sound run serves the reference's own first choice at every token
SERVE_LIMITS = {"logit_gap_mean": 1e-5}


@pytest.fixture(autouse=True)
def small(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness.Cell, "limits", lambda self: (
        SERVE_LIMITS if self.kind == "serve" else TRAIN_LIMITS))
    # the smoke-sized model ignores ``dtype``; give it the one asked for,
    # so the control (the program in bfloat16) runs at this size too
    from repro import api

    build = api.build_model

    def build_model(arch, *, smoke=False, dtype=None):
        model = build(arch, smoke=smoke, dtype=dtype)
        if smoke and hasattr(model, "cfg"):
            model = dataclasses.replace(model, cfg=dataclasses.replace(model.cfg, dtype=dtype))
        return model

    monkeypatch.setattr(api, "build_model", build_model)


def go(workload, *extra):
    return run.main(["--workload", workload, "--seed", "3000000019", "--seconds", "1",
                     "--trace", "0", *extra], bench=SPEC, allow_cpu=True)


TRAIN = ["qwen-smoke.train", "mnist-small.train"]


@pytest.mark.parametrize("workload", TRAIN + ["qwen-smoke.serve"])
def test_sound_run_is_correct(workload):
    out = go(workload)
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert "setup_s" in out["metrics"]


@pytest.mark.parametrize("workload", TRAIN)
def test_control_fails(workload):
    """The program in bfloat16, the step a later change might take."""
    assert not go(workload, "--variant", "control", "--check-only")["correct"]


@pytest.mark.parametrize("workload", TRAIN)
def test_half_batch_fails(workload, monkeypatch):
    """Half of the batch left out inside the step, the mean over the rest."""
    import jax

    from repro.train import trainer

    step = trainer.Trainer._train_step

    def half(self, state, batch):
        return step(self, state, jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch))

    monkeypatch.setattr(trainer.Trainer, "_train_step", half)
    assert not go(workload, "--check-only")["correct"]


@pytest.mark.parametrize("workload", TRAIN)
def test_unchanged_state_fails(workload, monkeypatch):
    """A step that returns its state unchanged."""
    from repro.train import trainer

    step = trainer.Trainer._train_step

    def unchanged(self, state, batch):
        _, metrics = step(self, state, batch)
        return state, metrics

    monkeypatch.setattr(trainer.Trainer, "_train_step", unchanged)
    assert not go(workload, "--check-only")["correct"]


def test_serving_control_fails():
    """The reference in bfloat16 in the program's place."""
    out = go("qwen-smoke.serve", "--variant", "control")
    assert not out["correct"]
    gap = out["checks"]["logit_gap_mean"]
    assert gap["value"] > gap["limit"], out["checks"]


def test_altered_token_fails(monkeypatch):
    """A served token altered where the decode step produces it."""
    from repro.serve import engine

    make = engine.make_serve_step

    def altered(model):
        step = make(model)

        def serve_step(params, token, caches, cache_len):
            nxt, logits, upd = step(params, token, caches, cache_len)
            return (nxt + 1) % model.cfg.vocab_size, logits, upd

        return serve_step

    monkeypatch.setattr(engine, "make_serve_step", altered)
    assert not go("qwen-smoke.serve")["correct"]


@pytest.mark.parametrize("workload", TRAIN)
def test_wrong_noise_fails(workload, monkeypatch):
    """Noise drawn where the configuration's hardware has none: the
    projections stay unbiased, so only the gradient's energy sees it."""
    from repro import api

    build = api.build_session

    def noisy(**kw):
        return build(**{**kw, "hardware": "emu_onchip"})

    monkeypatch.setattr(api, "build_session", noisy)
    out = go(workload, "--check-only")
    assert not out["correct"]
    gap = out["checks"]["grad1_noise_gap"]
    assert gap["value"] > gap["limit"], out["checks"]
