"""``correct`` of the Moonlight cell at test size, on the CPU: a sound run
passes, and the control and each fault of the expert layer make it come
out false — half of the batch left out, a state left unchanged, tokens
dropped over a capacity, the routed scale halved, and a softmax in place
of the sigmoid.

The run is the benchmark's own (``run.main``) with the look for a chip
skipped, on the smoke-sized Moonlight (1 dense + 2 MoE layers, 16
experts with 4 held, top-4) and the full cell's reference, on noiseless
emulated hardware so that a sound run agrees with the reference to
rounding.  Each fault is read against the limits of ``test_correct.py``'s
twins and against the cell's own (``limits/moonlight-16b-a3b.dfa-emu.json``,
with the twins' ``grad1_noise_gap``: the twin draws no noise), so that it
fails a check the cell keeps.
"""

import dataclasses
import os

import pytest

import harness
import run

SPEC = harness.load_json(os.path.join(os.path.dirname(__file__), "data",
                                      "moonlight-bench-small.json"))
TRAIN_LIMITS = {"loss_gap": 1e-4, "grad1_gap": 1e-3, "change_gap": 1e-3,
                "grad1_noise_gap": 1e-3}
CAPACITY_FACTOR = 1.0


@pytest.fixture(autouse=True)
def small(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(harness.Cell, "limits", lambda self: TRAIN_LIMITS)
    from repro import api

    build = api.build_model

    def build_model(arch, *, smoke=False, dtype=None):
        model = build(arch, smoke=smoke, dtype=dtype)
        if smoke:
            model = dataclasses.replace(model, cfg=dataclasses.replace(model.cfg, dtype=dtype))
        return model

    monkeypatch.setattr(api, "build_model", build_model)


def cell_limits():
    limits = harness.load_json(harness.bench_file("limits", "moonlight-16b-a3b.dfa-emu.json"))
    return {**limits["limits"], "grad1_noise_gap": TRAIN_LIMITS["grad1_noise_gap"]}


@pytest.fixture(params=["twin", "cell"])
def limits(request, monkeypatch):
    chosen = TRAIN_LIMITS if request.param == "twin" else cell_limits()
    monkeypatch.setattr(harness.Cell, "limits", lambda self: chosen)
    return chosen


def go(*extra):
    return run.main(["--workload", "moonlight-smoke.train", "--seed", "3000000019",
                     "--seconds", "1", "--trace", "0", *extra], bench=SPEC, allow_cpu=True)


def test_sound_run_is_correct():
    out = go()
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0


def test_control_fails():
    """The program in bfloat16."""
    assert not go("--variant", "control", "--check-only")["correct"]


def test_half_batch_fails(limits, monkeypatch):
    """Half of the batch left out inside the step, the mean over the rest."""
    import jax

    from repro.train import trainer

    step = trainer.Trainer._train_step

    def half(self, state, batch):
        return step(self, state, jax.tree_util.tree_map(lambda x: x[: x.shape[0] // 2], batch))

    monkeypatch.setattr(trainer.Trainer, "_train_step", half)
    assert not go("--check-only")["correct"]


def test_unchanged_state_fails(limits, monkeypatch):
    """A step that returns its state unchanged."""
    from repro.train import trainer

    step = trainer.Trainer._train_step

    def unchanged(self, state, batch):
        _, metrics = step(self, state, batch)
        return state, metrics

    monkeypatch.setattr(trainer.Trainer, "_train_step", unchanged)
    assert not go("--check-only")["correct"]


def test_tokens_dropped_over_a_capacity_fail(limits, monkeypatch):
    """Each held expert keeps only its first ``capacity`` assignments, in
    (token, slot) order, as a capacity-bound dispatch does."""
    import jax.numpy as jnp

    from repro.nn import moe

    experts_fn = moe.MoE._experts

    def dropping(self, params, x_flat, experts):
        per_slot, sizes = experts_fn(self, params, x_flat, experts)
        lo, hi = self.held
        flat = experts.reshape(-1)
        cap = int(CAPACITY_FACTOR * flat.shape[0] / self.n_experts)
        onehot = (flat[:, None] == jnp.arange(lo, hi)[None, :]).astype(jnp.int32)
        position = jnp.sum((jnp.cumsum(onehot, 0) - onehot) * onehot, -1)
        kept = (position < cap) | (onehot.sum(-1) == 0)
        return per_slot * kept[:, None], sizes

    monkeypatch.setattr(moe.MoE, "_experts", dropping)
    assert not go("--check-only")["correct"]


def test_halved_routed_scale_fails(limits, monkeypatch):
    from repro.nn import moe

    route = moe.MoE.route

    def halved(self, params, x_flat):
        logits, scores, experts, weights = route(self, params, x_flat)
        return logits, scores, experts, weights * 0.5

    monkeypatch.setattr(moe.MoE, "route", halved)
    assert not go("--check-only")["correct"]


def test_softmax_in_place_of_sigmoid_fails(limits, monkeypatch):
    from repro.nn import moe

    route = moe.MoE.route

    def softmax(self, params, x_flat):
        return route(dataclasses.replace(self, scoring="softmax"), params, x_flat)

    monkeypatch.setattr(moe.MoE, "route", softmax)
    assert not go("--check-only")["correct"]


def test_moe_gmm_cost_by_hand():
    gmm = harness.load_py(harness.bench_file("kernels", "moe_gmm.py"))
    call = {"kind": "gmm", "rows": 4, "c": 3, "n": 2, "groups": 2}
    # 2*4*3*2 = 48 ops; rows 12 bf16 (24 B) + weights 2*3*2 f32 (48 B) + out 8 f32 (32 B)
    assert gmm.cost(call) == (48.0, 104.0)
    # weight gradient: rows 12 + gradient 8 in bf16 (40 B), weights 12 f32 (48 B)
    assert gmm.cost({**call, "kind": "drhs"}) == (48.0, 88.0)


def test_moonlight_step_flops_and_expert_calls():
    """About 44.1 TFLOP a step at 2 × 8192 tokens (2.69 GFLOP a token), and
    8 grouped-matmul calls in each of the 5 MoE layers."""
    cell = harness.Cell(harness.load_json(harness.bench_file("..", "BENCHMARK.json")),
                        "moonlight-16b-a3b.dfa-emu")
    flops = cell.config_module(".flops.py")
    assert flops.step_flops(cell.config, cell.traffic) == pytest.approx(44.1e12, rel=0.01)
    assert flops.projections(cell.config, cell.traffic) == [
        {"t": 16384, "k": 2048, "m": 2048, "count": 7}]
    calls = flops.expert_gemms(cell.config, cell.traffic)
    assert sum(c["count"] for c in calls) == 40
    assert {c["rows"] for c in calls} == {12288}


def test_moe_gmm_readers_on_a_synthetic_trace():
    """The two readers find the grouped kernel's three calls by name, and
    no other kernel's; the roofline share is the step's floor over their
    time."""
    cell = harness.Cell(harness.load_json(harness.bench_file("..", "BENCHMARK.json")),
                        "moonlight-16b-a3b.dfa-emu")
    ops = {
        '%moe_gmm.3 = f32[98304,2816]{1,0} custom-call(s32[9]{0}, ...), '
        'custom_call_target="tpu_custom_call", backend_config={"kernel_metadata":'
        '{"kernel":"moe_gmm"}}': (10, 0.010),
        '%moe_gmm_dlhs.1 = f32[98304,2048]{1,0} custom-call(s32[9]{0}, ...), '
        'custom_call_target="tpu_custom_call", backend_config={"kernel_metadata":'
        '{"kernel":"moe_gmm_dlhs"}}': (5, 0.005),
        '%moe_gmm_drhs.2 = f32[8,2048,2816]{2,1,0} custom-call(s32[9]{0}, ...), '
        'custom_call_target="tpu_custom_call", backend_config={"kernel_metadata":'
        '{"kernel":"moe_gmm_drhs"}}': (5, 0.005),
        '%emu_bank.19 = f32[1,16384,2176]{2,1,0} custom-call(f32[1,18,16384,128]{3,2,1,0}, ...), '
        'custom_call_target="tpu_custom_call"': (5, 0.5),
    }

    class Reduced:
        def op_seconds(self):
            return ops

    reading = run.Reading(cell=cell, reduced=Reduced(), records={"steps": 1},
                          peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, chips=1)
    kernel_ms = harness.load_py(harness.bench_file("metrics", "kernel_ms.moe_gmm.py")).read
    roofline = harness.load_py(harness.bench_file("metrics", "moe_gmm_roofline.py")).read
    assert kernel_ms(reading) == pytest.approx(20.0)
    cost = harness.load_py(harness.bench_file("kernels", "moe_gmm.py")).cost
    calls = cell.config_module(".flops.py").expert_gemms(cell.config, cell.traffic)
    floor = sum(c["count"] * max(cost(c)[0] / 197e12, cost(c)[1] / 819e9) for c in calls)
    assert roofline(reading) == pytest.approx(floor / 0.020 * 100.0)
    emu_match = harness.load_py(harness.bench_file("kernels", "emu_bank.py")).match
    assert [op for op in ops if emu_match(op)] == [list(ops)[3]]
