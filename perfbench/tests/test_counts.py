"""Operation and byte counts, checked by hand at one small shape each."""

import pytest

import harness


def _cfg(name):
    return harness.load_py(harness.bench_file("configs", name))


def test_emu_bank_cost_by_hand():
    emu = harness.load_py(harness.bench_file("kernels", "emu_bank.py"))
    # T=4, K=3, M=2: 2*4*3*2 = 48 ops; A 12 + B 6 + C 8 floats of 4 bytes
    assert emu.cost(4, 3, 2) == (48.0, 104.0)


def test_qwen_step_flops_by_hand():
    flops = _cfg("qwen1.5-0.5b.flops.py")
    c = {"hidden_size": 2, "intermediate_size": 3, "vocab_size": 5,
         "num_hidden_layers": 1, "head_dim": 1, "num_attention_heads": 2,
         "num_key_value_heads": 2}
    traffic = {"data": {"batch": 1, "seq": 4}}
    # per token: block projections 2*(2*2 + 2*2*2 + 2*2 + 3*2*3) = 2*(4+8+4+18) = 68,
    # attention 2*S*q = 2*4*2 = 16 -> block 84, forward + vjp = 3*84 = 252;
    # head 2*2*5 = 20 forward, 40 backward; one block + embed projection 2*2*2*2 = 16
    assert flops.step_flops(c, traffic) == 4 * (252 + 20 + 40 + 16)
    assert flops.projections(c, traffic) == [{"t": 4, "k": 2, "m": 2, "count": 2}]


def test_qwen_full_width_step():
    """About 24.6 TFLOP a step at 8 x 1024 tokens (3.0 GFLOP a token)."""
    flops = _cfg("qwen1.5-0.5b.flops.py")
    cell = harness.Cell(harness.load_json(harness.bench_file("..", "BENCHMARK.json")),
                        "qwen1.5-0.5b.dfa-emu")
    total = flops.step_flops(cell.config, cell.traffic)
    assert total == pytest.approx(24.6e12, rel=0.01)


def test_mlp_step_flops_by_hand():
    flops = _cfg("mnist_mlp.flops.py")
    c = {"input_dim": 3, "hidden_sizes": [2], "num_classes": 4}
    traffic = {"data": {"batch": 5}}
    # hidden 2*3*2 = 12, head 2*2*4 = 16: forward 28; exact head gradient 16;
    # hidden vjp 12; one projection 2*4*2 = 16
    assert flops.step_flops(c, traffic) == 5 * (28 + 16 + 12 + 16)
    assert flops.projections(c, traffic) == [{"t": 5, "k": 4, "m": 2, "count": 1}]
