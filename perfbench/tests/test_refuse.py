"""The command gives no result where it cannot measure the chip."""

import os
import subprocess
import sys

import pytest

import harness
import run

ROOT = os.path.dirname(harness.BENCH_DIR)


def test_no_tpu_no_result(capsys):
    with pytest.raises(harness.BenchError, match="no TPU"):
        run.main(["--workload", "mnist_mlp.dfa-emu", "--seed", "1", "--seconds", "1"])
    assert capsys.readouterr().out == ""


def test_unknown_device_kind():
    with pytest.raises(harness.BenchError, match="not in peaks.json"):
        harness.peaks_for("TPU v99")
    assert harness.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


def test_command_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "perfbench/run.py", "--workload", "mnist_mlp.dfa-emu",
                           "--seed", "2", "--seconds", "1", "--trace", "0"],
                          cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_every_metric_has_a_reader_and_every_cell_its_files():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        assert os.path.exists(harness.bench_file("metrics", m["name"] + ".py")), m["name"]
    for w in bench["workloads"]:
        cell = harness.Cell(bench, w["name"])
        assert os.path.exists(harness.bench_file("kinds", cell.kind + ".py"))
        assert cell.limits(), f"{w['name']} has no limits"
        cell.config_module(".ref.py")
        cell.config_module(".flops.py")
