"""BENCH_*.json serialization — the repo's perf trajectory format.

Every benchmark emission goes through one stable schema so CI can archive
the files and later PRs can be judged against the recorded numbers::

    {
      "schema":  "repro.bench/v1",
      "name":    "train_throughput",          # -> BENCH_train_throughput.json
      "created_unix": 1722470400.0,
      "env":     {"jax": "0.9.0", "backend": "cpu", "device_count": 8,
                  "python": "3.10.14"},
      "metrics": {"steps_per_s": 12.5, ...},  # numbers only, all finite
      "meta":    {...}                        # free-form provenance
    }

``validate`` raises ValueError on anything that doesn't round-trip, so a
schema drift breaks tests/CI instead of silently corrupting the trajectory.
"""

from __future__ import annotations

import json
import math
import os
import platform
import time

SCHEMA = "repro.bench/v1"
_PREFIX = "BENCH_"


def environment() -> dict:
    import jax

    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
    }


def make_report(name: str, metrics: dict, meta: dict | None = None) -> dict:
    return validate({
        "schema": SCHEMA,
        "name": name,
        "created_unix": time.time(),
        "env": environment(),
        "metrics": dict(metrics),
        "meta": dict(meta or {}),
    })


def validate(report: dict) -> dict:
    """Check the stable schema; returns the report or raises ValueError."""
    if not isinstance(report, dict):
        raise ValueError(f"bench report must be a dict, got {type(report)}")
    if report.get("schema") != SCHEMA:
        raise ValueError(
            f"bench schema mismatch: {report.get('schema')!r} != {SCHEMA!r}")
    name = report.get("name")
    if not isinstance(name, str) or not name:
        raise ValueError(f"bench name must be a non-empty str, got {name!r}")
    if not isinstance(report.get("created_unix"), (int, float)):
        raise ValueError("bench created_unix must be a unix timestamp")
    if not isinstance(report.get("env"), dict):
        raise ValueError("bench env must be a dict")
    metrics = report.get("metrics")
    if not isinstance(metrics, dict) or not metrics:
        raise ValueError("bench metrics must be a non-empty dict")
    for k, v in metrics.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            raise ValueError(f"bench metric {k!r} must be a number, got {v!r}")
        if not math.isfinite(v):
            raise ValueError(f"bench metric {k!r} is not finite: {v!r}")
    if not isinstance(report.get("meta", {}), dict):
        raise ValueError("bench meta must be a dict")
    return report


def bench_path(name: str, out_dir: str = ".") -> str:
    return os.path.join(out_dir, f"{_PREFIX}{name}.json")


def write_bench(name: str, metrics: dict, meta: dict | None = None,
                out_dir: str = ".") -> str:
    """Validate + serialize one report; returns the BENCH_<name>.json path."""
    report = make_report(name, metrics, meta)
    os.makedirs(out_dir, exist_ok=True)
    path = bench_path(name, out_dir)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    return path


def load_bench(path: str) -> dict:
    with open(path) as f:
        return validate(json.load(f))


def _shard_multiplier(mesh, batch) -> int:
    """Devices the step's flops are split over: the mesh size only when the
    batch dim actually sharded; the divisibility fallback replicates the
    batch, so each device computes full-batch flops and the multiplier is 1
    (anything else records a phantom mesh-size speedup in the BENCH json)."""
    if mesh is None:
        return 1
    import jax

    from repro.dist.sharding import make_batch_shardings

    specs = [s.spec for s in jax.tree_util.tree_leaves(
        make_batch_shardings(mesh, batch))]
    batched = [s for s in specs if len(s) >= 1]  # scalar leaves can't shard
    if batched and all(s[0] is not None for s in batched):
        return int(mesh.devices.size)
    return 1


def report_throughput(session, state, batch, timer, meta: dict | None = None,
                      out_dir: str = ".") -> tuple[str, dict]:
    """Finish a timed ``session.fit``: attach the step's per-device HLO cost
    to ``timer`` (device count = the devices the batch is actually split
    over — utils.hlo_cost reports post-SPMD per-device flops), write
    BENCH_train_throughput.json, and print the headline numbers."""
    n_dev = _shard_multiplier(session.mesh, batch)
    timer.set_step_cost(session.step_cost(state, batch).flops,
                        device_count=n_dev)
    summary = timer.summary()
    base = {"data_parallel": session.mesh is not None, "devices": int(n_dev)}
    base.update(meta or {})
    path = write_bench("train_throughput", summary, meta=base, out_dir=out_dir)
    print(f"[bench] {path}: steps/s={summary['steps_per_s']:.2f} "
          f"examples/s={summary.get('examples_per_s', 0):.0f} "
          f"MACs/s={summary.get('macs_per_s', 0):.3e} devices={n_dev}",
          flush=True)
    return path, summary


def clamped_warmup(total_steps: int, target: int) -> int:
    """Warmup steps for a StepTimer over a ``total_steps`` fit: at least one
    step must remain measured, however short the run."""
    return max(0, min(target, total_steps - 1))
