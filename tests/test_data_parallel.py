"""Data-parallel training path + bench telemetry.

The in-process tests exercise the sharded path whenever the test run has
more than one device (CI's XLA_FLAGS=--xla_force_host_platform_device_count=8
matrix job); the subprocess test forces 8 host devices so the equivalence
claim is checked even from a single-device tier-1 run.
"""

import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from repro import api
from repro.bench import report, telemetry
from repro.data.pipeline import DevicePrefetcher
from repro.dist import sharding

MULTI = jax.device_count() >= 2


def _batch(model, key, n=32):
    return {"x": jax.random.normal(key, (n, model.in_dim)),
            "y": jax.random.randint(key, (n,), 0, model.n_classes)}


def _max_diff(a, b):
    diffs = jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)
    return max(jax.tree_util.tree_leaves(diffs))


# ---------------------------------------------------------------------------
# sharded vs single-device equivalence (runs under the 8-device CI job)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not MULTI, reason="needs >1 device (XLA_FLAGS force)")
@pytest.mark.parametrize("algo", ["bp", "dfa"])
def test_sharded_grads_match_single_device(algo):
    s_dp = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                             data_parallel=True, log_every=10**9)
    s_1d = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                             data_parallel=False, log_every=10**9)
    assert s_dp.mesh is not None and s_1d.mesh is None
    batch = _batch(s_1d.model, jax.random.PRNGKey(0),
                   n=8 * jax.device_count())
    rng = jax.random.PRNGKey(7)
    state = s_1d.init_state()

    (l1, _), g1 = jax.jit(s_1d.value_and_grad())(
        state["params"], state["fb"], batch, rng)

    mesh = s_dp.mesh
    with sharding.use_mesh(mesh):
        rep = sharding.replicate(mesh, {"p": state["params"], "fb": state["fb"]})
        db = sharding.put_batch(mesh, batch)
        assert db["x"].sharding.spec[0] is not None  # actually split on dim 0
        (l2, _), g2 = jax.jit(s_dp.value_and_grad())(
            rep["p"], rep["fb"], db, rng)

    assert abs(float(l1) - float(l2)) < 1e-5
    assert _max_diff(g1, g2) < 1e-5


@pytest.mark.skipif(not MULTI, reason="needs >1 device (XLA_FLAGS force)")
def test_data_parallel_fit_matches_single_device():
    batch = None
    states, losses = {}, {}
    for dp in (True, False):
        s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                              data_parallel=dp, log_every=10**9)
        if batch is None:
            batch = _batch(s.model, jax.random.PRNGKey(1),
                           n=8 * jax.device_count())
        state, metrics = s.fit(lambda step: batch, total_steps=4,
                               verbose=False)
        states[dp], losses[dp] = state, float(metrics["loss"])
    assert losses[True] == pytest.approx(losses[False], abs=1e-5)
    assert _max_diff(states[True]["params"], states[False]["params"]) < 1e-5


@pytest.mark.skipif(not MULTI, reason="needs >1 device (XLA_FLAGS force)")
def test_data_parallel_composes_with_microbatching():
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel=True, microbatches=2,
                          log_every=10**9)
    batch = _batch(s.model, jax.random.PRNGKey(2), n=8 * jax.device_count())
    state, metrics = s.fit(lambda step: batch, total_steps=2, verbose=False)
    assert int(state["step"]) == 2
    assert jnp.isfinite(metrics["loss"])


@pytest.mark.skipif(not MULTI, reason="needs >1 device (XLA_FLAGS force)")
def test_indivisible_batch_falls_back_to_replication():
    """Batch size not divisible by the device count must still train (the
    batch sharding falls back to replicated)."""
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel=True, log_every=10**9)
    n = 8 * jax.device_count() - 3
    batch = _batch(s.model, jax.random.PRNGKey(3), n=n)
    state, metrics = s.fit(lambda step: batch, total_steps=1, verbose=False)
    assert jnp.isfinite(metrics["loss"])


# ---------------------------------------------------------------------------
# mesh-less fallback
# ---------------------------------------------------------------------------

def test_meshless_fallback_still_trains():
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel=False, log_every=10**9)
    assert s.mesh is None
    batch = _batch(s.model, jax.random.PRNGKey(4), n=16)
    state, metrics = s.fit(lambda step: batch, total_steps=2, verbose=False)
    assert int(state["step"]) == 2
    assert jnp.isfinite(metrics["loss"])


def test_data_parallel_off_string_means_off():
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel="off", log_every=10**9)
    assert s.mesh is None
    with pytest.raises(ValueError, match="data_parallel"):
        api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel="bogus")


@pytest.mark.skipif(not MULTI, reason="needs >1 device (XLA_FLAGS force)")
def test_report_throughput_replication_fallback_multiplier_is_one(tmp_path):
    """Indivisible batch -> replication fallback -> per-device flops are
    full-batch flops, so MACs/s must NOT be multiplied by the mesh size."""
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel=True, log_every=10**9)
    n = 8 * jax.device_count() - 3
    batch = _batch(s.model, jax.random.PRNGKey(8), n=n)
    t = telemetry.StepTimer(warmup=report.clamped_warmup(2, 4))
    state, _ = s.fit(lambda step: batch, total_steps=2, verbose=False, timer=t)
    _, summary = report.report_throughput(
        s, state, batch, t, out_dir=str(tmp_path))
    assert summary["device_count"] == 1


def test_auto_resolves_by_device_count():
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel="auto", log_every=10**9)
    if jax.local_device_count() > 1:
        assert s.mesh is not None
        assert s.mesh.devices.size == jax.local_device_count()
    else:
        assert s.mesh is None


# ---------------------------------------------------------------------------
# subprocess: force 8 host devices from a single-device tier-1 run
# ---------------------------------------------------------------------------

_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from repro import api
    from repro.dist import sharding

    out = {"devices": jax.device_count()}
    batch = None
    for algo in ("bp", "dfa"):
        s_dp = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                                 data_parallel=True, log_every=10**9)
        s_1d = api.build_session(arch="mnist_mlp", smoke=True, algo=algo,
                                 data_parallel=False, log_every=10**9)
        if batch is None:
            key = jax.random.PRNGKey(0)
            batch = {"x": jax.random.normal(key, (64, s_1d.model.in_dim)),
                     "y": jax.random.randint(key, (64,), 0,
                                             s_1d.model.n_classes)}
        rng = jax.random.PRNGKey(7)
        state = s_1d.init_state()
        (l1, _), g1 = jax.jit(s_1d.value_and_grad())(
            state["params"], state["fb"], batch, rng)
        mesh = s_dp.mesh
        with sharding.use_mesh(mesh):
            rep = sharding.replicate(mesh, {"p": state["params"],
                                            "fb": state["fb"]})
            db = sharding.put_batch(mesh, batch)
            (l2, _), g2 = jax.jit(s_dp.value_and_grad())(
                rep["p"], rep["fb"], db, rng)
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))), g1, g2)
        out[algo] = {"loss_diff": abs(float(l1) - float(l2)),
                     "grad_diff": max(jax.tree_util.tree_leaves(diffs)),
                     "batch_split": str(db["x"].sharding.spec[0])}
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_sharded_equivalence_on_8_forced_devices():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8
    for algo in ("bp", "dfa"):
        assert out[algo]["loss_diff"] < 1e-5
        assert out[algo]["grad_diff"] < 1e-5
        assert out[algo]["batch_split"] == "data"


_KERNEL_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    import jax, jax.numpy as jnp
    from repro import api
    from repro.core import photonics

    backends = {
        "pallas": (photonics.PallasBackend(interpret=True), "ideal"),
        "emu": (photonics.EmulatedMRRBackend(emu_kernel="pallas"), "emu_ideal"),
    }
    key = jax.random.PRNGKey(5)
    batch = None
    out = {}
    for name, (backend, hardware) in backends.items():
        runs = {}
        for dp in (True, False):
            s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                  hardware=hardware, backend=backend,
                                  data_parallel=dp,
                                  microbatches=1 if dp else 4,
                                  log_every=10**9)
            if batch is None:
                batch = {"x": jax.random.normal(key, (32, s.model.in_dim)),
                         "y": jax.random.randint(key, (32,), 0,
                                                 s.model.n_classes)}
            state, metrics = s.fit(lambda step: batch, total_steps=3,
                                   verbose=False)
            runs[dp] = (float(metrics["loss"]), state["params"])
        diffs = jax.tree_util.tree_map(
            lambda a, b: float(jnp.max(jnp.abs(a - b))),
            runs[True][1], runs[False][1])
        out[name] = {"loss_diff": abs(runs[True][0] - runs[False][0]),
                     "param_diff": max(jax.tree_util.tree_leaves(diffs))}
    print(json.dumps(out))
""")


@pytest.mark.slow
def test_kernel_backends_train_per_shard_on_4_forced_devices():
    """The Pallas and emu kernels cannot be split by the SPMD partitioner,
    so under a data mesh each device projects its own rows; four-way data
    parallel fit matches one device running the batch as four
    microbatches (each encoded by its own bank in both cases)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.run([sys.executable, "-c", _KERNEL_SUBPROC], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    for name in ("pallas", "emu"):
        assert out[name]["loss_diff"] < 1e-5
        assert out[name]["param_diff"] < 1e-5


# ---------------------------------------------------------------------------
# bench: schema round-trip + telemetry units
# ---------------------------------------------------------------------------

def test_bench_schema_round_trip(tmp_path):
    path = report.write_bench(
        "unit", {"steps_per_s": 12.5, "examples_per_s": 800.0},
        meta={"arch": "mnist_mlp"}, out_dir=str(tmp_path))
    assert os.path.basename(path) == "BENCH_unit.json"
    obj = report.load_bench(path)
    assert obj["schema"] == report.SCHEMA
    assert obj["metrics"]["steps_per_s"] == 12.5
    assert obj["env"]["device_count"] == jax.device_count()


@pytest.mark.parametrize("mutate", [
    lambda r: r.update(schema="repro.bench/v0"),
    lambda r: r.update(name=""),
    lambda r: r.update(metrics={}),
    lambda r: r["metrics"].update(bad=float("nan")),
    lambda r: r["metrics"].update(bad="fast"),
])
def test_bench_validate_rejects_drift(mutate):
    rep = report.make_report("unit", {"steps_per_s": 1.0})
    mutate(rep)
    with pytest.raises(ValueError):
        report.validate(rep)


def test_step_timer_derives_throughput():
    t = telemetry.StepTimer(warmup=2, examples_per_step=64)
    t.start()
    for _ in range(6):
        time.sleep(0.002)
        t.tick()
    assert t.recorded_steps == 4
    t.set_step_cost(flops_per_device=2e6, device_count=4)
    s = t.summary()
    assert s["steps_per_s"] > 0
    assert s["examples_per_s"] == pytest.approx(64 * s["steps_per_s"])
    assert s["macs_per_s"] == pytest.approx(s["steps_per_s"] * 1e6 * 4)
    assert s["mean_step_s"] >= 0.002


def test_step_timer_requires_measured_steps():
    t = telemetry.StepTimer(warmup=5)
    t.start()
    t.tick()
    with pytest.raises(ValueError):
        t.summary()


def test_device_prefetcher_double_buffers_and_limits():
    calls = []

    def data_fn(step):
        calls.append(step)
        return {"x": step}

    feed = DevicePrefetcher(data_fn, put_fn=lambda b: b, depth=2, limit=4)
    assert feed(0) == {"x": 0}
    assert calls == [0, 1, 2]       # depth=2: two steps prefetched ahead
    assert feed(1) == {"x": 1}
    assert calls == [0, 1, 2, 3]    # buffered batches reused, 3 enqueued
    assert feed(3) == {"x": 3}      # seek drops stale entries
    assert 4 not in calls           # limit stops the lookahead


def test_clamped_warmup_always_leaves_a_measured_step():
    assert report.clamped_warmup(32, 4) == 4
    assert report.clamped_warmup(2, 4) == 1
    assert report.clamped_warmup(1, 4) == 0
    assert report.clamped_warmup(0, 4) == 0


def test_report_throughput_uses_mesh_size_not_host_devices(tmp_path):
    """MACs/s must scale by the mesh the step is sharded over (1 without a
    mesh), never by the host device count — an un-sharded run on a
    multi-device host must not overcount."""
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          data_parallel=False, log_every=10**9)
    batch = _batch(s.model, jax.random.PRNGKey(6), n=16)
    t = telemetry.StepTimer(warmup=report.clamped_warmup(2, 4))
    state, _ = s.fit(lambda step: batch, total_steps=2, verbose=False, timer=t)
    path, summary = report.report_throughput(
        s, state, batch, t, meta={"arch": "mnist_mlp"}, out_dir=str(tmp_path))
    obj = report.load_bench(path)
    assert obj["meta"]["devices"] == 1
    assert obj["meta"]["data_parallel"] is False
    assert obj["metrics"]["macs_per_s"] == pytest.approx(
        summary["steps_per_s"] * summary["flops_per_step_per_device"] / 2.0)


def test_trainer_fit_with_timer_records_steps():
    s = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                          log_every=10**9)
    batch = _batch(s.model, jax.random.PRNGKey(5), n=16)
    t = telemetry.StepTimer(warmup=1)
    state, _ = s.fit(lambda step: batch, total_steps=4, verbose=False,
                     timer=t)
    assert t.recorded_steps == 3
    assert t.examples_per_step == 16
    cost = s.step_cost(state, batch)
    assert cost.flops > 0
    t.set_step_cost(cost.flops)
    assert t.summary()["macs_per_s"] > 0
