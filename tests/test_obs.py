"""repro.obs: metrics registry + sinks, Chrome-trace recording/export,
hardware health monitoring, the disabled-observer fast path, and the
end-to-end wiring into fit / the serve engine / the simulators."""

from __future__ import annotations

import json

import jax
import numpy as np
import pytest

from repro import api, configs, obs, sim
from repro.core import photonics
from repro.hardware.mrr import MRRConfig
from repro.obs.hwmon import DEAD_RING_FACTOR, HardwareMonitor
from repro.obs.metrics import Histogram, JsonlSink, MemorySink, Registry
from repro.obs.trace import HOST_PID, TraceRecorder
from repro.serve import Engine, Request
from repro.sim.autotune import expected_drift_sigma


# ---------------------------------------------------------------------------
# metrics: instruments, percentiles, sinks, the batched drain
# ---------------------------------------------------------------------------

def test_histogram_percentiles_match_numpy():
    """The bounded-window histogram uses numpy's default (linear
    interpolation) percentile method — cross-check on awkward sizes."""
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 999):
        xs = rng.normal(size=n)
        h = Histogram("h", window=2048)
        for x in xs:
            h.observe(float(x))
        for q in (0, 25, 50, 90, 99, 100):
            assert h.percentile(q) == pytest.approx(
                float(np.percentile(xs, q)), rel=1e-12, abs=1e-12)
    with pytest.raises(ValueError):
        Histogram("empty").percentile(50)


def test_histogram_window_bounds_memory():
    h = Histogram("h", window=8)
    for i in range(100):
        h.observe(float(i))
    assert len(h) == 8
    assert h.percentile(0) == 92.0  # only the last window remains


def test_registry_drain_is_one_transfer_and_handles_host_values():
    """``drain`` accepts a mix of device arrays and plain floats and
    returns pure host floats (the jit-safe one-device_get contract)."""
    dev = {"a": jax.numpy.float32(1.5), "b": 2.0, "c": np.float64(3.0)}
    host = Registry.drain(dev)
    assert host == {"a": 1.5, "b": 2.0, "c": 3.0}
    assert all(type(v) is float for v in host.values())


def test_registry_record_fans_out_to_sinks(tmp_path):
    path = str(tmp_path / "m.jsonl")
    reg = Registry([MemorySink(4), JsonlSink(path)])
    reg.record(3, {"loss": jax.numpy.float32(0.25), "lr": 1e-3})
    reg.counter("steps").inc()
    reg.close()
    mem = reg.sinks[0].rows
    assert len(mem) == 1 and mem[0]["step"] == 3
    assert mem[0]["metrics"]["loss"] == 0.25
    rows = [json.loads(ln) for ln in open(path)]
    assert rows[0]["metrics"] == mem[0]["metrics"]
    snap = reg.snapshot()
    assert snap["steps"] == 1.0 and snap["loss"] == 0.25


def test_memory_sink_is_a_bounded_ring():
    reg = Registry([MemorySink(3)])
    for s in range(10):
        reg.emit(s, {"x": float(s)})
    assert [r["step"] for r in reg.sinks[0].rows] == [7, 8, 9]


# ---------------------------------------------------------------------------
# trace: span nesting, event schema, export round-trip
# ---------------------------------------------------------------------------

def test_trace_span_nesting_and_roundtrip(tmp_path):
    rec = TraceRecorder()
    with rec.span("outer", step=1):
        with rec.span("inner"):
            pass
        rec.instant("mark", note="hi")
    rec.counter("load", {"q": 3})
    path = obs.export.write(rec, str(tmp_path / "t.json"))
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    by_name = {e["name"]: e for e in evs}
    inner, outer = by_name["inner"], by_name["outer"]
    assert inner["ph"] == outer["ph"] == "X"
    # LIFO close order: inner is recorded first and nests inside outer
    assert evs.index(inner) < evs.index(outer)
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert outer["args"] == {"step": 1}
    assert by_name["mark"]["ph"] == "i" and by_name["mark"]["s"] == "t"
    assert by_name["load"]["ph"] == "C" and by_name["load"]["args"]["q"] == 3.0


def test_trace_events_carry_required_chrome_fields():
    """Every emitted event has the fields the Perfetto importer needs."""
    rec = TraceRecorder()
    with rec.span("s"):
        pass
    rec.instant("i")
    rec.counter("c", {"v": 1})
    rec.async_begin("a", 7)
    rec.async_instant("m", 7)
    rec.async_end("a", 7)
    rec.name_process(5, "p")
    rec.name_thread(5, 1, "t")
    for ev in rec.events:
        assert {"ph", "name", "pid"} <= set(ev)
        if ev["ph"] != "M":
            assert "ts" in ev
        if ev["ph"] == "X":
            assert "dur" in ev
        if ev["ph"] in "bne":
            assert ev["id"] == 7
    # metadata names are deduplicated
    n_meta = len([e for e in rec.events if e["ph"] == "M"])
    rec.name_process(5, "p")
    rec.name_thread(5, 1, "t")
    assert len([e for e in rec.events if e["ph"] == "M"]) == n_meta


# ---------------------------------------------------------------------------
# hwmon: OU prediction, derived gauges, edge-triggered alerts
# ---------------------------------------------------------------------------

def _mon(**kw):
    dev = MRRConfig()  # drift ON by default
    kw.setdefault("recalibrate_every", 16)
    return HardwareMonitor(dev, **kw), dev


def test_hwmon_gauges_and_expected_sigma():
    mon, dev = _mon()
    exp = expected_drift_sigma(dev, 16)
    out = mon.sample(0, {"hw_residual_rms": exp, "hw_drift_rms": 0.04,
                         "hw_dead_rings": 2.0})
    assert out["hw_expected_sigma"] == pytest.approx(exp)
    assert out["hw_residual_vs_expected"] == pytest.approx(1.0)
    assert out["hw_effective_bits"] == pytest.approx(
        photonics.sigma_to_resolution(exp))
    assert out["hw_dead_rings"] == 2.0
    # rows without hardware scalars produce no gauges (e.g. pure-emu runs)
    assert mon.sample(1, {"loss": 0.5}) == {}


def test_hwmon_alert_is_edge_triggered():
    """One alert per budget crossing: below→above fires, staying above
    does not re-fire, and recovery re-arms the trigger."""
    mon, _ = _mon(drift_budget=0.03)
    seq = [0.01, 0.02, 0.05, 0.06, 0.07, 0.02, 0.01, 0.04]
    for step, resid in enumerate(seq):
        mon.sample(step, {"hw_residual_rms": resid})
    assert [a.step for a in mon.alerts] == [2, 7]
    a = mon.alerts[0]
    assert a.kind == "drift_budget" and a.value == 0.05 and a.budget == 0.03
    assert "exceeds" in a.message


def test_hwmon_default_budget_and_dead_ring_threshold():
    mon, dev = _mon()
    assert mon.drift_budget == pytest.approx(0.5 * dev.drift_sigma)
    assert mon.dead_ring_threshold == pytest.approx(
        DEAD_RING_FACTOR * dev.drift_sigma)


# ---------------------------------------------------------------------------
# the disabled-observer fast path
# ---------------------------------------------------------------------------

def test_null_observer_allocates_nothing():
    """The null observer keeps nothing: no recorder, no rows.  The one
    object a span makes is its profiler annotation, which carries the
    name alone (the Chrome-event args are dropped)."""
    null = obs.resolve(None)
    assert null is obs.NULL and not null.enabled
    assert not hasattr(null, "trace") and not hasattr(null, "metrics")
    span = null.span("a", x=1)
    assert type(span) is jax.profiler.TraceAnnotation
    assert type(null.step_span("s", 3)) is jax.profiler.StepTraceAnnotation
    with span:
        pass
    with null.step_span("s", 3):
        pass
    null.event("e")
    null.counter("c", {"v": 1})
    assert null.log_step(0, {"loss": 1.0}) == {}
    assert null.alerts == []
    null.close()


def test_resolve_contract():
    assert obs.resolve(False) is obs.NULL
    assert isinstance(obs.resolve(True), obs.Observer)
    o = obs.Observer()
    assert obs.resolve(o) is o


# ---------------------------------------------------------------------------
# observer log_step: drain + hwmon merge + alert surfacing
# ---------------------------------------------------------------------------

def test_observer_log_step_merges_hwmon_and_emits_alert_instants():
    mon, _ = _mon(drift_budget=0.03)
    o = obs.Observer(hwmon=mon)
    host = o.log_step(1, {"loss": jax.numpy.float32(0.5),
                          "hw_residual_rms": 0.05})
    assert host["loss"] == 0.5
    assert "hw_effective_bits" in host and "hw_expected_sigma" in host
    # the hwmon gauges reach the metrics sinks, not just the trace
    row = o.metrics.sinks[0].rows[-1]
    assert "hw_effective_bits" in row["metrics"]
    warns = [e for e in o.trace.events
             if e["ph"] == "i" and e["name"].startswith("WARN:")]
    assert len(warns) == 1 and warns[0]["args"]["budget"] == 0.03
    assert o.metrics.counter("hwmon_alerts").value == 1.0
    # staying over budget adds no second instant (edge trigger)
    o.log_step(2, {"hw_residual_rms": 0.06})
    warns = [e for e in o.trace.events if e["name"].startswith("WARN:")]
    assert len(warns) == 1


# ---------------------------------------------------------------------------
# wiring: Session.fit, the serve engine, the simulators
# ---------------------------------------------------------------------------

def test_fit_with_observer_records_steps_and_hw_gauges(tmp_path):
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                hardware="emu_offchip", backend="emu",
                                recalibrate_every=4, log_every=2)
    observer = session.observe(
        metrics_path=str(tmp_path / "m.jsonl"),
        trace_path=str(tmp_path / "t.json"))
    x = np.random.default_rng(0).normal(
        size=(8, session.model.in_dim)).astype(np.float32)
    y = np.zeros((8,), np.int32)
    session.fit(lambda s: {"x": x, "y": y}, total_steps=8, verbose=False)
    path = observer.close()
    doc = json.load(open(path))
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    steps = [e for e in spans if e["name"] == "train.step"]
    assert [e["args"]["step"] for e in steps] == list(range(8))
    names = [e["name"] for e in spans]
    assert names.count("train.dispatch") == names.count("train.data_fn") == 8
    assert names.count("train.put") == 8
    drains = [e for e in spans if e["name"] == "train.drain"]
    assert [e["args"]["step"] for e in drains] == [2, 4, 6, 8]
    recals = [e for e in doc["traceEvents"] if e["name"] == "recalibration"]
    assert {e["args"]["step"] for e in recals} == {4}
    rows = [json.loads(ln) for ln in open(tmp_path / "m.jsonl")]
    assert [r["step"] for r in rows] == [2, 4, 6, 8]  # log_every=2
    assert all("hw_effective_bits" in r["metrics"] for r in rows)
    assert all("loss" in r["metrics"] for r in rows)


def _profiled_spans(log_dir) -> list:
    """[(start_ns, end_ns, name, step_num or None)] of the ``train.*``
    host spans in the one profile recorded under ``log_dir``."""
    from jax.profiler import ProfileData

    [path] = list(log_dir.glob("plugins/profile/*/*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("train."):
                    stats = dict(e.stats)
                    out.append((e.start_ns, e.start_ns + e.duration_ns, e.name,
                                stats.get("step_num")))
    return sorted(out)


@pytest.mark.parametrize("prefetch", [2, 0])
def test_fit_spans_reach_the_profile_without_an_observer(tmp_path, prefetch):
    """``Session.fit`` with no observer still puts its spans in a profile:
    one ``train.step`` per step with its step number, one
    ``train.dispatch`` inside each, and one ``train.data_fn`` and one
    ``train.put`` per batch inside a step.  The prefetcher (depth 2)
    makes all three batches in step 0; without it each step makes its
    own."""
    session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                prefetch=prefetch)
    x = np.random.default_rng(0).normal(
        size=(8, session.model.in_dim)).astype(np.float32)
    y = np.zeros((8,), np.int32)
    fit = lambda: session.fit(lambda s: {"x": x, "y": y},  # noqa: E731
                              total_steps=3, verbose=False)
    jax.block_until_ready(fit()[0])  # compile outside the profile
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(fit()[0])
    spans = _profiled_spans(tmp_path)

    steps = [sp for sp in spans if sp[2] == "train.step"]
    assert [sp[3] for sp in steps] == [0, 1, 2]

    def step_of(sp):
        [k] = [st[3] for st in steps if st[0] <= sp[0] and sp[1] <= st[1]]
        return k

    batches = [0, 0, 0] if prefetch else [0, 1, 2]
    want = {"train.dispatch": [0, 1, 2], "train.data_fn": batches,
            "train.put": batches, "train.drain": [2]}
    for name, in_steps in want.items():
        assert [step_of(sp) for sp in spans if sp[2] == name] == in_steps, name


def test_fit_without_observer_unchanged():
    """observer=None keeps the seed behaviour: same losses, no trace."""
    def run(observer):
        session = api.build_session(arch="mnist_mlp", smoke=True, algo="dfa",
                                    log_every=4)
        x = np.random.default_rng(1).normal(
            size=(8, session.model.in_dim)).astype(np.float32)
        y = np.zeros((8,), np.int32)
        _, metrics = session.fit(lambda s: {"x": x, "y": y}, total_steps=4,
                                 verbose=False, observer=observer)
        return Registry.drain(metrics)
    a, b = run(None), run(obs.Observer())
    assert a.keys() == b.keys()
    assert a["loss"] == pytest.approx(b["loss"])


def test_engine_observer_emits_request_lifecycle_tracks():
    model = configs.get("qwen1.5-0.5b").make_smoke()
    params = model.init(jax.random.PRNGKey(0))
    o = obs.Observer()
    eng = Engine(model, params, batch_slots=2, max_len=32, observer=o)
    reqs = [Request(prompt=[i + 1], max_new=3) for i in range(3)]
    eng.run(reqs)
    evs = o.trace.events
    begins = [e for e in evs if e["ph"] == "b"]
    ends = [e for e in evs if e["ph"] == "e"]
    # per request: one request-track + QUEUED + PREFILL + DECODE begins,
    # all of them closed
    assert len(begins) == len(ends) == 3 * 4
    firsts = [e for e in evs if e["ph"] == "n" and e["name"] == "FIRST_TOKEN"]
    assert len(firsts) == 3
    # phases of one request share its id and appear in lifecycle order
    rid = begins[0]["id"]
    names = [e["name"] for e in evs
             if e.get("id") == rid and e["ph"] in "bne"]
    assert names.index("QUEUED") < names.index("PREFILL") < \
        names.index("DECODE")
    spans = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"prefill_tick", "decode_tick"} <= spans


def test_pipeline_trace_matches_report_occupancy(tmp_path):
    pcfg = photonics.PhotonicConfig(n_buses=2)
    work = [sim.Gemm("g0", t=4, m=64, k=48), sim.Gemm("g1", t=4, m=32, k=48)]
    rec = obs.TraceRecorder()
    report = sim.simulate(work, pcfg, include_weight_update=False, trace=rec)
    evs = [e for e in rec.events if e["ph"] == "X"]
    assert len(evs) == len(report.events)
    # per-stage track durations sum to the busy time occupancy came from
    alive_wall_us = report.n_buses * report.wall_clock_s * 1e6
    for stage, occ in report.occupancy.items():
        dur = sum(e["dur"] for e in evs if e["args"]["stage"] == stage)
        assert dur == pytest.approx(occ * alive_wall_us, rel=1e-9, abs=1e-9)
    # path form writes a loadable file
    path = str(tmp_path / "pipe.json")
    sim.simulate(work, pcfg, include_weight_update=False, trace=path)
    doc = json.load(open(path))
    pids = {e["pid"] for e in doc["traceEvents"]}
    assert pids == {obs.export.SIM_PIPELINE_PID}


def test_serving_trace_rounds_and_requests(tmp_path):
    model = api.build_model("mnist_mlp")
    svc = sim.service_model(model, photonics.PhotonicConfig())
    reqs = [sim.RequestSpec(arrival_s=0.0, prompt_len=9, decode_len=5)]
    path = str(tmp_path / "serve.json")
    rep = sim.simulate_serving(reqs, svc, batch_slots=4, prefill_chunk=4,
                               trace=path)
    evs = json.load(open(path))["traceEvents"]
    rounds = [e for e in evs if e["ph"] == "X"]
    assert len(rounds) == rep.rounds
    assert sum(e["dur"] for e in rounds) == pytest.approx(
        rep.makespan_s * 1e6, rel=1e-9)
    assert len([e for e in evs if e["ph"] == "b"]) == 1
    assert len([e for e in evs if e["ph"] == "e"]) == 1
    firsts = [e for e in evs if e["ph"] == "n" and e["name"] == "FIRST_TOKEN"]
    # first token lands at the end of the last prefill round
    assert firsts[0]["ts"] == pytest.approx(
        (svc.round_s(4) * 2 + svc.round_s(1)) * 1e6, rel=1e-9)
    assert HOST_PID not in {e["pid"] for e in evs}


# ---------------------------------------------------------------------------
# summarize CLI round-trip
# ---------------------------------------------------------------------------

def test_summarize_roundtrip(tmp_path, capsys):
    path = str(tmp_path / "m.jsonl")
    reg = Registry([JsonlSink(path)])
    for s in range(10):
        reg.emit(s, {"loss": 1.0 / (s + 1), "steps_per_s": 100.0 + s})
    reg.close()
    from repro.obs import summarize
    rc = summarize.main([path])
    assert rc == 0
    out = capsys.readouterr().out
    assert "loss" in out and "steps_per_s" in out
