"""What every cell of the benchmark shares: the spec, the chip, the clock.

``run.py`` is the command; this module holds what it needs apart from one
traffic kind: reading ``BENCHMARK.json`` and the files a cell names, the
look for the chip and its peaks, the compile clock, the metric readers and
the result line.  Nothing here knows a configuration, a traffic mix or a
metric by name: each lives in files of its own, found by the name in the
spec.

    configs/<config>.json        sizes as run, source, reductions
    configs/<config>.flops.py    model FLOPs of one step, DFA projections
    configs/<config>.ref.py      the plain reference
    traffic/<traffic>.json       parameters of one traffic mix
    generators/<generator>.py    the generator a traffic mix names
    kinds/<kind>.py              the driver of one traffic kind
    kernels/<kernel>.py          operations and bytes of one kernel call
    metrics/<metric>.py          the reader of one per-layer metric
    limits/<workload>.json       the correctness limits of one cell
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import statistics
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
SRC = os.path.join(ROOT, "src")
# JAX's persistent compilation cache: a fixed path inside the checkout,
# because the path is part of the cache's key
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(RuntimeError):
    """The run cannot give a result (no chip, unknown device, bad spec)."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_py(path: str):
    """Import one of the benchmark's data-driven Python files by path."""
    path = os.path.abspath(path)
    if path in _MODULES:
        return _MODULES[path]
    if not os.path.exists(path):
        raise BenchError(f"missing file {os.path.relpath(path, ROOT)}")
    name = "perfbench_" + "".join(
        c if c.isalnum() else "_" for c in os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


def bench_file(*parts: str) -> str:
    return os.path.join(BENCH_DIR, *parts)


class Cell:
    """One workload of the spec with the files it names."""

    def __init__(self, bench: dict, workload: str):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; known: {sorted(cells)}")
        self.bench = bench
        self.workload = cells[workload]
        self.name = workload
        self.chips = int(self.workload["chips"])
        self.config_name = self.workload["config"]
        entry = {c["name"]: c for c in bench["configs"]}[self.config_name]
        self.config = load_json(os.path.join(ROOT, entry["file"]))
        self.traffic = load_json(bench_file("traffic", self.workload["traffic"] + ".json"))
        self.kind = self.traffic["kind"]

    def config_module(self, suffix: str):
        return load_py(bench_file("configs", self.config_name + suffix))

    def metrics(self, section: str) -> list[dict]:
        """This cell's metrics of ``end_to_end`` or ``per_layer``."""
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]

    def _limits_file(self) -> dict:
        path = bench_file("limits", self.name + ".json")
        return load_json(path) if os.path.exists(path) else {}

    def limits(self) -> dict:
        """The limit of each number compared."""
        return self._limits_file().get("limits", {})

    def centers(self) -> dict:
        """The expected value of each two-sided number (the number
        compared is its distance from this)."""
        return self._limits_file().get("centers", {})


def peaks_for(device_kind: str) -> dict:
    table = load_json(bench_file("peaks.json"))["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json "
                         f"({sorted(table)}): no peak to measure against")
    return table[device_kind]


def require_chips(n: int, allow_cpu: bool = False):
    """The devices this cell runs on: TPUs, never the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX found {devs[0].platform}; the "
                         "benchmark never measures on another platform")
    if len(devs) < n:
        raise BenchError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def enable_compile_cache() -> str:
    """Persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``.jax_cache`` in the checkout.  Small programs are cached
    too, so a warm run compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir


class CompileClock:
    """Backend-compile seconds, compiles and persistent-cache hits since
    ``reset`` (JAX's monitoring events)."""

    def __init__(self):
        from jax import monitoring

        self.reset()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self, phase: str) -> dict:
        row = {"compile_s": self.seconds, "compiles": self.compiles,
               "cache_hits": self.cache_hits}
        note(f"{phase}: {row}")
        self.reset()
        return row


def note(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


class Phases:
    """Seconds from process start to the end of each set-up phase, noted
    on stderr, so that a slow set-up shows where it went."""

    def __init__(self, t0: float):
        self.t0 = t0
        self.marks: dict = {}

    def __call__(self, name: str) -> None:
        self.marks[name] = round(time.monotonic() - self.t0, 3)

    def report(self) -> None:
        note(f"set-up phases (s from start): {self.marks}")


class GcWatch:
    """Python's garbage collections while on: a ``gc`` host span in the
    trace for each, and the longest pause by generation."""

    def __init__(self):
        self.on = False
        self.longest: dict = {}
        self.count = 0
        self._t = None
        self._span = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.monotonic()
            self._span = span("gc")
            self._span.__enter__()
        elif self._t is not None:
            self._span.__exit__(None, None, None)
            dt = time.monotonic() - self._t
            g = info.get("generation")
            self.longest[g] = max(self.longest.get(g, 0.0), dt)
            self.count += 1
            self._t = self._span = None

    def report(self, phase: str) -> None:
        note(f"{phase}: {self.count} garbage collections, longest by generation (ms) "
             f"{ {g: round(s * 1e3, 3) for g, s in sorted(self.longest.items())} }")


def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Profiler:
    """JAX's profiler with a ``window`` host span, started and stopped
    once; a no-op when ``on`` is false.  The Python tracer is off (it
    would slow the host path this benchmark measures)."""

    def __init__(self, on: bool, log_dir: str | None):
        self.on, self.log_dir = on, log_dir
        self.window = None
        self.running = False

    def start(self):
        if not self.on:
            return
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.running = True

    def open_window(self):
        if self.running and self.window is None:
            self.window = span("window")
            self.window.__enter__()

    def stop(self):
        if not self.running:
            return
        import jax

        if self.window is not None:
            self.window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.running = False


def memory_peak_bytes(devices) -> int | None:
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
             for d in devices]
    peaks = [p for p in peaks if p >= 0]
    return max(peaks) if peaks else None


def check_model(model, config: dict) -> None:
    """The program's model has the sizes the configuration file states."""
    for attr, key in config["program_keys"].items():
        obj = model
        for part in attr.split("."):
            obj = getattr(obj, part)
        want = config[key]
        if isinstance(obj, tuple):
            obj = list(obj)
        if obj != want:
            raise BenchError(f"the program's {attr} is {obj!r}, the "
                             f"configuration's {key} is {want!r}")


def seed_key(seed: int):
    """The weights' key for ``--seed`` (any whole number below 2**64)."""
    import jax

    if not 0 <= seed < 2 ** 64:
        raise BenchError(f"--seed {seed} is outside [0, 2**64)")
    return jax.random.PRNGKey(seed)


def quantile(values, q: float) -> float | None:
    """The q-quantile (0 < q < 1) by Python's exclusive method, as the
    driver reads spreads; None for fewer than two values."""
    values = list(values)
    if len(values) < 2:
        return values[0] if values else None
    n = 100
    return statistics.quantiles(values, n=n)[round(q * n) - 1]


def finite(x) -> bool:
    return isinstance(x, (int, float)) and math.isfinite(x)


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; correct only if every one
    is finite and within it, and the cell has limits at all."""
    checks = {}
    ok = bool(limits)
    for name, limit in limits.items():
        v = values.get(name)
        checks[name] = {"value": v, "limit": limit}
        if not finite(v) or v > limit:
            ok = False
    for name, v in values.items():
        if name not in checks:
            checks[name] = {"value": v, "limit": None}
    return ok, checks


def read_metrics(cell: Cell, reading) -> dict:
    """Each per-layer metric of the cell, by its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        value = load_py(bench_file("metrics", m["name"] + ".py")).read(reading)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def idle_share(r) -> float | None:
    if r.reduced is None:
        return None
    return (1.0 - r.reduced.busy_s / r.reduced.window_s) * 100.0


def mfu(r) -> float | None:
    """Model FLOPs per step × steps over (traced window × chips × peak)."""
    if r.reduced is None:
        return None
    flops = r.records["step_flops"] * r.records["steps"]
    return flops / (r.reduced.window_s * r.chips * r.peaks["flops_per_s"]) * 100.0


def kernel_seconds(r, kernel: str) -> float | None:
    """Device seconds of a kernel's calls in the traced window, per chip;
    None where the trace holds none."""
    if r.reduced is None:
        return None
    match = load_py(bench_file("kernels", kernel + ".py")).match
    s = sum(sec for name, (_, sec) in r.reduced.op_seconds().items() if match(name))
    return s or None


def roofline_share(r, kernel: str) -> float | None:
    """The least time of the step's kernel calls (``projections`` of the
    configuration's FLOP file, split over the chips) over their device
    time, in percent."""
    s = kernel_seconds(r, kernel)
    if s is None:
        return None
    cost = load_py(bench_file("kernels", kernel + ".py")).cost
    floor = bound = 0.0
    for p in r.records["projections"]:
        ops, nbytes = cost(p["t"] // r.chips, p["k"], p["m"])
        t_ops, t_bytes = ops / r.peaks["flops_per_s"], nbytes / r.peaks["hbm_bytes_per_s"]
        floor += p["count"] * max(t_ops, t_bytes)
        bound += p["count"] * (t_ops - t_bytes)
    note(f"{kernel}: roofline bound by {'operations' if bound >= 0 else 'bytes'}")
    return floor * r.records["steps"] / s * 100.0


def emit(result: dict, checks: dict) -> dict:
    """The compared numbers as the last lines of stderr, and the result as
    the last line of stdout with ``checks`` as its last key; -> that
    line."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = {**result, "checks": checks}
    print(json.dumps(line), flush=True)
    return line
