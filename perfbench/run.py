#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  ``--trace 0`` prints the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiler trace of the
window (``device.busy_s``/``window_s`` and a ``breakdown`` besides).  The
last line of stdout is the result; the last lines of stderr are the
numbers that decided ``correct``, each beside its limit.  The command
exits non-zero and prints no result when JAX finds no TPU, fewer chips
than the cell asks for, or a device kind that ``peaks.json`` lacks.

Options for reading the limits of ``correct`` (PERF.md, "How `correct`
is decided"; never used by the benchmark's own runs): ``--variant
control`` runs the control (the program in bfloat16 for training, the
reference in bfloat16 for serving); ``--variant noise_off`` trains on the
noiseless twin of the emulated bank (the upper reading of
``grad1_noise_gap``); ``--check-only`` reads a training cell's compared
numbers without the window.
"""

from __future__ import annotations

import time

T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))

import harness  # noqa: E402


@dataclasses.dataclass
class Context:
    cell: harness.Cell
    seed: int
    seconds: float
    trace: bool
    variant: str | None
    check_only: bool
    t0: float
    phases: harness.Phases
    devices: list
    peaks: dict
    clock: harness.CompileClock
    trace_dir: str | None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric reader reads."""

    cell: harness.Cell
    reduced: object           # xplane.Reduced, or None without a trace
    records: dict             # the kind's own records of the run
    peaks: dict
    chips: int


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--variant", choices=("control", "noise_off"))
    ap.add_argument("--check-only", action="store_true")
    return ap.parse_args(argv)


def main(argv=None, *, bench=None, allow_cpu: bool = False) -> dict:
    """Run the cell; -> the result printed.  ``bench`` (a spec) and
    ``allow_cpu`` are for the benchmark's own tests."""
    args = parse(argv)
    if not os.path.isdir(os.path.join(harness.SRC, "repro")):
        raise harness.BenchError(f"the program is not in this checkout ({harness.SRC}/repro)")
    bench = bench or harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell = harness.Cell(bench, args.workload)
    phases = harness.Phases(T0)
    phases("imports")
    devices = harness.require_chips(cell.chips, allow_cpu)
    kind = devices[0].device_kind
    peaks = harness.peaks_for(kind) if not allow_cpu else {}
    phases("chips")
    harness.enable_compile_cache()
    clock = harness.CompileClock()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") if args.trace else None
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                  variant=args.variant, check_only=args.check_only, t0=T0, phases=phases,
                  devices=devices, peaks=peaks, clock=clock, trace_dir=trace_dir)
    try:
        out = harness.load_py(harness.bench_file("kinds", cell.kind + ".py")).run(ctx)
        reduced = None
        if args.trace and not args.check_only:
            import xplane

            reduced = xplane.reduce(xplane.find_trace(trace_dir))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ok, checks = harness.judge(out["values"], cell.limits())
    ok = ok and out["failed"] == 0
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": ok, "attempted": out["attempted"], "failed": out["failed"]}
    if args.trace:
        reading = Reading(cell=cell, reduced=reduced, records=out["records"],
                          peaks=peaks, chips=len(devices))
        result["metrics"] = harness.read_metrics(cell, reading)
        if reduced is not None:
            device.update(busy_s=reduced.busy_s, window_s=reduced.window_s)
            result["breakdown"] = reduced.breakdown()
    else:
        # a qualified name (``train_step_ms.mnist``) reads its quantity
        # (``train_step_ms``): one quantity, bounds of its own per cell
        e2e = out["e2e"]
        result["metrics"] = {}
        for m in cell.metrics("end_to_end"):
            value = e2e.get(m["name"], e2e.get(m["name"].split(".")[0]))
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"] = device
    return harness.emit(result, checks)


if __name__ == "__main__":
    try:
        main()
    except harness.BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        sys.exit(2)
