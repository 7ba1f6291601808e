"""Observability overhead study — BENCH_obs.json (ISSUE 8 headline).

Times the SAME fused-emu training fit (the qwen1.5-0.5b smoke arch —
the model shape BENCH_emu_kernel gates — on the device-level ``emu``
backend with the fused ``xla`` kernel) twice:

* observer **off** — ``fit(observer=None)``: the null-observer fast path
  (spans are bare profiler annotations, one batched ``jax.device_get`` per
  logging interval);
* observer **on**  — a fully-wired ``obs.Observer``: per-step trace
  spans, recalibration instants, hardware monitor (drift vs the OU
  prediction, effective bits, dead rings), JSONL metrics sink and a
  Chrome trace written at the end.

``log_every=1`` drains metrics EVERY step — the worst case for the
observer — so the measured ratio upper-bounds any real logging cadence.
The acceptance bar is overhead <= 2% (throughput_ratio >= 0.98); the
perf gate (``benchmarks/check_regression.py``) holds ``throughput_ratio``
with a small wall-clock-jitter tolerance.  The run's trace and metrics
files land next to the BENCH json (``obs-trace.json``,
``obs-metrics.jsonl``) so CI archives a loadable example of both.

CLI:  PYTHONPATH=src python -m benchmarks.obs_overhead [--steps N]
"""

from __future__ import annotations

import argparse
import os
import time

BENCH_NAME = "obs"


ARCH = "qwen1.5-0.5b"


def _build_session(log_every: int):
    from repro import api

    return api.build_session(
        arch=ARCH, smoke=True, algo="dfa", hardware="emu_offchip",
        backend="emu", emu_kernel="xla", recalibrate_every=16,
        log_every=log_every)


def _fit_wall_s(session, batch, steps: int, observer) -> float:
    """Wall time of one ``fit`` over ``steps`` steps (result synced)."""
    import jax

    t0 = time.monotonic()
    state, _ = session.fit(lambda s: batch, total_steps=steps,
                           verbose=False, observer=observer)
    jax.block_until_ready(state)
    return time.monotonic() - t0


def probe_off_parity(steps: int = 4, batch_size: int = 8,
                     seq_len: int = 32) -> bool:
    """Acceptance check for the diagnostics plane: with ``probe_every``
    left at its default (None), an observed fit must produce BIT-IDENTICAL
    training state to an unobserved one — observability that perturbs
    training is a bug, not overhead."""
    import jax
    import numpy as np

    from repro import obs
    from repro.data import tokens

    def final_state(observed: bool):
        session = _build_session(10**9)
        gen = tokens.MarkovTokens(session.model.cfg.vocab_size, seq_len,
                                  batch_size, 0)
        batch = gen.batch(0)
        observer = obs.for_session(session) if observed else None
        state, _ = session.fit(lambda s: batch, total_steps=steps,
                               verbose=False, observer=observer)
        return jax.device_get(state)

    plain, observed = final_state(False), final_state(True)
    return all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(plain),
                        jax.tree_util.tree_leaves(observed)))


def run(steps: int = 96, warmup: int = 8, batch_size: int = 8,
        seq_len: int = 32, log_every: int = 1, repeats: int = 5,
        out_dir: str = ".") -> dict:
    """Measure observer-off vs observer-on fit throughput on the fused emu
    step.  Interleaves the two modes ``repeats`` times and takes the best
    wall per mode (min suppresses one-off scheduler jitter on shared
    runners; both modes see the same conditions).  ``steps`` must be large
    enough that the fit-entry fixed cost (state init, feed setup) washes
    out — at 96 steps the ratio is step-cost dominated.  Per-fit jitter
    on a loaded host is a few percent, larger than the observer's real
    per-step cost (~tens of µs on an ~10 ms step), so the min over
    ``repeats`` is what makes the ratio meaningful."""
    import jax

    from repro import obs
    from repro.data import tokens

    session = _build_session(log_every)
    gen = tokens.MarkovTokens(session.model.cfg.vocab_size, seq_len,
                              batch_size, 0)
    batch = gen.batch(0)

    # compile + warm both code paths before any measurement
    _fit_wall_s(session, batch, warmup, None)
    _fit_wall_s(session, batch, warmup, obs.for_session(session))

    off_walls, on_walls = [], []
    for _ in range(repeats):
        off_walls.append(_fit_wall_s(session, batch, steps, None))
        on_walls.append(_fit_wall_s(session, batch, steps,
                                    obs.for_session(session)))
    off_s, on_s = min(off_walls), min(on_walls)

    # one final observed run keeps its artifacts for inspection/CI upload
    trace_path = os.path.join(out_dir, "obs-trace.json")
    metrics_path = os.path.join(out_dir, "obs-metrics.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)  # JsonlSink appends; keep one run's rows
    observer = obs.for_session(session, metrics_path=metrics_path,
                               trace_path=trace_path)
    _fit_wall_s(session, batch, steps, observer)
    observer.close()

    off_sps, on_sps = steps / off_s, steps / on_s
    ratio = on_sps / off_sps
    with open(metrics_path) as f:
        n_rows = sum(1 for line in f if line.strip())
    parity = probe_off_parity(batch_size=batch_size, seq_len=seq_len)
    return {
        "arch": ARCH, "backend": "emu", "emu_kernel": "xla",
        "steps": steps, "repeats": repeats, "log_every": log_every,
        "batch": batch_size, "seq_len": seq_len,
        "jax_backend": jax.default_backend(),
        "off": {"wall_s": off_s, "steps_per_s": off_sps},
        "on": {"wall_s": on_s, "steps_per_s": on_sps},
        "throughput_ratio": ratio,
        "overhead_pct": (1.0 - ratio) * 100.0,
        "probe_off_parity": parity,
        "trace_events": len(observer.trace.events),
        "metric_rows": n_rows,
        "alerts": len(observer.alerts),
        "trace_path": trace_path,
        "metrics_path": metrics_path,
    }


def bench_metrics(res: dict) -> dict:
    """The gated BENCH metric view (see benchmarks/check_regression.py)."""
    return {
        "off_steps_per_s": res["off"]["steps_per_s"],
        "on_steps_per_s": res["on"]["steps_per_s"],
        "throughput_ratio": res["throughput_ratio"],
        "overhead_pct": res["overhead_pct"],
        # 1.0 iff an observed fit (probe off) matches an unobserved one
        # bitwise — failure here means observability perturbed training
        "probe_off_parity": float(res["probe_off_parity"]),
        "trace_events": float(res["trace_events"]),
        "metric_rows": float(res["metric_rows"]),
    }


def write_report(res: dict, out_dir: str = ".") -> str:
    from repro.bench import write_bench

    return write_bench(BENCH_NAME, bench_metrics(res),
                       meta={k: res[k] for k in
                             ("arch", "backend", "emu_kernel", "steps",
                              "repeats", "log_every", "batch", "seq_len",
                              "jax_backend", "alerts", "trace_path",
                              "metrics_path")},
                       out_dir=out_dir)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=96)
    ap.add_argument("--warmup", type=int, default=8)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--log-every", type=int, default=1)
    ap.add_argument("--out-dir", default=".",
                    help="directory for BENCH_obs.json + trace/metrics files")
    args = ap.parse_args()
    res = run(steps=args.steps, warmup=args.warmup, repeats=args.repeats,
              log_every=args.log_every, out_dir=args.out_dir)
    print(f"observer off: {res['off']['steps_per_s']:.2f} steps/s | "
          f"on: {res['on']['steps_per_s']:.2f} steps/s | "
          f"ratio {res['throughput_ratio']:.4f} "
          f"(overhead {res['overhead_pct']:.2f}%)")
    print(f"trace: {res['trace_events']} events -> {res['trace_path']}; "
          f"metrics: {res['metric_rows']} rows -> {res['metrics_path']}")
    print(f"probe-off parity (observed fit bitwise == unobserved): "
          f"{res['probe_off_parity']}")
    print("wrote", write_report(res, args.out_dir))


if __name__ == "__main__":
    main()
