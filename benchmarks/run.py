"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (us_per_call = wall time of the
benchmark's core computation; derived = the figure's headline quantity and
its paper anchor).  Individual modules offer richer CLIs:

  python -m benchmarks.mac_noise          (Fig. 3c)
  python -m benchmarks.mnist_accuracy     (Fig. 5b; --full for paper scale)
  python -m benchmarks.resolution_sweep   (Fig. 5c)
  python -m benchmarks.energy             (Fig. 6 / Eq. 2)
  python -m benchmarks.gemm_cycles        (§3 GeMM compiler)
  python -m benchmarks.dfa_vs_bp          (§1 claim)
  python -m benchmarks.roofline           (deliverable g; --bench auto-
                                           generates results/dryrun.json)
  python -m benchmarks.pipeline_sim       (repro.sim timing study)
  python -m benchmarks.emu_kernel         (fused emu-kernel speedup)

``--smoke`` instead runs one ``repro.api.build_session(...).fit`` step for
EVERY algorithm registered in ``repro.algos`` (mnist_mlp smoke arch) — the
registry's rot check: a newly registered algorithm that can't complete a
training step fails here (and in tests/test_api_smoke.py) immediately —
plus one fit step through the device-level "emu" backend, plus a reduced
``benchmarks.mac_noise`` sweep checking the measured per-MAC effective
bits against the paper's Fig. 3(c) values.  Exit code is the gate:
nonzero when any of them fails.

``--bench`` measures training throughput (repro.bench.StepTimer over a
data-parallel ``Session.fit``) and writes ``BENCH_train_throughput.json``
plus the drift/recalibration study (``benchmarks.drift_recovery``) as
``BENCH_hardware.json``, the multi-wavelength scale-out sweep
(``benchmarks.bus_scaling``) as ``BENCH_bus_scaling.json``, the repro.sim
timing study (``benchmarks.pipeline_sim``) as ``BENCH_pipeline.json``,
the roofline + photonic-backward parity numbers (auto-generating the
dry-run record when missing) as ``BENCH_roofline.json``, and the
request-level serving study (``benchmarks.serving``: p50/p99 TTFT and
latency, requests/s and J/request vs offered load + the SLO-constrained
serving autotuner) as ``BENCH_serving.json``, and the fused emu-kernel
study (``benchmarks.emu_kernel``: fused vs unfused steps/s and MACs/s
plus the measured-feedback schedule co-tuning) as
``BENCH_emu_kernel.json``, and the observability overhead study
(``benchmarks.obs_overhead``: observer-off vs observer-on fit throughput
on the fused emu step, with the run's Chrome trace + metrics JSONL as
artifacts) as ``BENCH_obs.json``, and the diagnostics-plane study
(``benchmarks.alignment``: DFA-vs-BP alignment curves, the emu
noise-budget attribution + closure check, probe-on vs probe-off
throughput) as ``BENCH_alignment.json``; combined with ``--smoke`` it also
writes ``BENCH_smoke.json``.  CI archives the ``BENCH_*.json`` files — they are
the repo's perf trajectory, and ``benchmarks/check_regression.py`` gates
changes against the committed ``benchmarks/baselines/``.
"""

from __future__ import annotations

import argparse
import importlib
import os
import time


def _sibling(name: str):
    """Import a sibling benchmark module under either invocation style:
    ``python -m benchmarks.run`` (package) or ``python benchmarks/run.py``
    (CI — sys.path[0] is the benchmarks dir itself)."""
    try:
        return importlib.import_module(f"benchmarks.{name}")
    except ModuleNotFoundError:
        return importlib.import_module(name)


def _timed(fn):
    t0 = time.monotonic()
    out = fn()
    return (time.monotonic() - t0) * 1e6, out


def fig3c_mac_noise():
    from benchmarks.mac_noise import run

    us, rows = _timed(lambda: run(n=3900))
    best = {r["preset"]: r["measured_bits"] for r in rows}
    derived = ("bits[single_mrr]=%.2f(paper 6.72) offchip=%.2f(4.35) "
               "onchip=%.2f(3.31)" % (best["single_mrr"], best["offchip_bpd"],
                                      best["onchip_bpd"]))
    return us, derived


def fig5b_mnist_noise_robustness():
    from benchmarks.mnist_accuracy import run

    us, rows = _timed(lambda: run(train_n=16384, test_n=4096, steps=1024,
                                  hidden=(800, 800)))
    acc = {r["preset"]: r["test_accuracy"] for r in rows}
    src = rows[0]["source"]
    derived = ("acc%%[%s]: ideal=%.2f offchip=%.2f onchip=%.2f "
               "(paper@MNIST: 98.10/97.41/96.33)"
               % (src, acc["ideal"], acc["offchip_bpd"], acc["onchip_bpd"]))
    return us, derived


def fig5c_resolution_sweep():
    from benchmarks.resolution_sweep import run

    us, rows = _timed(lambda: run(bits_list=(3.31, 4.35, 8.0), steps=256))
    pts = " ".join(f"{r['bits']}b={r['test_accuracy']:.1f}%" for r in rows)
    return us, f"acc vs resolution: {pts} (robust >=3.31b per paper)"


def fig6_energy_model():
    from benchmarks.energy import headline

    us, h = _timed(headline)
    return us, ("tops=%.1f(paper 20) pJ_heat=%.2f(1.0) pJ_trim=%.2f(0.28) "
                "tops_mm2=%.2f(5.78)" % (h["tops_50x20"], h["pj_heaters"],
                                         h["pj_trimming"], h["tops_mm2"]))


def tab_gemm_cycles():
    from benchmarks.gemm_cycles import run

    us, rows = _timed(run)
    mlp = rows[0]
    return us, ("paper MLP backward: %d cycles %.1f ns on 50x20 bank "
                "(%.1f TOPS)" % (mlp["cycles"], mlp["seconds"] * 1e9, mlp["tops"]))


def tab_dfa_vs_bp():
    from benchmarks.dfa_vs_bp import run

    us, rows = _timed(lambda: run(steps=768))
    d = {r["algo"]: r["test_accuracy"] for r in rows}
    return us, ("dfa=%.2f%% bp=%.2f%% align(h0)=%.2f align(h1)=%.2f"
                % (d["dfa"], d["bp"], d["alignment_h0"], d["alignment_h1"]))


def tab_ternary_error():
    from benchmarks.ternary_error import run

    us, rows = _timed(lambda: run(steps=384))
    d = {r["error_compress"]: r["test_accuracy"] for r in rows}
    return us, ("acc%%: full=%.2f int8=%.2f ternary=%.2f "
                "(int8 lossless at 1/4 broadcast; ternary trades accuracy "
                "at short horizons — ref[48] closes the gap at scale)"
                % (d["none"], d["int8"], d["ternary"]))


def tab_dfa_pipeline_latency():
    sim_rows = _sibling("dfa_pipeline_latency").sim_rows

    us, rows = _timed(sim_rows)
    if not rows:
        return us, "SKIP (no results/dryrun.json)"
    r = rows[0]
    return us, ("photonic DFA backward (repro.sim): %s %.3fs vs BP bwd "
                "%.3fs -> %.0f buses for parity"
                % (r["arch"], r["t_dfa_bwd_sim_s"], r["t_bp_bwd_s"],
                   r["buses_for_parity"]))


def tab_roofline():
    path = os.environ.get("REPRO_DRYRUN_JSON", "results/dryrun.json")
    if not os.path.exists(path):
        return 0.0, f"SKIP (no {path}; run python -m repro.launch.dryrun)"
    from benchmarks.roofline import roofline_rows

    us, rows = _timed(lambda: roofline_rows(path, "single"))
    ok = [r for r in rows if r["status"] == "ok"]
    worst = min(ok, key=lambda r: r["compute_fraction"])
    best = max(ok, key=lambda r: r["compute_fraction"])
    return us, ("%d cells; compute-fraction best=%.2f(%s/%s) worst=%.2f(%s/%s)"
                % (len(ok), best["compute_fraction"], best["arch"], best["shape"],
                   worst["compute_fraction"], worst["arch"], worst["shape"]))


def tab_bus_scaling():
    from benchmarks.bus_scaling import bench_metrics, run

    us, rows = _timed(lambda: run(steps=64))
    m = bench_metrics(rows)
    return us, ("%d-bus LM backward: %.1fx cycle speedup, acc spread "
                "%.2fpts, pJ/MAC %.2f->%.2f"
                % (max(r["n_buses"] for r in rows), m["cycle_speedup"],
                   m["acc_spread_pts"],
                   m[f"pj_per_mac_b{min(r['n_buses'] for r in rows)}"],
                   m[f"pj_per_mac_b{max(r['n_buses'] for r in rows)}"]))


def tab_drift_recovery():
    from benchmarks.drift_recovery import bench_metrics, run

    us, rows = _timed(lambda: run(steps=128))
    m = bench_metrics(rows)
    return us, ("emu-vs-ref gap=%.2fpts; drift costs %.2fpts, "
                "recalibration recovers %.2fpts"
                % (m["emu_vs_ref_gap_pts"], m["drift_cost_pts"],
                   m["recal_recovery_pts"]))


TABLES = [
    ("fig3c_mac_noise", fig3c_mac_noise),
    ("fig5b_mnist_noise_robustness", fig5b_mnist_noise_robustness),
    ("fig5c_resolution_sweep", fig5c_resolution_sweep),
    ("fig6_energy_model", fig6_energy_model),
    ("tab_gemm_cycles", tab_gemm_cycles),
    ("tab_dfa_vs_bp", tab_dfa_vs_bp),
    ("tab_ternary_error", tab_ternary_error),
    ("tab_dfa_pipeline_latency", tab_dfa_pipeline_latency),
    ("tab_bus_scaling", tab_bus_scaling),
    ("tab_drift_recovery", tab_drift_recovery),
    ("tab_roofline", tab_roofline),
]


def _smoke_mac_noise(n: int = 1024, tolerance_bits: float = 0.5):
    """Reduced Fig. 3(c) sweep: every preset's measured effective bits must
    land within ``tolerance_bits`` of the paper's value — the noise-model
    calibration rot check (previously orphaned from CI)."""
    run = _sibling("mac_noise").run

    worst = 0.0
    for r in run(n=n):
        worst = max(worst, abs(r["measured_bits"] - r["paper_bits"]))
    if worst > tolerance_bits:
        raise AssertionError(
            f"mac-noise calibration off by {worst:.2f} bits "
            f"(> {tolerance_bits})")
    return worst


def smoke(bench_dir: str | None = None) -> int:
    """One fit step per registered algorithm through repro.api (plus the
    device-level "emu" backend and the mac-noise calibration check);
    returns the number of failures (the CLI exit code — CI gates on it).
    With ``bench_dir`` the per-algorithm timings land in BENCH_smoke.json."""
    import jax

    from repro import algos, api

    failures = 0
    rows = []
    cells = [(name, {}) for name in algos.list_algos()]
    # the hardware-emulation backend through the same rot check (drifting
    # device + in-situ calibration exercised by the fit step)
    cells.append(("dfa@emu", {"backend": "emu", "hardware": "emu_onchip",
                              "recalibrate_every": 1}))
    print("smoke: algo,us_per_call,loss")
    for name, extra in cells:
        try:
            session = api.build_session(arch="mnist_mlp", algo=name.split("@")[0],
                                        smoke=True, log_every=10**9, **extra)
            kx, ky = jax.random.split(jax.random.PRNGKey(0))
            batch = {
                "x": jax.random.normal(kx, (16, session.model.in_dim)),
                "y": jax.random.randint(ky, (16,), 0, session.model.n_classes),
            }
            us, (state, metrics) = _timed(
                lambda: session.fit(lambda step: batch, total_steps=1,
                                    verbose=False))
            # one scalar read per cell, outside the timed region
            loss = float(metrics["loss"])  # lint: disable=RL002
            rows.append({"algo": name, "us_per_fit_step": us, "loss": loss})
            print(f"{name},{us:.0f},{loss:.4f}", flush=True)
        except Exception as ex:
            failures += 1
            rows.append({"algo": name, "error": f"{type(ex).__name__}: {str(ex)[:200]}"})
            print(f"{name},0,ERROR {type(ex).__name__}: {str(ex)[:120]}", flush=True)
    try:
        us, worst = _timed(_smoke_mac_noise)
        print(f"mac_noise,{us:.0f},worst_bits_err={worst:.3f}", flush=True)
    except Exception as ex:
        failures += 1
        print(f"mac_noise,0,ERROR {type(ex).__name__}: {str(ex)[:120]}",
              flush=True)
    if bench_dir is not None:
        from repro.bench import write_bench

        ok = [r for r in rows if "error" not in r]
        path = write_bench(
            "smoke",
            {"algorithms": len(rows), "failures": failures,
             "mean_us_per_fit_step":
                 sum(r["us_per_fit_step"] for r in ok) / max(len(ok), 1)},
            meta={"rows": rows}, out_dir=bench_dir)
        print(f"[bench] wrote {path}", flush=True)
    return failures


def bench_throughput(out_dir: str = ".", steps: int = 32, batch: int = 256,
                     algo: str = "dfa", arch: str = "mnist_mlp") -> str:
    """Measure data-parallel training throughput and write
    BENCH_train_throughput.json (steps/s, examples/s, model MACs/s)."""
    import numpy as np

    from repro import api
    from repro.bench import StepTimer, clamped_warmup, report_throughput
    from repro.data import pipeline

    session = api.build_session(arch=arch, algo=algo, smoke=True,
                                log_every=10**9)
    rng = np.random.default_rng(0)
    n = batch * 4
    x = rng.normal(size=(n, session.model.in_dim)).astype("float32")
    y = rng.integers(0, session.model.n_classes, size=(n,)).astype("int32")
    pipe = pipeline.ArrayClassification(x, y, batch_size=batch, seed=0)
    timer = StepTimer(warmup=clamped_warmup(steps, max(2, steps // 8)))
    state, _ = session.fit(pipe.batch, total_steps=steps, verbose=False,
                           timer=timer)
    path, _summary = report_throughput(
        session, state, pipe.batch(0), timer,
        meta={"arch": arch, "algo": algo, "batch": batch, "steps": steps},
        out_dir=out_dir)
    return path


def bench_hardware(out_dir: str = ".", steps: int = 192) -> str:
    """Run the drift/recalibration study and write BENCH_hardware.json."""
    dr = _sibling("drift_recovery")

    path = dr.write_report(dr.run(steps=steps), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_bus_scaling(out_dir: str = ".", steps: int = 96) -> str:
    """Run the multi-wavelength scale-out sweep and write
    BENCH_bus_scaling.json (accuracy / cycles / pJ-per-MAC vs bus count)."""
    bs = _sibling("bus_scaling")

    path = bs.write_report(bs.run(steps=steps), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_pipeline(out_dir: str = ".") -> str:
    """Run the repro.sim pipeline study (latency / MACs-per-s / occupancy /
    pJ-per-MAC vs bus count + the autotuner's pick) and write
    BENCH_pipeline.json."""
    ps = _sibling("pipeline_sim")

    path = ps.write_report(ps.run(), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_serving(out_dir: str = ".") -> str:
    """Run the request-level serving study (p50/p99 TTFT + latency,
    requests/s, J/request vs offered load, plus the SLO-constrained
    serving autotuner) and write BENCH_serving.json."""
    sv = _sibling("serving")

    path = sv.write_report(sv.run(), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_emu_kernel(out_dir: str = ".", steps: int = 3) -> str:
    """Run the fused emu-kernel study (ref vs fused-xla step time on the
    qwen1.5-0.5b-shaped DFA backward + the measured-feedback schedule
    co-tuning) and write BENCH_emu_kernel.json."""
    ekb = _sibling("emu_kernel")

    path = ekb.write_report(ekb.run(steps=steps, warmup=1), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_obs(out_dir: str = ".", steps: int = 96) -> str:
    """Run the observability overhead study (observer-off vs observer-on
    fit throughput on the fused emu step, trace + metrics artifacts) and
    write BENCH_obs.json."""
    ob = _sibling("obs_overhead")

    path = ob.write_report(ob.run(steps=steps, out_dir=out_dir), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def bench_alignment(out_dir: str = ".", steps: int = 160) -> str:
    """Run the diagnostics-plane study (DFA-vs-BP alignment curves on ref
    + emu_onchip MNIST fits, the emu noise-budget attribution with its
    closure check, probe-on vs probe-off throughput) and write
    BENCH_alignment.json plus the archived diagnostics JSONL."""
    al = _sibling("alignment")

    path = al.write_report(al.run(steps=steps, out_dir=out_dir), out_dir)
    print(f"[bench] wrote {path}", flush=True)
    return path


def _dryrun_path(out_dir: str = ".") -> str:
    """Where the roofline's dry-run record lives: the env override, an
    existing local ``results/dryrun.json``, else INSIDE the bench dir —
    auto-generation must not scatter side-outputs relative to the CWD
    when the caller asked for everything under ``--bench-dir``."""
    override = os.environ.get("REPRO_DRYRUN_JSON")
    if override:
        return override
    legacy = os.path.join("results", "dryrun.json")
    if os.path.exists(legacy):
        return legacy
    return os.path.join(out_dir, "dryrun.json")


def _ensure_dryrun(path: str, arch: str = "qwen1.5-0.5b") -> str:
    """Auto-generate the dry-run record the roofline needs (one train cell
    on the single-pod mesh, ~10 s) when none exists yet.  Runs in a
    subprocess: repro.launch.dryrun forces 512 placeholder devices at
    import, which must not leak into this process's jax."""
    import subprocess
    import sys

    if os.path.exists(path):
        return path
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    # the dry-run studies placeholder CPU devices; this process may hold
    # the accelerator, which a child cannot share
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
         "--shape", "train_4k", "--mesh", "single", "--out", path],
        check=True, env=env)
    return path


def bench_roofline(out_dir: str = ".") -> str:
    """Wire the (previously orphaned) roofline + DFA-pipeline-latency
    studies into the bench trajectory: auto-generate the dry-run record if
    missing, then write BENCH_roofline.json (per-cell roofline terms plus
    the repro.sim photonic-backward parity numbers)."""
    rl = _sibling("roofline")
    dpl = _sibling("dfa_pipeline_latency")
    from repro.bench import write_bench

    path = _ensure_dryrun(_dryrun_path(out_dir))
    rows = rl.roofline_rows(path, "single")
    sim_rows = dpl.sim_rows(path, "single")
    metrics = {}
    for r in rows:
        if r["status"] != "ok":
            continue
        p = r["arch"].replace(".", "_").replace("-", "_")
        metrics[f"{p}_{r['shape']}_compute_fraction"] = r["compute_fraction"]
        metrics[f"{p}_{r['shape']}_t_compute_s"] = r["t_compute_s"]
        metrics[f"{p}_{r['shape']}_t_memory_s"] = r["t_memory_s"]
    for r in sim_rows:
        p = r["arch"].replace(".", "_").replace("-", "_")
        metrics[f"{p}_{r['shape']}_dfa_bwd_sim_s"] = r["t_dfa_bwd_sim_s"]
        metrics[f"{p}_{r['shape']}_buses_for_parity"] = r["buses_for_parity"]
    if not metrics:
        raise RuntimeError(f"no ok roofline cells in {path}")
    out = write_bench("roofline", metrics,
                      meta={"dryrun": path, "rows": rows,
                            "sim_rows": sim_rows}, out_dir=out_dir)
    print(f"[bench] wrote {out}", flush=True)
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one build_session().fit step per registered algorithm")
    ap.add_argument("--bench", action="store_true",
                    help="record BENCH_*.json throughput telemetry")
    ap.add_argument("--bench-dir", default=".",
                    help="directory for BENCH_*.json output")
    ap.add_argument("--bench-steps", type=int, default=32)
    ap.add_argument("--bench-batch", type=int, default=256)
    ap.add_argument("--bench-algo", default="dfa")
    ap.add_argument("--hardware-steps", type=int, default=192,
                    help="training steps per drift_recovery variant")
    ap.add_argument("--bus-steps", type=int, default=96,
                    help="training steps per bus_scaling cell")
    args = ap.parse_args()
    from repro.utils import compile_cache

    compile_cache.enable()
    if args.smoke:
        failures = smoke(bench_dir=args.bench_dir if args.bench else None)
        if failures or not args.bench:
            raise SystemExit(min(failures, 1))
        # --smoke --bench: smoke passed — continue to the throughput bench
    if args.bench:
        bench_throughput(out_dir=args.bench_dir, steps=args.bench_steps,
                         batch=args.bench_batch, algo=args.bench_algo)
        bench_hardware(out_dir=args.bench_dir, steps=args.hardware_steps)
        bench_bus_scaling(out_dir=args.bench_dir, steps=args.bus_steps)
        bench_pipeline(out_dir=args.bench_dir)
        bench_roofline(out_dir=args.bench_dir)
        bench_serving(out_dir=args.bench_dir)
        bench_emu_kernel(out_dir=args.bench_dir)
        bench_obs(out_dir=args.bench_dir)
        bench_alignment(out_dir=args.bench_dir)
        return
    print("name,us_per_call,derived")
    for name, fn in TABLES:
        try:
            us, derived = fn()
            print(f"{name},{us:.0f},{derived}", flush=True)
        except Exception as ex:  # keep the harness going
            print(f"{name},0,ERROR {type(ex).__name__}: {str(ex)[:120]}", flush=True)


if __name__ == "__main__":
    main()
