"""Training launcher — a thin CLI over ``repro.api.build_session``.

Small-scale (this container): runs real steps on the host devices.

  PYTHONPATH=src python -m repro.launch.train --arch mnist_mlp \
      --steps 500 --preset offchip_bpd --ckpt-dir runs/mlp

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 100 --algo dfa

``--algo`` accepts any name registered in ``repro.algos`` (bp, dfa,
dfa-fused, dfa-layerwise, plus anything a plugin registers); ``--preset``
is the photonic hardware model (including the device-level ``emu_*``
presets) and ``--backend`` the execution path (ref | pallas | emu | auto).
``--recal-every`` sets the in-situ recalibration cadence for drifting
hardware under the emu backend; ``--autotune`` (optionally with
``--power-budget-w``) lets the ``repro.sim`` schedule autotuner pick the
fastest (n_buses, tiling, f_s) for the model's DFA backward before
training starts.  Adding an algorithm or backend is a registration —
this launcher picks it up without edits.

Production-scale posture: the same step function is what launch/dryrun.py
lowers against the (pod, data, model) mesh; on a real multi-host cluster
this entrypoint would be invoked once per host under jax.distributed with
the dry-run's shardings (see DESIGN.md §5).
"""

from __future__ import annotations

import argparse

from repro import algos, api, configs
from repro.core import photonics
from repro.data import mnist, pipeline, tokens
from repro.train import SGDM
from repro.utils import compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (default: the published widths)")
    ap.add_argument("--algo", choices=algos.list_algos(), default="dfa")
    ap.add_argument("--preset", choices=list(photonics.PRESETS), default="ideal")
    ap.add_argument("--backend", choices=["auto", *photonics.BACKENDS], default="auto")
    ap.add_argument("--error-compress", choices=["none", "ternary", "int8"], default="none")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.01)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--log", default=None)
    ap.add_argument("--data-parallel", choices=["auto", "on", "off"],
                    default="auto",
                    help="shard the batch dim across all local devices "
                         "(auto: whenever >1 device exists)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="host->device input pipeline depth (0 disables)")
    ap.add_argument("--recal-every", type=int, default=None,
                    help="in-situ recalibration cadence (steps) for stateful "
                         "emu hardware; default: 500 when the device drifts")
    ap.add_argument("--n-buses", type=int, default=None,
                    help="parallel WDM buses (multi-wavelength scale-out); "
                         "default: the preset's bus count (1)")
    ap.add_argument("--autotune", action="store_true",
                    help="repro.sim schedule autotuning: pick the fastest "
                         "(n_buses, tiling, f_s) for this model's DFA "
                         "backward under --power-budget-w")
    ap.add_argument("--power-budget-w", type=float, default=None,
                    help="wall-plug power budget [W] for --autotune "
                         "(default: unconstrained)")
    ap.add_argument("--bench-json", default=None, metavar="DIR",
                    help="measure throughput and write "
                         "BENCH_train_throughput.json into DIR")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of the run "
                         "(step spans, recal events, hwmon gauges) to PATH")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append obs metrics rows (JSONL) to PATH; render "
                         "with python -m repro.obs.summarize")
    ap.add_argument("--probe-every", type=int, default=None, metavar="N",
                    help="in-situ diagnostics cadence: every N steps log "
                         "DFA-vs-BP alignment per layer (and the emu "
                         "noise budget) as observer rows — see the "
                         "alignment/noise-budget tables in summarize")
    args = ap.parse_args()
    if args.power_budget_w is not None and not args.autotune:
        ap.error("--power-budget-w only steers --autotune")
    compile_cache.enable()

    session = api.build_session(
        arch=args.arch,
        smoke=args.smoke,
        algo=args.algo,
        hardware=args.preset,
        backend=args.backend,
        error_compress=args.error_compress,
        optimizer=SGDM(lr=args.lr, momentum=args.momentum),
        seed=args.seed, ckpt_dir=args.ckpt_dir, log_path=args.log,
        log_every=max(1, args.steps // 20),
        data_parallel={"auto": "auto", "on": True, "off": False}[args.data_parallel],
        prefetch=args.prefetch,
        recalibrate_every=args.recal_every,
        n_buses=args.n_buses,
        schedule="auto" if args.autotune else None,
        power_budget_w=args.power_budget_w,
        schedule_batch=args.batch if args.autotune else None,
        probe_every=args.probe_every,
    )
    model = session.model
    observer = None
    if args.trace_out or args.metrics_out:
        observer = session.observe(metrics_path=args.metrics_out,
                                   trace_path=args.trace_out)
    if session.mesh is not None:
        print(f"[dist] data-parallel over {session.mesh.devices.size} devices")
    if session.schedule is not None:
        print(f"[sim] autotuned schedule: {session.schedule.describe()}")

    timer = None
    if args.bench_json is not None:
        from repro.bench import StepTimer, clamped_warmup

        timer = StepTimer(warmup=clamped_warmup(args.steps, 4))

    if args.arch == "mnist_mlp":
        data = mnist.load(seed=args.seed)
        print(f"[data] source={data['source']}")
        xtr, ytr = data["train"]
        xte, yte = data["test"]
        if xtr.shape[1] != model.in_dim:  # --smoke shrinks in_dim
            xtr, xte = xtr[:, :model.in_dim], xte[:, :model.in_dim]
        pipe = pipeline.ArrayClassification(xtr, ytr, args.batch, args.seed)
        state, _ = session.fit(pipe.batch, total_steps=args.steps, timer=timer)
        _report_bench(args, session, state, pipe.batch(0), timer)
        ev = session.evaluate(state, pipe.eval_batches(xte, yte, 256))
        print(f"[eval] {ev}")
    else:
        vocab = model.cfg.vocab_size
        gen = tokens.MarkovTokens(vocab, args.seq, args.batch, args.seed)

        def batch_fn(step):
            b = gen.batch(step)
            if args.arch == "whisper-small":
                import numpy as np

                rng = np.random.default_rng((args.seed, step, 7))
                b["frames"] = rng.normal(size=(args.batch, model.cfg.n_frames,
                                               model.cfg.d_model)).astype("float32") * 0.1
            if args.arch == "internvl2-2b":
                import numpy as np

                rng = np.random.default_rng((args.seed, step, 8))
                v = model.cfg.vision
                b["patch_embeds"] = rng.normal(size=(args.batch, v.n_patches,
                                                     v.d_vision)).astype("float32") * 0.1
            return b

        state, metrics = session.fit(batch_fn, total_steps=args.steps, timer=timer)
        _report_bench(args, session, state, batch_fn(0), timer)
        print(f"[final] {({k: float(v) for k, v in metrics.items()})}")

    if observer is not None:
        trace_path = observer.close()
        if trace_path:
            print(f"[obs] wrote trace {trace_path}")
        if args.metrics_out:
            print(f"[obs] wrote metrics {args.metrics_out}")
        if observer.alerts:
            print(f"[obs] {len(observer.alerts)} alert(s) "
                  "(hwmon + anomaly); first: "
                  f"{observer.alerts[0].message}")


def _report_bench(args, session, state, batch, timer):
    if timer is None:
        return
    if timer.recorded_steps == 0:
        # e.g. a checkpoint-restored fit that had nothing left to run
        print("[bench] no steps executed — skipping throughput report",
              flush=True)
        return
    from repro.bench import report_throughput

    report_throughput(
        session, state, batch, timer,
        meta={"arch": args.arch, "algo": args.algo, "preset": args.preset,
              "batch": args.batch, "steps": args.steps},
        out_dir=args.bench_json)


if __name__ == "__main__":
    main()
