"""Serving launcher: continuous batching with prefill/decode split on the
digital or photonic (emulated MRR) forward.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --requests 8 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b \
      --backend emu --arrival-rate 4 --bench-json serve-out

``--smoke`` (default on) builds the shrunk smoke config; ``--no-smoke``
serves the full-size model.  ``--arrival-rate`` switches from
serve-everything-at-once to Poisson open-loop arrivals, reporting
measured p50/p99 TTFT and end-to-end latency; ``--bench-json DIR``
writes the measurements as ``BENCH_serve_live.json``.
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import api, configs
from repro.serve import Request
from repro.utils import compile_cache


def _pct(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ASSIGNED))
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction, default=True,
                    help="shrunk smoke config (default); --no-smoke for full size")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "ref", "emu", "pallas"],
                    help="forward execution: auto = exact digital; emu runs "
                         "projections through the MRR device emulation")
    ap.add_argument("--hardware", default=None,
                    help="photonics preset for a photonic backend "
                         "(default: digital for auto, emu_ideal for emu)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals at this rate (req/s); default: "
                         "submit all requests up front")
    ap.add_argument("--bench-json", default=None, metavar="DIR",
                    help="write BENCH_serve_live.json with the measured "
                         "latency distribution to DIR")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Perfetto-loadable Chrome trace of the "
                         "request lifecycles and engine ticks to PATH")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="append obs metrics rows (JSONL) to PATH")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    photonic = args.backend not in ("auto",)
    hardware = args.hardware or ("emu_ideal" if photonic else "digital")
    # bp session: serving is forward-only — the facade still owns model
    # construction and the photonics/backend pairing
    session = api.build_session(arch=args.arch, smoke=args.smoke, algo="bp",
                                hardware=hardware, backend=args.backend,
                                seed=args.seed)
    observer = None
    if args.trace_out or args.metrics_out:
        observer = session.observe(metrics_path=args.metrics_out,
                                   trace_path=args.trace_out)
    model = session.model
    params = model.init(jax.random.PRNGKey(args.seed))
    vocab = model.cfg.vocab_size

    eng = session.engine(params, batch_slots=args.slots, max_len=args.max_len,
                         prefill_chunk=args.prefill_chunk, seed=args.seed)
    reqs = [Request(prompt=[(7 * i + 3 + 13 * j) % vocab
                            for j in range(max(1, args.prompt_len))],
                    max_new=args.max_new) for i in range(args.requests)]
    t0 = time.monotonic()  # duration: monotonic, immune to wall-clock steps
    if args.arrival_rate:
        rng = np.random.default_rng(args.seed)
        arrivals = np.cumsum(rng.exponential(1.0 / args.arrival_rate,
                                             size=len(reqs)))
        done, ticks = eng.run_arrivals(reqs, arrivals.tolist())
    else:
        done, ticks = eng.run(reqs)
    dt = time.monotonic() - t0

    total_tokens = sum(len(r.out) for r in done)
    ttfts = [r.ttft_s for r in done if r.ttft_s is not None]
    lats = [r.latency_s for r in done if r.latency_s is not None]
    print(f"[serve] {len(done)} requests, {total_tokens} tokens, "
          f"{ticks} ticks, {dt:.2f}s ({total_tokens / max(dt, 1e-9):.1f} tok/s) "
          f"backend={args.backend}")
    print(f"[serve] ttft p50 {_pct(ttfts, 50) * 1e3:.1f}ms "
          f"p99 {_pct(ttfts, 99) * 1e3:.1f}ms | latency "
          f"p50 {_pct(lats, 50) * 1e3:.1f}ms p99 {_pct(lats, 99) * 1e3:.1f}ms")
    print(f"[serve] engine stats: {eng.stats}")
    for r in done[:4]:
        print(f"  prompt={r.prompt[:4]}{'...' if len(r.prompt) > 4 else ''} "
              f"-> {r.out}")

    if args.bench_json:
        from repro.bench import write_bench

        metrics = {
            "requests": float(len(done)),
            "tokens": float(total_tokens),
            "wall_s": dt,
            "tok_per_s": total_tokens / max(dt, 1e-9),
            "ttft_p50_ms": _pct(ttfts, 50) * 1e3,
            "ttft_p99_ms": _pct(ttfts, 99) * 1e3,
            "latency_p50_ms": _pct(lats, 50) * 1e3,
            "latency_p99_ms": _pct(lats, 99) * 1e3,
            "prefill_steps": float(eng.stats["prefill_steps"]),
            "decode_steps": float(eng.stats["decode_steps"]),
        }
        meta = {"arch": args.arch, "backend": args.backend,
                "hardware": hardware, "smoke": args.smoke,
                "slots": args.slots, "prefill_chunk": args.prefill_chunk,
                "arrival_rate": args.arrival_rate or 0.0}
        path = write_bench("serve_live", metrics, meta, args.bench_json)
        print(f"[serve] wrote {path}")

    if observer is not None:
        trace_path = observer.close()
        if trace_path:
            print(f"[obs] wrote trace {trace_path}")
        if args.metrics_out:
            print(f"[obs] wrote metrics {args.metrics_out}")


if __name__ == "__main__":
    main()
