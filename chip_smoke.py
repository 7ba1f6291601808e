#!/usr/bin/env python3
"""Bring-up check: the main path once on a TPU, at full published width.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # four chips: data-parallel DFA only

Run it from the root of a checkout.  It uses no network.  Everything runs
in this one process, which holds the chip.

One chip, in this order:

* ``kernels``: each Pallas kernel of the main path against its reference
  on a small input (photonic product, fused DFA gradient, fused emu bank).
* ``train-pallas``: ``api.build_session(arch="qwen1.5-0.5b", algo="dfa",
  hardware="offchip_bpd")`` with the default backend, which must resolve
  to ``pallas``; a few ``Session.fit`` steps on ``tokens.MarkovTokens``.
* ``train-emu``: the same model on ``backend="emu"``, ``emu_onchip``; the
  emu kernel must resolve to the fused Pallas kernel.
* ``mnist-emu``: the paper's own MLP, DFA on ``emu_onchip``.
* ``serve``: ``Session.engine`` on qwen1.5-0.5b answers a few requests.

``--four-chips`` runs the qwen1.5-0.5b DFA ``fit`` on ``ideal`` hardware,
data-parallel over four devices, and compares it with one chip running the
same global batch as four microbatches: loss and updated parameters must
agree to 1e-5.

Each phase prints one JSON line; the last line of stdout is
``{"ok": true, "device": {...}}``.  The script exits non-zero, and prints
no such line, when JAX finds no TPU, when a backend resolves to anything
but the Pallas kernels, when a lowered step holds no ``tpu_custom_call``,
when ``REPRO_EMU_KERNEL`` is set, or when any phase fails.

Compiles go to JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` when
set, else ``.jax_cache`` in the checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen1.5-0.5b"
SEQ = 1024
TRAIN_BATCH = 8      # sequences of SEQ tokens per step on one chip
TRAIN_STEPS = 3
MNIST_BATCH = 64
MNIST_STEPS = 4
SERVE_REQUESTS = 4
SERVE_PROMPT = 24
SERVE_NEW = 32
TOL = 1e-5           # tests/test_data_parallel.py's sharded-vs-single bound


class SmokeError(RuntimeError):
    """A check of this script failed."""


def _require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


def _import_repro():
    """Import ``repro`` from this checkout's ``src`` and nowhere else."""
    sys.path.insert(0, os.path.join(HERE, "src"))
    try:
        import repro
    except ImportError as e:
        raise SmokeError(f"repro is not importable from {HERE}/src: {e}") from e
    mine = os.path.join(HERE, "src", "repro")
    _require([os.path.abspath(p) for p in repro.__path__] == [mine],
             f"repro was imported from {list(repro.__path__)}, not {mine}")
    return repro


class CompileClock:
    """Backend-compile seconds and persistent-cache hits since ``reset``."""

    def __init__(self):
        from jax import monitoring

        self.reset()
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def reset(self):
        self.seconds = 0.0
        self.cache_hits = 0

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _emit(phase: str, clock: CompileClock, t0: float, **fields) -> None:
    row = {"phase": phase, "compile_s": round(clock.seconds, 3),
           "cache_hits": clock.cache_hits,
           "wall_s": round(time.monotonic() - t0, 3), **fields}
    print(json.dumps(row), flush=True)
    clock.reset()


def _lowered_fit_step(session, batch):
    """The jitted function ``Session.fit`` runs, lowered for this state."""
    import jax

    tr = session.trainer
    state = jax.eval_shape(tr.init_state)
    if tr.mesh is not None:
        from repro.dist import sharding

        rep = sharding.replicated(tr.mesh)
        state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep), state)
        batch = sharding.put_batch(tr.mesh, batch)
    with tr._mesh_ctx():
        return tr._fit_step_fn.lower(state, batch)


def _require_kernel(lowered, what: str) -> None:
    _require("tpu_custom_call" in lowered.as_text(),
             f"{what}: the lowered step holds no tpu_custom_call")


def _fit(session, data_fn, steps: int):
    """Session.fit with per-step losses kept by an in-memory observer."""
    from repro import obs

    observer = obs.Observer()
    state, _ = session.fit(data_fn, total_steps=steps, verbose=False,
                           observer=observer)
    rows = observer.metrics.sinks[0].rows
    losses = [r["metrics"]["loss"] for r in rows if "loss" in r["metrics"]]
    _require(len(losses) == steps, f"fit logged {len(losses)} of {steps} losses")
    _require(all(map(_finite, losses)), f"non-finite loss: {losses}")
    return state, losses


def _require_per_device_kernels(hlo: str, rows_local: int) -> int:
    """The compiled per-device program runs each kernel on its own
    ``rows_local`` rows, and never gathers the batch.  -> kernel calls."""
    calls = [ln for ln in hlo.splitlines()
             if "tpu_custom_call" in ln and "custom-call(" in ln]
    _require(calls, "the data-parallel step holds no tpu_custom_call")
    _require(all(re.search(rf"\[{rows_local},", ln) for ln in calls),
             f"a kernel call does not run on {rows_local} local rows")
    _require("all-gather" not in hlo, "the data-parallel step gathers")
    return len(calls)


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def _tokens(model, batch: int, seed: int = 0):
    from repro.data import tokens

    gen = tokens.MarkovTokens(model.cfg.vocab_size, SEQ, batch, seed)
    return gen.batch


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_kernels(clock):
    """Each kernel against its reference on a small input."""
    import jax
    import jax.numpy as jnp

    from repro.core import photonics
    from repro.hardware import channel
    from repro.kernels import emu_matmul, ops

    t0 = time.monotonic()
    ka, kb = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(ka, (256, 384), jnp.float32)
    b = jax.random.normal(kb, (200, 384), jnp.float32)
    ideal = photonics.preset("ideal")
    with jax.default_matmul_precision("highest"):
        ref = photonics.photonic_matmul(a, b, ideal)
        emu_cfg = photonics.preset("emu_ideal")
        a_n, b_n, _, _ = photonics.normalise_operands(a, b, emu_cfg)
        emu_ref = channel.bank_product(a_n, b_n, emu_cfg)
    got = {
        "photonic_matmul": (ops.photonic_matmul(a, b, ideal), ref),
        "emu_bank": (emu_matmul.fused_bank_product(a_n, b_n, emu_cfg,
                                                   impl="pallas"), emu_ref),
    }
    errs = {}
    for name, (out, want) in got.items():
        _require(out.shape == want.shape, f"{name}: shape {out.shape}")
        # relative to the output's scale: loose enough for the MXU's f32
        # passes, far tighter than any tiling or layout fault
        errs[name] = float(jnp.max(jnp.abs(out - want)) / jnp.max(jnp.abs(want)))
        _require(errs[name] < 1e-2, f"{name}: relative error {errs[name]}")
    _emit("kernels", clock, t0, rel_err=errs)


def phase_train(clock, *, hardware: str, backend: str, batch: int, phase: str):
    from repro import api
    from repro.core import photonics
    from repro.hardware import channel

    t0 = time.monotonic()
    kw = {} if backend == "auto" else {"backend": backend}
    session = api.build_session(arch=ARCH, algo="dfa", hardware=hardware,
                                log_every=1, **kw)
    be = photonics.get_backend(session.config.dfa.backend)
    resolved = be.name
    if be.stateful_hardware:
        resolved = f"{be.name}/{channel.resolve_emu_kernel(be.emu_kernel)}"
    _require(resolved in ("pallas", "emu/pallas"),
             f"{phase}: backend resolved to {resolved}")
    data = _tokens(session.model, batch)
    _require_kernel(_lowered_fit_step(session, data(0)), phase)
    _, losses = _fit(session, data, TRAIN_STEPS)
    _emit(phase, clock, t0, arch=ARCH, hardware=hardware, backend=resolved,
          batch=[batch, SEQ], steps=TRAIN_STEPS, losses=losses)


def phase_mnist(clock):
    from repro import api
    from repro.data import mnist, pipeline
    from repro.hardware import channel

    t0 = time.monotonic()
    session = api.build_session(arch="mnist_mlp", algo="dfa",
                                hardware="emu_onchip", backend="emu",
                                log_every=1)
    kernel = channel.resolve_emu_kernel()
    _require(kernel == "pallas", f"mnist-emu: emu kernel resolved to {kernel}")
    data = mnist.load(split_sizes=(4096, 512), seed=0)
    xtr, ytr = data["train"]
    pipe = pipeline.ArrayClassification(xtr, ytr, MNIST_BATCH, seed=0)
    _require_kernel(_lowered_fit_step(session, pipe.batch(0)), "mnist-emu")
    _, losses = _fit(session, pipe.batch, MNIST_STEPS)
    _emit("mnist-emu", clock, t0, arch="mnist_mlp", hardware="emu_onchip",
          backend="emu/pallas", data=data["source"], batch=MNIST_BATCH,
          steps=MNIST_STEPS, losses=losses)


def phase_serve(clock):
    from repro import api
    from repro.serve import Request

    t0 = time.monotonic()
    session = api.build_session(arch=ARCH, algo="bp", hardware="digital")
    vocab = session.model.cfg.vocab_size
    eng = session.engine(batch_slots=SERVE_REQUESTS, max_len=128,
                         prefill_chunk=16)
    reqs = [Request(prompt=[(7 * i + 3 + 13 * j) % vocab
                            for j in range(SERVE_PROMPT)],
                    max_new=SERVE_NEW) for i in range(SERVE_REQUESTS)]
    done, ticks = eng.run(reqs)
    for r in done:
        _require(r.done, f"serve: a request ended in state {r.state}")
        _require(len(r.out) == SERVE_NEW,
                 f"serve: {len(r.out)} of {SERVE_NEW} tokens")
        _require(all(0 <= t < vocab for t in r.out), "serve: token out of vocab")
    _emit("serve", clock, t0, arch=ARCH, requests=len(done),
          tokens=sum(len(r.out) for r in done), ticks=ticks,
          prefill_steps=eng.stats["prefill_steps"],
          decode_steps=eng.stats["decode_steps"])


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def phase_four_chips(clock):
    """Data-parallel DFA over four devices against one chip running the
    same global batch as four microbatches."""
    import jax
    import numpy as np

    from repro import api
    from repro.core import photonics
    from repro.dist import sharding

    n = len(jax.devices())
    t0 = time.monotonic()
    dp = api.build_session(arch=ARCH, algo="dfa", hardware="ideal",
                           log_every=1)
    _require(dp.mesh is not None and dp.mesh.devices.size == n,
             f"data-parallel session has mesh {dp.mesh}")
    resolved = photonics.get_backend(dp.config.dfa.backend).name
    _require(resolved == "pallas", f"backend resolved to {resolved}")
    data = _tokens(dp.model, n * TRAIN_BATCH)
    placed = sharding.put_batch(dp.mesh, data(0))["tokens"]
    shard_rows = sorted({s.data.shape[0] for s in placed.addressable_shards})
    _require(shard_rows == [TRAIN_BATCH],
             f"batch not split {n} ways: shard rows {shard_rows}")
    calls = _require_per_device_kernels(
        _lowered_fit_step(dp, data(0)).compile().as_text(), TRAIN_BATCH * SEQ)
    state, dp_losses = _fit(dp, data, TRAIN_STEPS)
    dp_params = jax.device_get(state["params"])
    del state, dp
    dp_s = time.monotonic() - t0
    dp_compile = clock.seconds

    clock.reset()
    t1 = time.monotonic()
    one = api.build_session(arch=ARCH, algo="dfa", hardware="ideal",
                            microbatches=n, data_parallel=False, log_every=1)
    state, mb_losses = _fit(one, data, TRAIN_STEPS)
    mb_params = jax.device_get(state["params"])
    del state, one
    loss_diff = max(abs(x - y) for x, y in zip(dp_losses, mb_losses))
    param_diff = max(float(np.max(np.abs(x - y))) for x, y in zip(
        jax.tree_util.tree_leaves(dp_params), jax.tree_util.tree_leaves(mb_params)))
    _emit("four-chips", clock, t1, arch=ARCH, hardware="ideal",
          backend=resolved, global_batch=[n * TRAIN_BATCH, SEQ],
          steps=TRAIN_STEPS, dp_wall_s=round(dp_s, 3),
          dp_compile_s=round(dp_compile, 3), dp_losses=dp_losses,
          microbatched_losses=mb_losses, max_loss_diff=loss_diff,
          max_param_diff=param_diff, kernel_calls_per_device=calls)
    _require(loss_diff < TOL and param_diff < TOL,
             f"data-parallel and microbatched runs differ: loss {loss_diff}, "
             f"params {param_diff}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-device data-parallel comparison")
    args = ap.parse_args()
    _require(not os.environ.get("REPRO_EMU_KERNEL"),
             "REPRO_EMU_KERNEL is set; the smoke checks the platform's own "
             "choice of emu kernel")
    _import_repro()
    import jax

    from repro.utils import compile_cache

    dev = jax.devices()[0]
    _require(dev.platform == "tpu", f"no TPU: JAX found {dev.platform}")
    count = len(jax.devices())
    cache_dir = compile_cache.enable()
    print(json.dumps({"phase": "start", "device_kind": dev.device_kind,
                      "devices": count, "jax": jax.__version__,
                      "compile_cache": cache_dir}), flush=True)
    clock = CompileClock()
    if args.four_chips:
        _require(count == 4, f"--four-chips needs 4 devices, found {count}")
        phase_four_chips(clock)
    else:
        _require(count == 1, f"one chip expected, found {count}")
        phase_kernels(clock)
        phase_train(clock, hardware="offchip_bpd", backend="auto",
                    batch=TRAIN_BATCH, phase="train-pallas")
        phase_train(clock, hardware="emu_onchip", backend="emu",
                    batch=TRAIN_BATCH, phase="train-emu")
        phase_mnist(clock)
        phase_serve(clock)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        sys.exit(1)
