"""Trainer: jit'd train step (any algorithm registered in repro.algos:
bp, dfa, dfa-layerwise, ...), microbatch accumulation,
data-parallel batch sharding over the local device mesh, fault-tolerant
fit loop with checkpoint/auto-resume, straggler deadline hooks, CSV
metric logging, and optional throughput telemetry (repro.bench).

Data-parallel contract: with ``data_parallel`` on (default "auto": enabled
whenever more than one local device exists) the Trainer builds a 1-D data
mesh (launch/mesh.make_data_mesh), replicates the carried state, shards the
batch dim via dist.sharding.make_batch_shardings, and jits the fit step with
the carried state donated.  DFA's feedback projection is per-example, so the
only cross-device communication is the mean all-reduce over per-shard
gradients that the SPMD partitioner inserts — numerics match single-device
training up to float reduction order (tests/test_data_parallel.py).
Microbatch accumulation composes: the global batch is split over devices
first, microbatches second.

Hardware-in-the-loop contract: when the photonic backend consumes device
state (``PhotonicBackend.stateful_hardware``, e.g. the "emu" MRR emulation)
the Trainer carries a per-ring hardware pytree in ``state["hw"]`` —
resonance drift (OU process) plus the controller's calibration estimate.
Each step advances it (``repro.hardware.calibrate.advance``; recalibration
sweeps every ``TrainerConfig.recalibrate_every`` steps) and exposes it to
the projection via ``repro.hardware.drift.use_state``, all inside the same
jitted step — so long runs degrade (and recover) realistically, and the
state checkpoints/replicates/donates like any other training state.

Fault-tolerance contract: all training randomness (photonic noise, data
order) is a pure function of (seed, step), so `restore()` + `fit()` replays
identically after a crash — verified by tests/test_checkpoint.py.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import typing

import jax
import jax.numpy as jnp

from repro import algos
from repro import obs as obs_lib
from repro.algos.dfa import DFAConfig
from repro.core import photonics
from repro.data.pipeline import DevicePrefetcher
from repro.dist import sharding
from repro.hardware import calibrate as hw_calibrate
from repro.hardware import drift as hw_drift
from repro.lint import runtime as lint_runtime
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import SGDM
from repro.utils import prng


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    algo: str = "dfa"  # any name in algos.list_algos()
    dfa: DFAConfig = dataclasses.field(default_factory=DFAConfig)
    optimizer: typing.Any = dataclasses.field(default_factory=SGDM)
    seed: int = 0
    microbatches: int = 1
    # data-parallel scale-out: "auto" shards the batch over all local
    # devices when more than one exists; True forces a mesh (even of one
    # device); False keeps the original single-device path bit-for-bit.
    data_parallel: bool | str = "auto"
    # host->device pipeline depth for fit's input feeding (0 disables).
    prefetch: int = 2
    # in-situ calibration cadence for stateful photonic hardware (the "emu"
    # backend): a calibration sweep re-measures per-ring drift every this
    # many steps (0 = never — drift accumulates uncompensated).
    recalibrate_every: int = 0
    ckpt_dir: str | None = None
    ckpt_every: int = 500
    keep_ckpts: int = 3
    log_every: int = 50
    log_path: str | None = None
    # straggler mitigation: per-step wall deadline (None = off). On real
    # multi-host deployments a step exceeding the deadline raises through
    # the supervisor which restarts the slow host from the last snapshot.
    step_deadline_s: float | None = None
    # in-situ diagnostics cadence (obs.introspect.AlignmentProbe): every
    # this many steps fit() computes the true BP gradient on the step's
    # own batch and logs DFA-vs-BP alignment (plus the emu noise budget)
    # through the observer.  None/0 = off — the probe never consumes
    # training PRNG keys, so probed and unprobed runs are bit-identical.
    probe_every: int | None = None
    # opt-in runtime sanitizers (repro.lint.runtime): checkify the jitted
    # train step (NaN/Inf, div-by-zero, OOB indexing + the emu channel's
    # check_finite assertions) and fail on any retrace after warmup.
    debug_checks: bool = False


def _resolve_data_parallel(flag) -> bool:
    if isinstance(flag, str):
        if flag == "auto":
            return jax.local_device_count() > 1
        if flag in ("on", "true"):
            return True
        if flag in ("off", "false"):
            return False
        raise ValueError(
            "data_parallel must be a bool, 'auto', 'on', or 'off'; "
            f"got {flag!r}")
    return bool(flag)


class Trainer:
    def __init__(self, model, cfg: TrainerConfig):
        self.model = model
        self.cfg = cfg
        self.algorithm = algos.get(cfg.algo)
        self._vg = self.algorithm.value_and_grad(model, cfg.dfa)
        self.mesh = None
        if _resolve_data_parallel(cfg.data_parallel):
            from repro.launch.mesh import make_data_mesh

            self.mesh = make_data_mesh()
        # stateful photonic hardware (drift + calibration): only backends
        # that consume device state get a carried "hw" pytree
        self._hw_stateful = photonics.get_backend(
            cfg.dfa.backend).stateful_hardware
        # step() keeps a non-donating jit — callers re-use the state they
        # pass in (metrics probes, tests); fit() owns its carried state and
        # donates it so XLA updates parameters in place.
        self._sentinels: dict = {}
        if cfg.debug_checks:
            step_body, s_step = lint_runtime.instrument(
                self._train_step, "Trainer.step")
            fit_body, s_fit = lint_runtime.instrument(
                self._train_step, "Trainer.fit_step")
            self._step_fn = jax.jit(step_body)
            self._fit_step_fn = jax.jit(fit_body, donate_argnums=(0,))
            self._sentinels = {"step": s_step, "fit_step": s_fit}
        else:
            self._step_fn = jax.jit(self._train_step)
            self._fit_step_fn = jax.jit(self._train_step, donate_argnums=(0,))
        self.ckpt = CheckpointManager(cfg.ckpt_dir, cfg.keep_ckpts) if cfg.ckpt_dir else None
        self._log_file = None
        self._log_keys = None
        self._probe = None  # lazily-built AlignmentProbe (jit cache survives fits)

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return sharding.use_mesh(self.mesh)

    # ---------- state ----------
    def init_state(self, key=None):
        key = key if key is not None else prng.key(self.cfg.seed)
        params = self.model.init(key)
        fb = self.algorithm.init_extra_state(
            self.model, prng.fold_name(key, "feedback"), self.cfg.dfa)
        opt_state = self.cfg.optimizer.init(params)
        state = {"params": params, "fb": fb, "opt": opt_state,
                 "step": jnp.zeros((), jnp.int32)}
        if self._hw_stateful:
            state["hw"] = hw_drift.init_state(
                self.cfg.dfa.photonics, prng.fold_name(key, "hardware"))
        return state

    # ---------- core step ----------
    def _grads(self, params, fb, batch, rng):
        mb = self.cfg.microbatches
        if mb <= 1:
            return self._vg(params, fb, batch, rng)

        def split(x):
            return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

        batches = jax.tree_util.tree_map(split, batch)

        def body(carry, xs):
            acc, metrics_acc = carry
            micro, i = xs
            (loss, metrics), grads = self._vg(params, fb, micro, jax.random.fold_in(rng, i))
            acc = jax.tree_util.tree_map(jnp.add, acc, grads)
            metrics_acc = jax.tree_util.tree_map(jnp.add, metrics_acc, metrics)
            return (acc, metrics_acc), loss

        (l0, m0), g0 = self._vg(
            params, fb, jax.tree_util.tree_map(lambda x: x[0], batches),
            jax.random.fold_in(rng, 0))
        rest = jax.tree_util.tree_map(lambda x: x[1:], batches)
        (gsum, msum), losses = jax.lax.scan(
            body, (g0, m0), (rest, jnp.arange(1, mb)))
        grads = jax.tree_util.tree_map(lambda g: g / mb, gsum)
        metrics = jax.tree_util.tree_map(lambda m: m / mb, msum)
        loss = (l0 + jnp.sum(losses)) / mb
        return (loss, metrics), grads

    def _train_step(self, state, batch):
        rng = prng.step_key(self.cfg.seed, state["step"], "noise")
        hw = state.get("hw")
        if hw is not None:
            # advance the physical device (drift + calibration sweeps) and
            # expose it to the photonic projections inside this trace
            hw = hw_calibrate.advance(
                hw, self.cfg.dfa.photonics, state["step"],
                prng.step_key(self.cfg.seed, state["step"], "hardware"),
                recalibrate_every=self.cfg.recalibrate_every)
            hw_ctx = hw_drift.use_state(hw)
        else:
            hw_ctx = contextlib.nullcontext()
        with hw_ctx:
            (loss, metrics), grads = self._grads(state["params"], state["fb"], batch, rng)
        new_params, new_opt, info = self.cfg.optimizer.update(
            grads, state["opt"], state["params"])
        metrics = dict(metrics)
        metrics.update(info)
        new_state = {"params": new_params, "fb": state["fb"], "opt": new_opt,
                     "step": state["step"] + 1}
        if hw is not None:
            new_state["hw"] = hw
            device = self.cfg.dfa.photonics.mrr
            # hw gauges only when the device actually drifts: a drift-free
            # bank (emu_ideal, or an abstract-noise emu config) carries hw
            # state that is identically zero, and emitting all-zero
            # hw_residual_rms rows would just feed hwmon vacuous data
            if device is not None and device.stateful:
                resid = hw_drift.residual(hw)
                metrics["hw_drift_rms"] = jnp.sqrt(jnp.mean(jnp.square(hw["drift"])))
                metrics["hw_residual_rms"] = jnp.sqrt(jnp.mean(jnp.square(resid)))
                # rings whose uncompensated detuning left the usable range —
                # the hwmon dead-ring gauge, computed on device so the host
                # never touches the full (n_buses, rows, cols) grid
                thresh = obs_lib.hwmon.DEAD_RING_FACTOR * device.drift_sigma
                metrics["hw_dead_rings"] = jnp.sum(
                    jnp.abs(resid) > thresh).astype(jnp.float32)
        return new_state, metrics

    def _dispatch(self, state, batch, step_fn):
        t0 = time.monotonic()
        with self._mesh_ctx():
            if self.cfg.debug_checks:
                err, (state, metrics) = step_fn(state, batch)
                err.throw()  # surfaces checkify findings as JaxRuntimeError
            else:
                state, metrics = step_fn(state, batch)
        if self.cfg.step_deadline_s is not None:
            jax.block_until_ready(state["step"])
            dt = time.monotonic() - t0
            if dt > self.cfg.step_deadline_s:
                raise TimeoutError(
                    f"step {int(state['step'])} exceeded deadline "
                    f"({dt:.1f}s > {self.cfg.step_deadline_s}s) — straggler")
        return state, metrics

    def step(self, state, batch):
        if self.mesh is not None:
            batch = sharding.put_batch(self.mesh, batch)
        return self._dispatch(state, batch, self._step_fn)

    # ---------- cost model ----------
    def step_cost(self, state, batch):
        """Trip-count-aware HLO cost of one train step (utils.hlo_cost):
        PER-DEVICE flops / HBM-proxy bytes / collective bytes of the
        optimized, post-SPMD module.  Feeds the bench MACs/s metric."""
        from repro.utils import hlo_cost

        if self.mesh is not None:
            state = sharding.replicate(self.mesh, state)
            batch = sharding.put_batch(self.mesh, batch)
        with self._mesh_ctx():
            compiled = self._step_fn.lower(state, batch).compile()
        return hlo_cost.analyze(compiled.as_text())

    # ---------- loop ----------
    def restore_or_init(self, key=None):
        state = self.init_state(key)
        if self.ckpt is not None:
            restored, step = self.ckpt.restore(state)
            if restored is not None:
                return restored, int(step)
        return state, 0

    def _log(self, step, row):
        """Append one CSV row of already-host-side floats (the fit loop
        drains device metrics with one batched ``jax.device_get`` before
        calling this — never one blocking transfer per scalar)."""
        if self.cfg.log_path is None:
            return
        if self._log_file is None:
            os.makedirs(os.path.dirname(os.path.abspath(self.cfg.log_path)), exist_ok=True)
            new = not os.path.exists(self.cfg.log_path)
            self._log_file = open(self.cfg.log_path, "a")
            self._log_keys = sorted(row)
            if new:
                self._log_file.write("step," + ",".join(self._log_keys) + "\n")
        self._log_file.write(
            f"{step}," + ",".join(str(row.get(k, "nan"))
                                  for k in self._log_keys) + "\n")
        self._log_file.flush()

    def _make_feed(self, data_fn, total_steps: int, observer):
        """Wrap data_fn with the device-put (sharded under a mesh) and the
        double-buffered prefetcher so fit's input feeding is off-path;
        each call of either is a span (``train.data_fn``, ``train.put``)."""
        put_batch = jax.device_put
        if self.mesh is not None:
            put_batch = lambda batch: sharding.put_batch(self.mesh, batch)  # noqa: E731

        def load(step):
            with observer.span("train.data_fn"):
                return data_fn(step)

        def put(batch):
            with observer.span("train.put"):
                return put_batch(batch)

        if self.cfg.prefetch <= 0:
            return lambda step: put(load(step))
        return DevicePrefetcher(load, put_fn=put, depth=self.cfg.prefetch,
                                limit=total_steps)

    def fit(self, data_fn, total_steps: int, eval_fn=None, verbose=True,
            timer=None, observer=None):
        """data_fn(step) -> batch (deterministic — restart-safe).

        ``timer`` is an optional repro.bench.StepTimer; when given, each
        step is synced (block_until_ready) and its wall time recorded —
        bench-only, since the sync serializes dispatch.

        Spans, on the profiler's clock whatever the observer:
        ``train.step`` (one loop iteration, with its step number) holds
        ``train.dispatch`` (the jitted step's enqueue), ``train.probe``
        and the logging interval's ``train.drain``; ``train.data_fn`` and
        ``train.put`` wrap each batch made and enqueued, which the
        prefetcher does up to ``cfg.prefetch`` steps ahead.

        ``observer`` is an optional ``repro.obs.Observer``: it records
        those spans in its Chrome trace too, recalibration steps as an
        instant event, and drains each logging interval's device metrics
        through ``observer.log_step`` (one batched ``jax.device_get``,
        hwmon gauges + drift-budget alerts included).  ``None`` resolves
        to the shared null observer, whose spans are bare annotations.

        With ``cfg.probe_every`` set, every probe_every-th step first
        runs the ``obs.introspect.AlignmentProbe`` on the step's own
        (state, batch): DFA-vs-BP alignment, grad norms, and (on
        stateful hardware) the ``obs.attribution`` noise budget land as
        an extra observer row at that step.  The probe re-derives its
        keys from (seed, step) and never donates, so training states are
        bit-identical with the probe on or off.
        """
        observer = obs_lib.resolve(observer)
        probe = None
        if self.cfg.probe_every:
            if self._probe is None:
                from repro.obs.introspect import AlignmentProbe

                self._probe = AlignmentProbe(self)
            probe = self._probe
            if not observer.enabled:
                # probe rows need somewhere to land: an in-memory observer
                # (MemorySink ring) keeps the no-observer call signature
                observer = obs_lib.Observer()
        state, start = self.restore_or_init()
        if self.mesh is not None:
            state = sharding.replicate(self.mesh, state)
        feed = self._make_feed(data_fn, total_steps, observer)
        metrics = {}
        recal = self.cfg.recalibrate_every if self._hw_stateful else 0
        if timer is not None:
            timer.start()
        try:
            for step in range(start, total_steps):
                with observer.step_span("train.step", step):
                    batch = feed(step)
                    if timer is not None and timer.examples_per_step is None:
                        leaves = jax.tree_util.tree_leaves(batch)
                        if leaves and getattr(leaves[0], "ndim", 0) >= 1:
                            timer.examples_per_step = int(leaves[0].shape[0])
                    if probe is not None and step % self.cfg.probe_every == 0:
                        # diagnostics BEFORE the update: alignment of the DFA
                        # update this step is about to apply, on its own batch
                        with observer.span("train.probe", step=step):
                            with self._mesh_ctx():
                                probed = probe(state, batch)
                            probe_host = observer.log_step(step, probed)
                        if verbose:
                            print(f"[probe {step}] align_global="
                                  f"{probe_host.get('align_global', float('nan')):.4f}",
                                  flush=True)
                    # async under jit: device time shows in the drain, not here
                    with observer.span("train.dispatch"):
                        state, metrics = self._dispatch(state, batch, self._fit_step_fn)
                    if recal > 0 and step > 0 and step % recal == 0:
                        # mirrors hw_calibrate.advance's cadence inside the step
                        observer.event("recalibration", cat="hwmon", step=step)
                    if timer is not None:
                        timer.tick(state["step"])
                    if (step + 1) % self.cfg.log_every == 0 or step + 1 == total_steps:
                        with observer.span("train.drain", step=step + 1):
                            if observer.enabled:
                                host = observer.log_step(step + 1, metrics,
                                                        self.model.counters())
                            else:
                                # one batched transfer for the whole dict — never
                                # one blocking float() per metric; the floats
                                # below read host memory, not the device
                                drained = jax.device_get(dict(metrics))  # lint: disable=RL002
                                host = {k: float(v)  # lint: disable=RL002
                                        for k, v in drained.items()}
                            self._log(step + 1, host)
                        if verbose:
                            txt = " ".join(f"{k}={v:.4f}" for k, v in sorted(host.items()))
                            print(f"[step {step + 1}/{total_steps}] {txt}", flush=True)
                    if self.ckpt is not None and (step + 1) % self.cfg.ckpt_every == 0:
                        self.ckpt.save(step + 1, state)
        finally:
            # interrupted or not, buffered JSONL rows reach disk — an
            # aborted run leaves a parseable metrics file
            observer.flush()
        if self.ckpt is not None:
            self.ckpt.save(total_steps, state)
        if eval_fn is not None:
            return state, eval_fn(state)
        return state, metrics

    # ---------- eval ----------
    def evaluate(self, state, batches) -> dict:
        loss_fn = jax.jit(lambda p, b: self.model.loss(p, b))
        total = {}
        n = 0
        for batch in batches:
            if self.mesh is not None:
                batch = sharding.put_batch(self.mesh, batch)
            with self._mesh_ctx():
                _, metrics = loss_fn(state["params"], batch)
            for k, v in metrics.items():
                # accumulate on device; a float() here would block per batch
                total[k] = total.get(k, 0.0) + v
            n += 1
        host = jax.device_get(total)  # one batched transfer for the run
        return {k: float(v) / max(n, 1) for k, v in host.items()}
