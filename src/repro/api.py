"""``repro.api`` — the one-call facade over the algorithm × hardware ×
backend matrix.

The paper's experiment grid is three independent axes:

* **algo**     — a name in ``repro.algos`` (``bp`` | ``dfa`` |
  ``dfa-layerwise`` | anything registered later)
* **hardware** — a ``core.photonics`` preset name (``ideal`` |
  ``single_mrr`` | ``offchip_bpd`` | ``onchip_bpd`` | ``digital`` |
  ``emu_ideal`` | ``emu_offchip`` | ``emu_onchip``) or a
  ``PhotonicConfig`` instance
* **backend**  — how projections execute: ``auto`` | ``ref`` | ``pallas``
  | ``emu`` (or a ``PhotonicBackend`` instance)

The ``emu`` backend runs projections through the device-level MRR
emulation (``repro.hardware``): when the chosen hardware carries no
``MRRConfig`` the default device (drift ON) is attached, and
``recalibrate_every`` defaults to periodic in-situ recalibration so long
fits degrade — and recover — realistically.

Typical use::

    from repro import api

    session = api.build_session(arch="mnist_mlp", algo="dfa",
                                hardware="offchip_bpd")
    state, metrics = session.fit(data_fn, total_steps=512)
    session.evaluate(state, eval_batches)

``arch`` is a name from ``repro.configs`` (or an already-built DFAModel
instance).  Everything else is optional with paper-faithful defaults
(SGD momentum 0.9, lr 0.01 — the paper's §4 optimizer).

``schedule="auto"`` invokes the ``repro.sim`` autotuner: the fastest
(n_buses, tiling, f_s) for THIS model's DFA backward under
``power_budget_w`` is simulated from the emulator's real panel schedule
and applied to the session's photonics; the winning ``TunedSchedule``
(timeline report included) is kept on ``Session.schedule``.

``Session.engine()`` opens the serving plane on the same cell: a
continuous-batching ``serve.Engine`` whose forward projections run on
the session's photonic backend (``launch/serve.py`` is the CLI).
"""

from __future__ import annotations

import dataclasses
import typing

import jax
import jax.numpy as jnp

from repro import algos, configs
from repro import obs as obs_lib
from repro.algos.dfa import DFAConfig
from repro.core import feedback as fb_lib
from repro.core import photonics
from repro.train import SGDM, Trainer, TrainerConfig


def resolve_hardware(hardware) -> photonics.PhotonicConfig:
    """Preset name or PhotonicConfig -> PhotonicConfig."""
    if isinstance(hardware, photonics.PhotonicConfig):
        return hardware
    return photonics.preset(hardware)


def build_model(arch, *, smoke: bool = False, dtype=jnp.float32):
    """Arch name (repro.configs) or a model instance -> DFAModel."""
    if not isinstance(arch, str):
        return arch  # already a model
    a = configs.get(arch)
    if smoke:
        return a.make_smoke()
    return a.make_model(dtype)


@dataclasses.dataclass
class Session:
    """A bound (model, algorithm, hardware, backend) cell of the matrix."""

    model: typing.Any
    algorithm: algos.Algorithm
    trainer: Trainer
    # the autotuned photonic schedule (repro.sim), when built with
    # schedule="auto"; None means the hardware config was taken as given
    schedule: typing.Any = None
    # the bound repro.obs.Observer when built with observe=... (or attached
    # later via Session.observe()); None means observability is off
    observer: typing.Any = None

    @property
    def config(self) -> TrainerConfig:
        return self.trainer.cfg

    # ---- observability ----
    def observe(self, *, metrics_path: str | None = None,
                trace_path: str | None = None):
        """Attach (and return) an ``obs.Observer`` wired for this session:
        hardware monitor on stateful-hw backends (with the autotuned
        ``drift_budget`` when a schedule was planned), optional JSONL
        metrics sink and trace output path.  ``fit``/``engine`` pick it
        up automatically."""
        self.observer = obs_lib.for_session(self, metrics_path=metrics_path,
                                            trace_path=trace_path)
        return self.observer

    # ---- training ----
    def init_state(self, key=None):
        return self.trainer.init_state(key)

    def step(self, state, batch):
        return self.trainer.step(state, batch)

    def fit(self, data_fn, total_steps: int, eval_fn=None, verbose: bool = True,
            timer=None, observer=None):
        """Run the training loop; under ``data_parallel`` the batch dim is
        sharded across all local devices (see train.Trainer).  ``timer`` is
        an optional ``repro.bench.StepTimer`` for throughput telemetry;
        ``observer`` an ``obs.Observer`` (defaults to the session's)."""
        return self.trainer.fit(data_fn, total_steps, eval_fn=eval_fn,
                                verbose=verbose, timer=timer,
                                observer=observer if observer is not None
                                else self.observer)

    @property
    def mesh(self):
        """The active data-parallel mesh (None on the single-device path)."""
        return self.trainer.mesh

    def step_cost(self, state, batch):
        """Per-device HLO cost of one train step (utils.hlo_cost)."""
        return self.trainer.step_cost(state, batch)

    # ---- gradients / eval ----
    def value_and_grad(self):
        """fn(params, extra_state, batch, rng) -> ((loss, metrics), grads)."""
        return self.algorithm.value_and_grad(self.model, self.config.dfa)

    def evaluate(self, state, batches) -> dict:
        return self.trainer.evaluate(state, batches)

    # ---- serving ----
    def engine(self, params=None, *, batch_slots: int = 8, max_len: int = 512,
               eos_id: int | None = None, prefill_chunk: int = 16,
               hw_state=None, seed: int = 0, observer=None):
        """A ``serve.Engine`` on this session's (hardware, backend) cell.

        The session's backend choice carries over: ``auto``/``ref`` with
        photonics disabled serves the exact digital forward; ``emu`` (or an
        enabled photonic config) routes every forward projection through
        the same banks training used — drift, crosstalk, quantisation and
        all.  ``params`` defaults to a fresh ``model.init``.
        """
        from repro.serve import Engine

        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        hw_cfg = self.config.dfa.photonics
        backend = self.config.dfa.backend
        if not isinstance(backend, str):
            backend = "ref"
        if backend == "auto" and hw_cfg.enabled:
            backend = "ref"
        if not hw_cfg.enabled:
            backend = None
        return Engine(self.model, params, batch_slots=batch_slots,
                      max_len=max_len, eos_id=eos_id,
                      prefill_chunk=prefill_chunk, backend=backend,
                      photonics=hw_cfg if backend is not None else None,
                      hw_state=hw_state, seed=seed,
                      observer=observer if observer is not None
                      else self.observer,
                      debug_checks=self.config.debug_checks)


def build_session(*, arch="mnist_mlp", algo: str = "dfa", hardware="ideal",
                  backend="auto", emu_kernel: str | None = None,
                  optimizer=None, seed: int = 0,
                  smoke: bool = False, dtype=jnp.float32,
                  error_compress: str = "none", freeze_norms: bool = False,
                  feedback: fb_lib.FeedbackConfig | None = None,
                  n_buses: int | None = None,
                  schedule: str | None = None,
                  power_budget_w: float | None = None,
                  schedule_batch: int | None = None,
                  microbatches: int = 1,
                  data_parallel: bool | str = "auto", prefetch: int = 2,
                  digital_step_s: float | None = None,
                  recalibrate_every: int | str | None = None,
                  ckpt_dir: str | None = None,
                  ckpt_every: int = 500, log_every: int = 50,
                  log_path: str | None = None,
                  step_deadline_s: float | None = None,
                  observe=False, probe_every: int | None = None,
                  debug_checks: bool = False) -> Session:
    """Compose one cell of the algorithm × hardware × backend matrix.

    ``observe``: ``False`` (default) runs without observability; ``True``
    attaches a session-wired ``obs.Observer`` (hardware monitor on
    stateful-hw backends); an ``Observer`` instance is taken as given.

    ``probe_every``: in-situ diagnostics cadence — every this many steps
    ``fit`` runs the ``obs.introspect.AlignmentProbe`` (DFA-vs-BP
    alignment per layer, grad norms, and on the emu backend the
    ``obs.attribution`` noise budget), logged as observer rows.  The
    default None keeps training bit-identical to an unprobed run.

    ``debug_checks``: opt into the ``repro.lint.runtime`` sanitizers — the
    train step (and any ``session.engine()``) runs under
    ``jax.experimental.checkify`` (NaN/Inf, div-by-zero, plus the emu
    channel's explicit finiteness checks) and a recompilation sentinel
    raises ``lint.RecompileError`` if a hot path retraces after warmup.
    """
    model = build_model(arch, smoke=smoke, dtype=dtype)
    algorithm = algos.get(algo)             # fail fast on unknown names
    backend_obj = photonics.get_backend(backend)  # (likewise for the backend)
    if emu_kernel is not None:
        # emu execution-path override ("ref" | "pallas" | "xla"): rebuild
        # the backend instance so the whole session (train + recalibrate)
        # runs the requested kernel.  Only meaningful on the emu backend.
        if not isinstance(backend_obj, photonics.EmulatedMRRBackend):
            raise ValueError(
                f"emu_kernel={emu_kernel!r} requires backend='emu', "
                f"got {backend_obj.name!r}")
        from repro.hardware.channel import resolve_emu_kernel

        resolve_emu_kernel(emu_kernel)      # fail fast on unknown specs
        backend_obj = dataclasses.replace(backend_obj, emu_kernel=emu_kernel)
        backend = backend_obj
    hw_cfg = resolve_hardware(hardware)
    if n_buses is not None:
        # multi-wavelength scale-out: override the preset's bus count
        hw_cfg = dataclasses.replace(hw_cfg, n_buses=n_buses)
    if backend_obj.stateful_hardware and hw_cfg.mrr is None:
        # device-level backend with an abstract hardware preset: attach the
        # default device description (drift ON) so the emulation has a bank
        # (before the schedule search so the autotuner sees the device too)
        from repro.hardware.mrr import MRRConfig

        hw_cfg = dataclasses.replace(hw_cfg, mrr=MRRConfig())
    tuned = None
    if schedule == "auto":
        # repro.sim schedule autotuning: search (n_buses, tiling, f_s) on
        # THIS model's DFA backward under the power budget and run the
        # session on the winner.  A caller-pinned n_buses narrows the
        # search to that bus count; schedule_batch is the nominal per-step
        # vector count the timelines stream (relative ranking is
        # batch-insensitive — fills and heater epilogues amortise).
        from repro import sim

        workload = sim.dfa_backward_workload(model, t=schedule_batch or 64)
        bus_counts = ((n_buses,) if n_buses is not None
                      else sim.DEFAULT_BUS_COUNTS)
        recal_candidates = (0,)
        drift_budget = None
        if recalibrate_every == "auto":
            # co-optimise the recalibration cadence: the heater sweep's
            # amortised sim-time cost trades against drift accuracy, held
            # under a budget of half the stationary drift (the regime
            # where BENCH_hardware's recovery curves keep DFA training)
            device = hw_cfg.mrr
            recal_candidates = sim.DEFAULT_RECAL_CANDIDATES
            if device is not None and device.drift_sigma > 0:
                drift_budget = 0.5 * device.drift_sigma
        # search only "panel" tilings: that is the layout the emulator
        # actually executes, so the applied (n_buses, f_s) is optimal for
        # the schedule the session will really run ("layer" projections
        # stay available through sim.autotune directly)
        tuned = sim.autotune(workload, hw_cfg,
                             power_budget_w=power_budget_w,
                             bus_counts=bus_counts, tilings=("panel",),
                             digital_s=digital_step_s or 0.0,
                             recal_candidates=recal_candidates,
                             drift_budget=drift_budget)
        hw_cfg = tuned.apply(hw_cfg)
        if recalibrate_every == "auto":
            recalibrate_every = tuned.recalibrate_every
    elif schedule is not None:
        raise ValueError(f"unknown schedule {schedule!r} (None | 'auto')")
    elif (power_budget_w is not None or schedule_batch is not None
          or digital_step_s is not None or recalibrate_every == "auto"):
        # these only steer the autotuner — accepting them without
        # schedule="auto" would silently enforce nothing
        raise ValueError("power_budget_w/schedule_batch/digital_step_s/"
                         "recalibrate_every='auto' require schedule='auto'")
    if recalibrate_every is None:
        # default cadence: in-situ recalibration on for any drifting device
        drifting = (backend_obj.stateful_hardware and hw_cfg.mrr is not None
                    and hw_cfg.mrr.stateful)
        recalibrate_every = 500 if drifting else 0
    dfa_cfg = DFAConfig(
        photonics=hw_cfg,
        feedback=feedback or fb_lib.FeedbackConfig(),
        error_compress=error_compress,
        backend=backend,
        freeze_norms=freeze_norms,
    )
    cfg = TrainerConfig(
        algo=algo, dfa=dfa_cfg,
        optimizer=optimizer or SGDM(lr=0.01, momentum=0.9),
        seed=seed, microbatches=microbatches,
        data_parallel=data_parallel, prefetch=prefetch,
        recalibrate_every=recalibrate_every,
        ckpt_dir=ckpt_dir, ckpt_every=ckpt_every,
        log_every=log_every, log_path=log_path,
        step_deadline_s=step_deadline_s,
        probe_every=probe_every,
        debug_checks=debug_checks,
    )
    session = Session(model=model, algorithm=algorithm,
                      trainer=Trainer(model, cfg), schedule=tuned)
    if observe is True:
        session.observe()
    elif observe:
        session.observer = observe
    return session
