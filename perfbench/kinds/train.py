"""Training cells: ``Session.fit`` on the cell's session, for a window.

Set-up builds one session (the compiled step and its state), with the
weights drawn from ``--seed`` and the data made from it.  It drives the
session's own ``fit`` and feed through the first steps (one, two and
three steps: the loss of each, the first gradient from the momentum after
one step, the parameters after three), which also compiles every program
the window runs.  The window is one ``fit`` of as many steps as fill
``--seconds`` at the step time set-up measured (over a further fit of
about a second where a step is too short for three to time).  It is
timed from the first ``data_fn`` call of that fit (the fit's own state
initialisation comes before it) to ``block_until_ready`` on the state
after the last step, with no sync in between.  The reference then
follows the same three steps once the program's state is freed.
"""

from __future__ import annotations

import gc
import math
import time

import numpy as np

import gen
import harness
import refkit

TRACE_SECONDS = 3.0     # the traced run's window, at most
CALIBRATE_SECONDS = 1.0  # a fit this long times a step shorter than ...
SHORT_STEP_S = 0.05      # ... this, where three steps are too few to time


class Feed:
    """``data_fn`` for ``Session.fit``: a host span per call, and the time
    of the first call (the start of the window)."""

    def __init__(self, fn):
        self.fn = fn
        self.first = None
        self.profiler = None

    def arm(self, profiler=None):
        self.first = None
        self.profiler = profiler

    def __call__(self, step: int):
        if self.first is None:
            self.first = time.monotonic()
            if self.profiler is not None:
                self.profiler.open_window()
        with harness.span("data_fn"):
            return self.fn(step)


# the noiseless twin of the emulated bank, in the program's place for the
# upper reading of ``grad1_noise_gap``
NOISELESS = "emu_ideal"


def build(ctx, variant):
    import jax
    import jax.numpy as jnp

    from repro import api

    cfg = ctx.cell.config
    dtype = jnp.bfloat16 if variant == "control" else jnp.dtype(cfg["dtype"])
    session_kw = dict(ctx.cell.traffic["session"])
    if variant == "noise_off":
        session_kw["hardware"] = NOISELESS
    session = api.build_session(arch=cfg["arch"], smoke=cfg.get("smoke", False),
                                dtype=dtype, **session_kw)
    harness.check_model(session.model, cfg)
    trainer = session.trainer
    init = jax.jit(type(trainer).init_state, static_argnums=0)
    init_params = jax.jit(lambda key: type(trainer).init_state(trainer, key)["params"])
    return session, trainer, init, init_params


def seed_session(trainer, init, key):
    """``Session.fit`` initialises its state from ``Trainer.init_state``;
    make that one jitted call from the seed's key, finished before the
    fit goes on."""
    import jax

    def init_state(_key=None):
        return jax.block_until_ready(init(trainer, key))

    trainer.init_state = init_state


def host(tree) -> dict:
    """A pytree on the host, leaf by leaf, by leaf path."""
    import jax

    return {k: jax.device_get(v) for k, v in refkit.flat(tree).items()}


def check_steps(session, feed, p0: dict) -> tuple[dict, float]:
    """The first steps through ``Session.fit``: -> ({"losses": the loss of
    steps 1..3, "grad1": the first gradient (the momentum after one step),
    "change": the parameters after three steps less ``p0``}, all on the
    host), and the seconds a step took in the last fit.  ``p0`` (the initial parameters) is on the
    host, so the device holds only what the program holds."""
    import jax

    prog, step_s = {"losses": []}, None
    for k in (1, 2, 3):
        feed.arm()
        state, m = session.fit(feed, k, verbose=False)
        jax.block_until_ready(state)
        step_s = (time.monotonic() - feed.first) / k
        prog["losses"].append(float(m["loss"]))
        if k == 1:
            prog["grad1"] = host(state["opt"]["mom"])
        if k == 3:
            prog["change"] = {name: np.asarray(v, np.float32) - np.asarray(p0[name], np.float32)
                              for name, v in host(state["params"]).items()}
        del state, m
    return prog, step_s


def run(ctx):
    import jax

    cell, seed, phases = ctx.cell, ctx.seed, ctx.phases
    session, trainer, init, init_params = build(ctx, ctx.variant)
    phases("session")
    key = harness.seed_key(seed)
    seed_session(trainer, init, key)
    data = cell.traffic["data"]
    data_fn, ref_batches = gen.generator(data).feed(cell, data, seed)
    feed = Feed(data_fn)
    phases("data")

    p0 = host(init_params(key))
    prog, step_s = check_steps(session, feed, p0)
    del p0
    phases("first steps")
    if step_s < SHORT_STEP_S:
        n = math.ceil(CALIBRATE_SECONDS / step_s)
        feed.arm()
        state, _ = session.fit(feed, n, verbose=False)
        jax.block_until_ready(state)
        step_s = (time.monotonic() - feed.first) / n
        del state
        phases("step timed")
    ctx.clock.report("set-up")
    phases.report()

    trace_on = ctx.trace
    seconds = min(ctx.seconds, TRACE_SECONDS) if trace_on else ctx.seconds
    steps = max(3, math.ceil(seconds / step_s))
    result = {}
    if not ctx.check_only:
        prof = harness.Profiler(trace_on, ctx.trace_dir)
        feed.arm(prof)
        prof.start()
        try:
            with harness.span("fit"):
                state, m = session.fit(feed, steps, verbose=False)
            with harness.span("drain"):
                jax.block_until_ready(state)
            t_end = time.monotonic()
        finally:
            prof.stop()
        window = t_end - feed.first
        result.update(
            setup_s=feed.first - ctx.t0,
            train_step_ms=window / steps * 1e3,
            final_loss=float(m["loss"]),
        )
        del state, m
        ctx.clock.report("window")
    result["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    del session, trainer, feed, data_fn
    gc.collect()

    ref_mod = cell.config_module(".ref.py")
    ref = ref_mod.train_reference(cell.config, ref_batches, seed, prog)
    values = refkit.train_gaps(prog, ref, cell.centers())
    harness.note(f"losses program {prog['losses']} reference {ref['losses']}")
    finite = all(np.isfinite(prog["losses"])) and np.isfinite(result.get("final_loss", 0.0))

    flops = cell.config_module(".flops.py")
    result["records"] = {
        "steps": steps,
        "step_flops": flops.step_flops(cell.config, cell.traffic),
        "projections": flops.projections(cell.config, cell.traffic),
    }
    result["values"] = values
    result["attempted"] = steps
    result["failed"] = 0 if finite else steps
    result["e2e"] = {k: result[k] for k in ("setup_s", "train_step_ms") if k in result}
    return result
