"""Serving cells: ``serve.Engine`` under open-loop arrivals.

Set-up builds the session's engine with weights drawn from ``--seed`` in
one jitted call, then serves warm-up requests that reach every slot and
every prefill and decode shape, and drains them.  The window offers the
arrivals that the traffic file's generator makes, at the cell's fixed
rate: each request is submitted once it is due, and timed from its due
time, so a stall that delays the loop counts against every request behind
it.  After each ``Engine.tick`` the harness stamps, for every live
request, its admission (it left ``QUEUED``: the tick's start) and each
new output token (the tick's end).  Requests due in the window are served
to the end, a minute past its close at most; one that never finishes has
failed.  Then every finished request is run through the plain reference.

The traced run traces a slice of ``TRACE_SECONDS`` from the middle of the
window, where the engine is as full as the load makes it: the profiler
starts before the window opens and stops at the slice's end, and its
per-layer records are of what came before that stop.  Host spans
``admit``, ``prefill`` and ``decode`` cover the engine's phases inside
each ``tick``, and ``gc`` Python's garbage collections.
"""

from __future__ import annotations

import gc
import time

import gen
import harness

DRAIN_S = 60.0          # how long requests due in the window may run on
TRACE_SECONDS = 5.0     # the traced slice of the window
PHASES = {"_admit": "admit", "_prefill_tick": "prefill", "_decode_tick": "decode"}


def build(ctx):
    import jax
    import jax.numpy as jnp

    from repro import api

    cfg = ctx.cell.config
    session = api.build_session(arch=cfg["arch"], smoke=cfg.get("smoke", False),
                                dtype=jnp.dtype(cfg["dtype"]),
                                **ctx.cell.traffic["session"])
    harness.check_model(session.model, cfg)
    params = jax.block_until_ready(
        jax.jit(session.model.init)(harness.seed_key(ctx.seed)))
    return session, session.engine(params=params, **ctx.cell.traffic["engine"])


class PhaseClock:
    """Each engine phase inside a tick as a host span, and its seconds in
    the current tick (the engine's own methods, wrapped on the instance)."""

    def __init__(self, engine):
        self.last: dict = {}
        for attr, name in PHASES.items():
            fn = getattr(engine, attr, None)
            if fn is not None:
                setattr(engine, attr, self._wrap(fn, name))

    def _wrap(self, fn, name):
        def timed(*a, **k):
            t = time.monotonic()
            with harness.span(name):
                out = fn(*a, **k)
            self.last[name] = time.monotonic() - t
            return out

        return timed


def requests(arrivals):
    from repro.serve import Request

    return [Request(prompt=list(a.prompt), max_new=a.max_new) for a in arrivals]


def drain(engine, reqs) -> None:
    for r in reqs:
        engine.submit(r)
    while engine.tick():
        pass


class Window:
    """The open-loop loop and what it stamps."""

    def __init__(self, engine, arrivals, seconds, profiler=None):
        self.engine = engine
        self.arrivals = arrivals
        self.reqs = requests(arrivals)
        self.seconds = seconds
        self.profiler = profiler
        self.phases = PhaseClock(engine)
        n = len(arrivals)
        self.admit = [None] * n
        self.stamps = [[] for _ in range(n)]
        self.ticks = []             # (start, end) of ticks that did work
        self.late = []              # submit time less due time
        self.worst = None           # (seconds, start, phase seconds) of the longest tick
        self.t0 = None
        self.stopped = None         # when the traced slice ended

    def run(self):
        eng, reqs, arr = self.engine, self.reqs, self.arrivals
        n = len(reqs)
        live: list = []
        nxt = 0
        self.t0 = t0 = time.monotonic()
        deadline = t0 + self.seconds + DRAIN_S
        prof = self.profiler
        if prof is not None:
            sl_open = t0 + max(0.0, (self.seconds - TRACE_SECONDS) / 2)
            sl_close = sl_open + min(TRACE_SECONDS, self.seconds)
        while True:
            now = time.monotonic()
            if prof is not None and prof.window is None and now >= sl_open:
                prof.open_window()
            if nxt < n and t0 + arr[nxt].due_s <= now:
                with harness.span("submit"):
                    while nxt < n and t0 + arr[nxt].due_s <= now:
                        eng.submit(reqs[nxt])
                        self.late.append(now - (t0 + arr[nxt].due_s))
                        live.append(nxt)
                        nxt += 1
            if not live:
                if nxt >= n:
                    break
                with harness.span("wait"):
                    time.sleep(max(0.0, min(1e-3, t0 + arr[nxt].due_s - time.monotonic())))
                continue
            if now > deadline:
                break
            with harness.span("tick"):
                did = eng.tick()
            end = time.monotonic()
            if did:
                self.ticks.append((now, end))
                if self.worst is None or end - now > self.worst[0]:
                    self.worst = (end - now, now - t0, dict(self.phases.last))
            self.phases.last.clear()
            still = []
            for i in live:
                r = reqs[i]
                if self.admit[i] is None and r.state != "QUEUED":
                    self.admit[i] = now
                k = len(r.out) - len(self.stamps[i])
                if k > 0:
                    self.stamps[i].extend([end] * k)
                if not r.done:
                    still.append(i)
            live = still
            if prof is not None and prof.running and end >= sl_close:
                self.stopped = end
                prof.stop()
        if prof is not None and prof.running:
            self.stopped = time.monotonic()
            prof.stop()

    def e2e(self) -> dict:
        t0, close = self.t0, self.t0 + self.seconds
        ttft = [(s[0] - (t0 + a.due_s)) * 1e3
                for s, a in zip(self.stamps, self.arrivals) if s]
        itl = [(b - a) * 1e3 for s in self.stamps for a, b in zip(s[:-1], s[1:])]
        done = sum(1 for s in self.stamps for t in s if t <= close)
        return {"ttft_p95_ms": harness.quantile(ttft, 0.95),
                "itl_p95_ms": harness.quantile(itl, 0.95),
                "serve_tokens_per_s": done / self.seconds}

    def records(self) -> dict:
        """What the per-layer readers read: ticks that started in the
        window and every admitted request, both only before the traced
        slice's end where there is one (stopping the profiler stalls the
        loop)."""
        t0 = self.t0
        stop = self.stopped or float("inf")
        return {
            "tick_ms": [(e - s) * 1e3 for s, e in self.ticks
                        if s < min(t0 + self.seconds, stop)],
            "queue_ms": [(a - (t0 + arr.due_s)) * 1e3
                         for a, arr in zip(self.admit, self.arrivals)
                         if a is not None and a < stop],
            "late_ms_p95": harness.quantile([x * 1e3 for x in self.late], 0.95),
        }

    def failed(self) -> int:
        """Requests that never finished, or finished short."""
        return sum(1 for r in self.reqs if not r.done or len(r.out) != r.max_new)


def compare(cell, seed, seqs, max_len, control: bool) -> dict:
    """How far the served tokens lie below the reference's best logit at
    their positions: the mean over every served token (most are the
    reference's own first choice and read 0), and the widest gap.  The
    reference computes in the precision the configuration states.  The
    control reads instead the gap of the token that the reference in
    bfloat16 puts first."""
    ref = cell.config_module(".ref.py")
    if control:
        low = ref.serve_logits(cell.config, seed, seqs, max_len, dtype="bfloat16")
        full = ref.serve_logits(cell.config, seed, seqs, max_len,
                                cols=[[t] for t in low["top"]])
        gaps = [b - c[0] for b, c in zip(full["best"], full["at_cols"])]
    else:
        full = ref.serve_logits(cell.config, seed, seqs, max_len)
        gaps = [b - s for b, s in zip(full["best"], full["served"])]
    return {"logit_gap_mean": float(sum(gaps) / len(gaps)),
            "logit_gap_max": float(max(gaps)), "tokens_compared": len(gaps)}


def run(ctx):
    if ctx.check_only:
        raise harness.BenchError("--check-only reads training cells: a serving cell "
                                 "is checked on what its window served")
    cell, seed, phases = ctx.cell, ctx.seed, ctx.phases
    data = cell.traffic["data"]
    generator = gen.generator(data)
    eng_cfg = cell.traffic["engine"]
    session, engine = build(ctx)
    phases("engine")
    vocab = cell.config["vocab_size"]
    drain(engine, requests(generator.warmup(data, vocab, seed, eng_cfg["batch_slots"],
                                            eng_cfg["prefill_chunk"])))
    phases("warm-up")
    arrivals = generator.arrivals(data, vocab, seed, ctx.seconds)
    t = time.monotonic()
    gc.collect()
    harness.note(f"a full garbage collection after set-up took "
                 f"{(time.monotonic() - t) * 1e3:.1f} ms")
    phases("arrivals")
    ctx.clock.report("set-up")
    phases.report()

    prof = harness.Profiler(ctx.trace, ctx.trace_dir) if ctx.trace else None
    win = Window(engine, arrivals, ctx.seconds, profiler=prof)
    gcw = harness.GcWatch()
    if prof is not None:
        prof.start()
    setup_s = time.monotonic() - ctx.t0
    gcw.on = True
    try:
        win.run()
    finally:
        gcw.on = False
        if prof is not None:
            prof.stop()
    ctx.clock.report("window")
    gcw.report("window")
    result = {"setup_s": setup_s, **win.e2e()}
    records = win.records()
    worst = win.worst
    harness.note(f"requests {len(arrivals)}, ticks {len(win.ticks)}, generator late "
                 f"p95 {records['late_ms_p95']} ms, engine stats {engine.stats}")
    if worst is not None:
        harness.note(f"longest tick {worst[0] * 1e3:.1f} ms at {worst[1]:.2f} s, phases (ms) "
                     f"{ {k: round(v * 1e3, 1) for k, v in worst[2].items()} }")
    result["memory_peak_bytes"] = harness.memory_peak_bytes(ctx.devices)
    reqs, max_len = win.reqs, eng_cfg["max_len"]
    failed = win.failed()
    del session, engine, win   # the engine's cache and weights go before the reference
    gc.collect()

    seqs = [(r.prompt, r.out) for r in reqs if r.done]
    values = compare(cell, seed, seqs, max_len, ctx.variant == "control")
    harness.note(f"compared {values.pop('tokens_compared')} served tokens")
    result["values"] = values
    result["records"] = records
    result["attempted"] = len(reqs)
    result["failed"] = failed
    result["e2e"] = {k: result[k] for k in ("setup_s", "ttft_p95_ms", "itl_p95_ms",
                                            "serve_tokens_per_s")}
    return result
