"""What the benchmark's traffic generators share, and the look-up of one.

A traffic file's ``data`` block names its generator (``"generator":
"<name>"``) and gives the rest of its parameters; the generator is
``generators/<name>.py``, found by that name, so a new generator is a new
file and a new mix of an existing one is a new traffic file alone.  Same
seed, same inputs.  The program receives only what a generator makes.
Where a seed could change the amount of work (how many requests, how
long), every seed gets the same sizes and gaps and draws only the
contents.

A training generator defines ``feed(cell, p, seed) -> (data_fn,
reference_batches)``: the ``data_fn(step)`` that ``Session.fit`` calls,
and the batches of the first steps for the reference.  A serving
generator defines ``arrivals(p, vocab, seed, seconds) -> [Arrival]`` and
``warmup(p, vocab, seed, slots, chunk) -> [Arrival]``.
"""

from __future__ import annotations

import dataclasses
import os
import statistics

import numpy as np

import harness


def generator(p: dict):
    """The module of the generator a traffic file's ``data`` block names."""
    name = p["generator"]
    path = harness.bench_file("generators", name + ".py")
    if not os.path.exists(path):
        raise harness.BenchError(f"unknown generator {name!r} (no generators/{name}.py)")
    return harness.load_py(path)


def rng_for(seed: int, *stream) -> np.random.Generator:
    """A generator for one stream of one seed (any seed below 2**64)."""
    return np.random.default_rng((seed, *stream))


@dataclasses.dataclass
class Arrival:
    due_s: float          # seconds after the window opens
    prompt: list
    max_new: int


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int) -> np.ndarray:
    """The n quantiles at (i + 0.5)/n of a lognormal, rounded and clipped."""
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(np.int64)
