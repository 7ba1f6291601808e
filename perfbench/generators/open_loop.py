"""Open-loop serving arrivals: exponential gaps at a fixed ``rate``,
lognormal prompt and output lengths, uniform token ids.

The gaps are the quantiles of an exponential at (i + 0.5)/n, the prompt
and output lengths those of clipped lognormals, each set in one fixed
shuffled order: every seed offers the same requests at the same times
(the order decides which requests run past the window's close, so a seed
that reordered them would change the work).  The seed draws the token
ids.

Parameters: ``rate`` (requests/s), ``prompt_median``, ``prompt_sigma``,
``prompt_min``, ``prompt_max``, ``output_median``, ``output_sigma``,
``output_min``, ``output_max``.
"""

from __future__ import annotations

import numpy as np

import gen


def arrivals(p: dict, vocab: int, seed: int, seconds: float) -> list:
    rate = float(p["rate"])
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(3)
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    prompts = gen.lognormal_quantiles(n, p["prompt_median"], p["prompt_sigma"],
                                      p["prompt_min"], p["prompt_max"])
    outs = gen.lognormal_quantiles(n, p["output_median"], p["output_sigma"],
                                   p["output_min"], p["output_max"])
    gaps, prompts, outs = order.permutation(gaps), order.permutation(prompts), order.permutation(outs)
    due = np.cumsum(gaps) - gaps[0]
    ids = gen.rng_for(seed, 3)
    return [gen.Arrival(float(due[i]), ids.integers(0, vocab, size=int(prompts[i])).tolist(),
                        int(outs[i])) for i in range(n)]


def warmup(p: dict, vocab: int, seed: int, slots: int, chunk: int) -> list:
    """Requests that touch every slot and every shape the window uses:
    prompts of one, two and three chunks and of a few tokens."""
    rng = gen.rng_for(seed, 4)
    lengths = [chunk + 1, 7, 2 * chunk + 3, int(p["prompt_min"])]
    return [gen.Arrival(0.0, rng.integers(0, vocab, size=lengths[i % 4]).tolist(), 4)
            for i in range(slots + 2)]
