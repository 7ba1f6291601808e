"""Token batches for language-model training: the arithmetic of
``repro.data.tokens.MarkovTokens`` (successor chain ``t' = (a·t + b) mod V``
with probability ``p_follow``, uniform noise otherwise), made once in
set-up as a pool of ``pool`` distinct batches that the feed cycles
through.

Parameters: ``batch``, ``seq``, ``pool``, ``p_follow``, ``a``, ``b``.
"""

from __future__ import annotations

import numpy as np

import gen


def pool(p: dict, vocab: int, seed: int) -> list[dict]:
    """``pool`` batches of (batch, seq) tokens and next-token labels."""
    b, s = int(p["batch"]), int(p["seq"])
    mult, add, p_follow = int(p["a"]), int(p["b"]), float(p["p_follow"])
    out = []
    for i in range(int(p["pool"])):
        rng = gen.rng_for(seed, 1, i)
        toks = np.empty((b, s + 1), np.int64)
        toks[:, 0] = rng.integers(0, vocab, size=b)
        follow = rng.random((b, s)) < p_follow
        noise = rng.integers(0, vocab, size=(b, s))
        for t in range(s):
            nxt = (toks[:, t] * mult + add) % vocab
            toks[:, t + 1] = np.where(follow[:, t], nxt, noise[:, t])
        toks = toks.astype(np.int32)
        out.append({"tokens": toks[:, :-1], "labels": toks[:, 1:]})
    return out


def feed(cell, p: dict, seed: int):
    batches = pool(p, cell.config["vocab_size"], seed)
    return (lambda step: batches[step % len(batches)]), batches[:3]
