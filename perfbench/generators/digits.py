"""MNIST-shaped digits for the paper's MLP, fed through the program's own
input pipeline (``repro.data.pipeline.ArrayClassification``), as users
train it.

The images are the arithmetic of ``repro.data.mnist.procedural_digits``
(5×7 font scaled, sheared and shifted into 28×28, pixel noise), with its
draws in its order, rendered with array operations instead of a Python
loop over images.

Parameters: ``n`` images, ``batch``.
"""

from __future__ import annotations

import numpy as np

import gen

_FONT = {
    0: ["01110", "10001", "10011", "10101", "11001", "10001", "01110"],
    1: ["00100", "01100", "00100", "00100", "00100", "00100", "01110"],
    2: ["01110", "10001", "00001", "00010", "00100", "01000", "11111"],
    3: ["11110", "00001", "00001", "01110", "00001", "00001", "11110"],
    4: ["00010", "00110", "01010", "10010", "11111", "00010", "00010"],
    5: ["11111", "10000", "11110", "00001", "00001", "10001", "01110"],
    6: ["00110", "01000", "10000", "11110", "10001", "10001", "01110"],
    7: ["11111", "00001", "00010", "00100", "01000", "01000", "01000"],
    8: ["01110", "10001", "10001", "01110", "10001", "10001", "01110"],
    9: ["01110", "10001", "10001", "01111", "00001", "00010", "01100"],
}


def _glyphs() -> np.ndarray:
    return np.array([[[float(c == "1") for c in row] for row in _FONT[d]]
                     for d in range(10)], np.float32)


def digits(p: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(images (n, 784) float32 in [0, 1], labels (n,) int32)."""
    n = int(p["n"])
    rng = gen.rng_for(seed, 2)
    labels = rng.integers(0, 10, size=n).astype(np.int32)
    scales = rng.uniform(2.4, 3.4, size=n)
    dx = rng.integers(-3, 4, size=n)
    dy = rng.integers(-3, 4, size=n)
    shear = rng.uniform(-0.25, 0.25, size=n)
    h = np.round(7 * scales).astype(np.int64)
    w = np.round(5 * scales).astype(np.int64)
    y0 = np.maximum(0, (28 - h) // 2 + dy)
    x0 = np.maximum(0, (28 - w) // 2 + dx)
    rows = np.arange(28)
    r = rows[None, :] - y0[:, None]                        # (n, 28)
    row_ok = (r >= 0) & (r < np.minimum(h, 28 - y0)[:, None])
    gy = np.clip((np.maximum(r, 0) / scales[:, None]).astype(np.int64), 0, 6)
    # np.roll of glyph row r by round(shear·(r − h/2)), as the loop does
    k = np.round(shear[:, None] * (r - h[:, None] / 2)).astype(np.int64)
    c = rows[None, :] - x0[:, None]                        # (n, 28)
    col_ok = (c >= 0) & (c < np.minimum(w, 28 - x0)[:, None])
    src = np.mod(c[:, None, :] - k[:, :, None], w[:, None, None])
    gx = np.clip((src / scales[:, None, None]).astype(np.int64), 0, 4)
    g = _glyphs()
    imgs = g[labels[:, None, None], gy[:, :, None], gx]
    imgs = imgs * (row_ok[:, :, None] & col_ok[:, None, :])
    imgs = imgs.astype(np.float32)
    imgs += rng.normal(0, 0.08, size=imgs.shape).astype(np.float32)
    imgs = np.clip(imgs, 0.0, 1.0)
    return imgs.reshape(n, 784), labels


def batch_rows(n: int, batch: int, seed: int, step: int) -> np.ndarray:
    """The rows ``ArrayClassification(seed=seed)`` takes at ``step``: a
    permutation of the dataset per epoch (a copy of its arithmetic, for
    the reference)."""
    per_epoch = n // batch
    epoch, i = divmod(step, per_epoch)
    perm = np.random.default_rng((seed, epoch)).permutation(n)
    return perm[i * batch:(i + 1) * batch]


def feed(cell, p: dict, seed: int):
    from repro.data import pipeline

    xs, ys = digits(p, seed)
    xs = xs[:, :cell.config["input_dim"]]
    batch = int(p["batch"])
    pipe = pipeline.ArrayClassification(xs, ys, batch, seed=seed)
    ref_batches = []
    for step in range(3):
        rows = batch_rows(len(xs), batch, seed, step)
        ref_batches.append({"x": xs[rows], "y": ys[rows]})
    return pipe.batch, ref_batches
