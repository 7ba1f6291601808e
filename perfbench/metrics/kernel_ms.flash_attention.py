"""Device time of the flash-attention kernel per training step (its
forwards and its vjp), in ms."""

import harness


def read(r):
    s = harness.kernel_seconds(r, "flash_attention")
    return None if s is None else s / r.records["steps"] * 1e3
