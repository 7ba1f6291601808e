"""Device time of the fused emu bank kernel per training step, in ms."""

import harness


def read(r):
    s = harness.kernel_seconds(r, "emu_bank")
    return None if s is None else s / r.records["steps"] * 1e3
