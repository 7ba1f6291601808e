"""Device time of the grouped expert matmul per training step (its
product and both vjp products), in ms."""

import harness


def read(r):
    s = harness.kernel_seconds(r, "moe_gmm")
    return None if s is None else s / r.records["steps"] * 1e3
