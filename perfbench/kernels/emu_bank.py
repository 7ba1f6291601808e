"""The fused emu weight-bank kernel (``repro.kernels.emu_matmul``): which
device ops are its calls, and the operations and bytes of one call.

A call shows in a TPU profile as a ``tpu_custom_call`` whose result is the
panel stack ``f32[NM,T,ROWS]`` and whose first operand is the bus-tiled
input ``f32[1,NJ,T,COLS]`` of the same T rows (``ROWS``×``COLS`` is the
bank).  The logical product is C = A·Bᵀ with A (T, K), B (M, K), C (T, M),
all float32: 2·T·K·M operations, and A, B and C each read or written once.
The emulator's panel schedule, its padding to whole bank panels and the
noise it draws are not work and are not counted.
"""

import re

_CALL = re.compile(r"= f32\[(\d+),(\d+),(\d+)\]\S* custom-call\(f32\[1,(\d+),(\d+),(\d+)\]")


def match(op: str) -> bool:
    """Is this device op (its HLO text in the trace) a call of the kernel?"""
    m = _CALL.search(op)
    return bool(m) and "tpu_custom_call" in op and m.group(2) == m.group(5)


def cost(t: int, k: int, m: int) -> tuple[float, float]:
    """-> (operations, bytes) of one call."""
    ops = 2.0 * t * k * m
    nbytes = 4.0 * (t * k + m * k + t * m)
    return ops, nbytes
