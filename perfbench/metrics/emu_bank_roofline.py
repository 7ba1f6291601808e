"""The fused emu bank kernel's share of its roofline, in percent: the
least time the chip could take for the step's DFA projections (the larger
of operations over peak FLOP/s and bytes over peak bytes/s, from
``kernels/emu_bank.py``) over the kernel's device time."""

import harness


def read(r):
    return harness.roofline_share(r, "emu_bank")
