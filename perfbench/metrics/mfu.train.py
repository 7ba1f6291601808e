"""Model FLOPs of the training steps in the traced window (from
``configs/<config>.flops.py``: no recomputed forward) over the window's
seconds, the chips and the chip's peak, in percent."""

import harness


def read(r):
    return harness.mfu(r)
