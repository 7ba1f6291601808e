"""Share of the traced training window in which no op ran on the device
(averaged over the chips), in percent."""

import harness


def read(r):
    return harness.idle_share(r)
