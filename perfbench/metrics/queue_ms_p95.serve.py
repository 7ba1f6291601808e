"""95th percentile, over the requests due in the window, of the time from
a request's due time to its admission (the start of the tick after which
it had left ``QUEUED``), in ms."""

import harness


def read(r):
    return harness.quantile(r.records["queue_ms"], 0.95)
