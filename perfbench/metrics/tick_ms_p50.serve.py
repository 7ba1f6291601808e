"""Median host time of an ``Engine.tick`` that did work in the window
(the harness's own span around each call), in ms."""

import harness


def read(r):
    return harness.quantile(r.records["tick_ms"], 0.5)
