"""Seconds from the process's start to the window's start: imports, weights
drawn and sealed, programs compiled or loaded from the cache, and the warm
traffic that brings the batch to its steady state."""


def read(run):
    return run.setup_s
