"""Share of the window spent splitting the test history by key
(the program's check.split phase: 100 x check.split_s / window)."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "check.split_s")
