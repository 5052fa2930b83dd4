"""Share of the window spent reconstructing counterexample witnesses of
invalid histories (the program's check.witness phase: 100 x
check.witness_s / window)."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "check.witness_s")
