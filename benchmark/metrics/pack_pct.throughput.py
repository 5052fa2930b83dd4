"""Share of the window spent on the host preparing launches: bucket
assignment, re-slotting, padding and stacking (the program's sched.pack
phase: 100 x sched.pack_s / window)."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "sched.pack_s")
