"""Share of the window the garbage collector ran inside the checks,
seconds counted by the program's gc.callbacks hook for collections that
start inside a phase (gc.collect_s / window). Collections overlap the
phases, so this is not part of their sum."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "gc.collect_s")
