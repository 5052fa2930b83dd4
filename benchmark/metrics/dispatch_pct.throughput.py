"""Share of the window spent enqueueing kernels and their inputs on
the device (the program's sched.dispatch phase: 100 x sched.dispatch_s /
window)."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "sched.dispatch_s")
