"""Share of the window the host blocks on device-to-host reads of
results, which is where device time surfaces on the host (the program's
sched.fetch_wait phase: 100 x sched.fetch_wait_s / window)."""

from benchmark.counters import window_share


def read(run):
    return window_share(run, "sched.fetch_wait_s")
