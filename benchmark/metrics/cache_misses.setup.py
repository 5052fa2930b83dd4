"""Programs compiled in set-up that the persistent compilation cache
did not hold (compile.cache_misses: jax counts each entry it writes);
0 on every run after a checkout's first."""

from benchmark.counters import setup_total


def read(run):
    return setup_total(run, "compile.cache_misses")
