"""Seconds jax spent in set-up tracing, lowering and compiling programs
(loads from the persistent cache included): compile.trace_s +
compile.lower_s + compile.backend_s, counted from the capture's opening,
before the entry is built, to the end of the warm-up."""

from benchmark.counters import setup_total


def read(run):
    return setup_total(run, "compile.trace_s", "compile.lower_s",
                       "compile.backend_s")
