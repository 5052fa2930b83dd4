"""Share of the window that no phase of the program names: 100 - the
sum of every phase counter (obs.PHASE_SECONDS) / window. The phase
counters are disjoint, so the shares and this sum to 100."""

from benchmark.counters import untraced_share


def read(run):
    return untraced_share(run)
