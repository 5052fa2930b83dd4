"""Readers of the program's phase and set-up counters, shared by the
per-layer metrics. Each returns None where the program under test has no
such counter (a program from before the counter existed), so that a
metric that finds nothing to read is left out of the result line."""

from __future__ import annotations


def window_share(run, counter: str):
    """100 x the counter's increase over the window / the window's
    seconds."""
    if counter not in run.counters1:
        return None
    return 100.0 * run.counter(counter) / run.window_s


def untraced_share(run):
    """100 - the sum of every phase counter's share: the share of the
    window in which no phase of the program was open."""
    try:
        from jepsen_etcd_demo_tpu.obs import PHASE_SECONDS
    except ImportError:
        return None
    shares = [window_share(run, c) for c in PHASE_SECONDS]
    if None in shares:
        return None
    return 100.0 - sum(shares)


def setup_total(run, *counters: str):
    """The sum of the counters over set-up: from the capture's opening,
    before the entry is built, to the end of the warm-up."""
    recs = [run.counters0.get(c) for c in counters]
    if None in recs:
        return None
    return float(sum(r.get("value", 0.0) for r in recs))
