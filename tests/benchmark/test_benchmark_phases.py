"""The phase and set-up metrics on small CPU runs of each cell: every
metric a cell lists reads a number, the phase shares and the untraced
share sum to 100, and a program without the counters reads nothing."""

import pytest
from _small import small_run

from benchmark import run
from benchmark.counters import untraced_share, window_share
from jepsen_etcd_demo_tpu import obs

CELLS = ("register-indep", "register-10k")
COUNTER_METRICS = {"split_pct.throughput", "pack_pct.throughput",
                   "dispatch_pct.throughput", "fetch_wait_pct.throughput",
                   "witness_pct.throughput", "gc_pct.throughput",
                   "untraced_pct.throughput", "compile_s.setup",
                   "cache_misses.setup"}


@pytest.fixture(scope="module")
def runs():
    """One small run per cell, with the Run its metric readers see."""
    seen = {}

    class Spy(run.Run):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen["run"] = self

    out = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(run, "Run", Spy)
        for cell in CELLS:
            result = small_run(cell, seconds=1.0)
            assert result["correct"], result["compared"]
            out[cell] = seen.pop("run")
    return out


@pytest.mark.parametrize("cell", CELLS)
def test_every_new_metric_reads_a_number(runs, cell):
    names = {m["name"] for m in run.load_cell(cell)["per_layer"]}
    mine = names & COUNTER_METRICS
    assert mine == COUNTER_METRICS - (
        set() if cell == "register-indep" else {"split_pct.throughput"})
    for name in sorted(mine):
        v = run.reader(name)(runs[cell])
        assert isinstance(v, float), (name, v)
        assert v >= 0 or name == "untraced_pct.throughput", (name, v)


@pytest.mark.parametrize("cell", CELLS)
def test_phase_shares_and_untraced_sum_to_100(runs, cell):
    r = runs[cell]
    shares = [window_share(r, c) for c in obs.PHASE_SECONDS]
    assert all(s >= 0 for s in shares)
    untraced = untraced_share(r)
    assert 0 <= untraced <= 100
    assert sum(shares) + untraced == pytest.approx(100.0)
    # The timed calls ran the phases the cell's path opens.
    assert r.counter("check.encode_s") > 0
    assert r.counter("sched.fetch_wait_s") > 0
    if cell == "register-indep":
        assert r.counter("check.split_s") > 0
        assert r.counter("check.release_s") > 0


def test_a_program_without_the_counters_reads_nothing():
    """A program from before these counters (the parent of this change,
    under the benchmark's files) leaves every new metric out."""
    bare = run.Run(counters0={}, counters1={}, window_s=10.0, trace=None)
    for name in sorted(COUNTER_METRICS - {"untraced_pct.throughput"}):
        assert run.reader(name)(bare) is None, name
    assert untraced_share(bare) is None
