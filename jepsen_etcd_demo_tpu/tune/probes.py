"""Autotuner probes — small deterministic microbenchmarks per knob group.

Each probe group builds its fixtures ONCE (fixed-seed histories from
utils/fuzz.py, so two tune runs on the same machine measure the same
work) and then times the real production code path — the same kernels,
the same routers — under candidate `KernelLimits` overrides installed
via `set_limits`. Probes measure; the search (tune/search.py) decides.

Groups (the `group` metadata on KernelLimits fields, ops/limits.py):

  dense_sweep  — the host-chunked dense long sweep
                 (wgl3.check_steps3_long): events/s vs `long_scan_chunk`
                 and `dense_cell_budget_chunked` (conservative-down
                 candidates only — [envelope] envelope fields).
  sparse       — the sparse active-tile engine's crossover
                 (ops/wgl3_sparse.py): live-tile density sweep tuning
                 `sparse_density_threshold_pct` / `sparse_min_tiles`
                 (PR 3 hardcoded a CPU measurement for these).
  sched        — the bucketed corpus scheduler (sched/engine.py):
                 padding-vs-compile tradeoff for `step_bucket_floor` /
                 `batch_bucket_floor` on a mixed-length corpus.
  pipeline     — `sched_pipeline_depth` (resumable sort sweep,
                 wgl2.check_steps_resumable) and `sched_poll_chunks`
                 (pipelined dense long sweep).
  pallas       — `pallas_step_chunk` / `max_k_pallas` where Mosaic
                 compiles (skipped wholesale off-TPU).
  stream       — the streaming check engine (stream/engine.py):
                 `stream_flush_ops` / `stream_max_lag_chunks` via a
                 full-speed replay of a fixed keyed op stream through
                 the stable-prefix dispatcher.
  dedup        — the frontier canonicalization pass + sparse seen memo
                 (ops/canon.py / ops/wgl3_sparse.py): a symmetry-heavy
                 history (small value domain, many forever-pending
                 duplicates) through the chunked dense sweep, tuning
                 `dedup_mode` / `dedup_hash_slots` /
                 `dedup_min_frontier`. Exact in every mode, so the
                 search is free to pick whatever measures fastest.
  elle         — the elle transitive-closure engine (ops/cycles.py /
                 ops/cycles_tiled.py / stream/elle.py):
                 `elle_dense_max_nodes` / `elle_tile` /
                 `elle_batch_floor` / `elle_density_threshold_pct` /
                 `elle_stream_flush` on fixed-seed dependency graphs
                 and a fixed txn stream (every route verdict-exact).
  spill        — the out-of-core spill tier (store/spill.py +
                 stream/longhaul.py): `host_spill_mode` /
                 `host_rss_budget_mb` / `spill_compress_mode` /
                 `encode_cache_cap_mb` via a fixed multi-segment
                 long-haul mini-lane through a scratch SpillDir
                 (verdict-exact in every mode).

Every measurement is warmup-then-best-of-N: the warmup call eats the
compile (the persistent XLA cache makes it cheap on re-tunes), the min
over repeats estimates the machine's floor — the quantity routing
decisions care about — rather than a load-dependent mean.
"""

from __future__ import annotations

import random
import time
from dataclasses import replace
from typing import Callable

from ..ops.limits import KernelLimits, limits, set_limits

# Fixed probe seeds — one per group, so fixtures never alias.
SEED_DENSE = 0xD5E1
SEED_SPARSE = 0x5BA5
SEED_SCHED = 0x5C4ED
SEED_PIPE = 0x919E
SEED_PALLAS = 0x9A11
SEED_STREAM = 0x57E4
SEED_DEDUP = 0xDED0
SEED_ELLE = 0xE17E
SEED_POD = 0x90D5
SEED_SPILL = 0x5B11

# Per-knob limit pins applied UNDER the candidate override while probing
# (e.g. the density threshold only matters once the sparse engine is
# eligible, so its probe pins the engagement floor to 1).
KNOB_PINS: dict[str, dict[str, int]] = {
    "sparse_density_threshold_pct": {"sparse_min_tiles": 1},
    # The memo only runs under the sparse engine; the min-frontier gate
    # only matters once the table pass is forced on.
    "dedup_hash_slots": {"sparse_mode": 2, "sparse_min_tiles": 1},
    "dedup_min_frontier": {"dedup_mode": 2},
    # Spill-window / codec knobs only matter once the out-of-core tier
    # is actually engaged, so their probes pin force-spill.
    "host_rss_budget_mb": {"host_spill_mode": 2},
    "spill_compress_mode": {"host_spill_mode": 2},
    "encode_cache_cap_mb": {"host_spill_mode": 2},
}


class ProbeContext:
    """Shared probe configuration. `scale` shrinks every fixture
    proportionally (the tier-1 CPU smoke runs at scale ~0.1, seconds of
    wall clock); `repeats` is the best-of count per measurement."""

    def __init__(self, model=None, scale: float = 1.0, repeats: int = 2):
        if model is None:
            from ..models import CASRegister

            model = CASRegister()
        self.model = model
        self.scale = max(0.02, float(scale))
        self.repeats = max(1, int(repeats))

    def n(self, full: int, floor: int) -> int:
        return max(floor, int(full * self.scale))


def _timed(fn: Callable[[], object], repeats: int) -> float:
    fn()                          # warmup: compile + caches
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _with_overrides(overrides: dict[str, int], fn: Callable[[], object],
                    repeats: int) -> float:
    """Time `fn` under a fresh default profile + `overrides`. The base is
    the DATACLASS default, not the currently-resolved profile: the tuner
    measures what a shipped profile would do, not what the previous
    profile already did. Env overrides still win (ops/limits.py
    precedence), which is why the search excludes env-pinned knobs."""
    prev = set_limits(replace(KernelLimits(), **overrides))
    try:
        return _timed(fn, repeats)
    finally:
        set_limits(prev)


class _LongSweepFixture:
    """One fixed-seed register history prepared for the chunked dense
    long sweep — shared shape between the dense_sweep, pipeline, and
    pallas groups (each with its own seed/geometry)."""

    def __init__(self, ctx: ProbeContext, seed: int, n_ops: int,
                 k_slots: int | None = None, budget: int | None = None):
        from ..ops import wgl3
        from ..ops.encode import (encode_register_history,
                                  encode_return_steps, reslot_events)
        from ..utils.fuzz import gen_register_history

        h = gen_register_history(random.Random(seed), n_ops=n_ops,
                                 n_procs=8, p_info=0.002)
        enc = encode_register_history(h, k_slots=32)
        k = k_slots if k_slots is not None else wgl3.tight_k_slots(enc)
        self.cfg = wgl3.dense_config(ctx.model, k, enc.max_value,
                                     budget=budget)
        if self.cfg is None:
            raise RuntimeError(f"probe geometry infeasible (k={k})")
        self.enc = reslot_events(enc, k) if enc.k_slots != k else enc
        self.rs = encode_return_steps(self.enc)
        self.model = ctx.model


class DenseSweepProbe:
    """events/s of the host-chunked dense sweep vs the chunking knobs.
    The history is long enough that `long_scan_chunk` candidates below
    its step count really change the chunk loop's shape."""

    knobs = ("long_scan_chunk", "dense_cell_budget_chunked")

    def __init__(self, ctx: ProbeContext):
        self.ctx = ctx
        self.fix = _LongSweepFixture(ctx, SEED_DENSE,
                                     n_ops=ctx.n(4000, 400))

    def candidates(self, knob: str) -> list[int] | None:
        if knob == "long_scan_chunk":
            # Ladder below the fixture's step count so every candidate
            # exercises a different chunk-loop shape; the conservative
            # clamp (<= default) is applied by the search.
            steps = self.fix.rs.n_steps
            return sorted({max(256, steps // 8), max(256, steps // 4),
                           max(256, steps // 2), 16384})
        return None

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import wgl3

        return _with_overrides(
            overrides,
            lambda: wgl3.check_steps3_long(self.fix.rs, self.fix.model,
                                           self.fix.cfg),
            self.ctx.repeats)


class SparseProbe:
    """Sparse-vs-dense crossover: a WIDE table (k_slots beyond the
    history's real concurrency — the tiny-live-frontier regime the
    sparse engine exists for) swept under candidate density thresholds
    and engagement floors. Chosen values replace PR 3's hardcoded CPU
    measurement with THIS machine's."""

    knobs = ("sparse_density_threshold_pct", "sparse_min_tiles")

    def __init__(self, ctx: ProbeContext):
        self.ctx = ctx
        k = 13 if ctx.scale < 0.5 else 18
        self.fix = _LongSweepFixture(ctx, SEED_SPARSE,
                                     n_ops=ctx.n(1500, 150),
                                     k_slots=k, budget=1 << 28)

    def tiles(self) -> int:
        lim = limits()
        w = self.fix.cfg.n_masks // 32
        return max(1, w // lim.sparse_tile_words)

    def candidates(self, knob: str) -> list[int] | None:
        if knob == "sparse_min_tiles":
            # Bracket THIS geometry's tile count: the engage/stay-dense
            # decision is what the candidates toggle.
            t = self.tiles()
            return sorted({max(1, t // 2), t, 2 * t, 2048})
        return None

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import wgl3

        return _with_overrides(
            overrides,
            lambda: wgl3.check_steps3_long(self.fix.rs, self.fix.model,
                                           self.fix.cfg),
            self.ctx.repeats)


class SchedProbe:
    """Bucketed-scheduler floors on a fixed mixed-length corpus: lower
    floors pad tighter but compile more shapes; the measurement is the
    warm steady state (the persistent XLA cache amortizes compiles
    across processes, so steady-state is what production pays)."""

    knobs = ("step_bucket_floor", "batch_bucket_floor")

    def __init__(self, ctx: ProbeContext):
        from ..ops.encode import encode_register_history
        from ..utils.fuzz import gen_register_history

        self.ctx = ctx
        rng = random.Random(SEED_SCHED)
        n_hist = ctx.n(192, 24)
        hi = ctx.n(300, 60)
        self.encs = [encode_register_history(
            gen_register_history(rng, n_ops=rng.randrange(10, hi),
                                 n_procs=8, p_info=0.002), k_slots=32)
            for _ in range(n_hist)]

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from .. import sched

        return _with_overrides(
            overrides,
            lambda: sched.check_corpus(self.encs, self.ctx.model),
            self.ctx.repeats)


class PipelineProbe:
    """Chunk-pipelining depth knobs. `sched_pipeline_depth` drives the
    resumable sort sweep's in-flight window (only buys anything on
    high-latency backends — which is the point of measuring it HERE);
    `sched_poll_chunks` drives the dense long sweep's death-poll
    interval."""

    knobs = ("sched_pipeline_depth", "sched_poll_chunks")

    def __init__(self, ctx: ProbeContext):
        self.ctx = ctx
        self.fix = _LongSweepFixture(ctx, SEED_PIPE, n_ops=ctx.n(3000, 300))

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import wgl2, wgl3

        if knob == "sched_pipeline_depth":
            fn = lambda: wgl2.check_steps_resumable(  # noqa: E731
                self.fix.rs, self.fix.model, chunk=256)
        else:
            fn = lambda: wgl3.check_steps3_long(  # noqa: E731
                self.fix.rs, self.fix.model, self.fix.cfg)
        return _with_overrides(overrides, fn, self.ctx.repeats)


class PallasProbe:
    """Mosaic-compiled resumable kernel knobs — only meaningful where
    pallas actually compiles; constructing the probe off-TPU raises
    ProbeUnavailable and the search records the group as skipped."""

    knobs = ("pallas_step_chunk", "max_k_pallas")

    def __init__(self, ctx: ProbeContext):
        from ..ops import wgl3_pallas

        if not wgl3_pallas.pallas_available():
            raise ProbeUnavailable("pallas unavailable on this backend")
        self.ctx = ctx
        self.fix = _LongSweepFixture(ctx, SEED_PALLAS,
                                     n_ops=ctx.n(3000, 300))
        # Second fixture at K=13 (>= 2 work-list blocks): with the
        # sparse work-list kernel routed by default wherever the
        # density signal selects it (wgl3_pallas.pallas_sparse_selected,
        # ISSUE 10), the tuned pallas geometry must be measured through
        # BOTH kernels — the chosen step chunk sizes the sparse
        # kernel's colmask blocks and 8-slot metadata windows too.
        self.fix_sparse = _LongSweepFixture(ctx, SEED_PALLAS + 1,
                                            n_ops=ctx.n(1200, 120),
                                            k_slots=13, budget=1 << 28)

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import wgl3_pallas

        def both():
            wgl3_pallas.check_steps3_long_pallas(
                self.fix.rs, self.fix.model, self.fix.cfg)
            wgl3_pallas.check_steps3_long_pallas(
                self.fix_sparse.rs, self.fix_sparse.model,
                self.fix_sparse.cfg)

        return _with_overrides(overrides, both, self.ctx.repeats)


class StreamProbe:
    """Streaming check engine knobs: a fixed keyed op stream (disjoint
    process-id ranges per key, round-robin interleaved — the record
    order a live independent-key run produces) replayed at full feed
    speed through the stable-prefix dispatcher (stream/engine.py).
    Measures the chunk-size / poll-lag tradeoff: smaller chunks start
    overlapping earlier but pay more dispatches, more frequent death
    polls sync the pipeline."""

    knobs = ("stream_flush_ops", "stream_max_lag_chunks")

    def __init__(self, ctx: ProbeContext):
        from ..utils.fuzz import gen_register_history, interleave_keyed

        self.ctx = ctx
        rng = random.Random(SEED_STREAM)
        n_keys = max(2, ctx.n(8, 2))
        per_key = [gen_register_history(rng, n_ops=ctx.n(1200, 120),
                                        n_procs=8, p_info=0.002)
                   for _ in range(n_keys)]
        self.ops = interleave_keyed(per_key)

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..stream import StreamSession

        def replay():
            session = StreamSession(self.ctx.model, keyed=True)
            for op in self.ops:
                session.feed(op)
            res = session.finalize()
            assert res, "stream probe fixture must stream"
            return res

        return _with_overrides(overrides, replay, self.ctx.repeats)


class DedupProbe:
    """Frontier canonicalization + sparse seen-memo knobs on a
    SYMMETRY-HEAVY fixture: a small value domain with a sizeable
    forever-pending population gives the canonicalization pass real
    equal-effect classes to reduce (a symmetry-free history would
    measure the knobs as pure no-ops). Every mode is verdict-exact
    (ops/canon.py), so the search may pick whatever measures fastest —
    including OFF on machines where the pass never pays."""

    knobs = ("dedup_mode", "dedup_hash_slots", "dedup_min_frontier")

    def __init__(self, ctx: ProbeContext):
        from ..ops import wgl2, wgl3
        from ..ops.encode import (encode_register_history,
                                  encode_return_steps, reslot_events)
        from ..utils.fuzz import gen_register_history

        self.ctx = ctx
        k = 13 if ctx.scale < 0.5 else 16
        h = gen_register_history(random.Random(SEED_DEDUP),
                                 n_ops=ctx.n(2000, 150), n_procs=8,
                                 value_range=2, p_info=0.04)
        enc = encode_register_history(h, k_slots=32)
        self.cfg = wgl3.dense_config(ctx.model, k, max(enc.max_value, 4),
                                     budget=1 << 28)
        if self.cfg is None:
            raise RuntimeError(f"dedup probe geometry infeasible (k={k})")
        self.enc = reslot_events(enc, k) if enc.k_slots != k else enc
        self.rs = encode_return_steps(self.enc)
        # Second fixture for the SORT-LADDER arm: in auto mode the
        # TABLE sweep is canon-free (dedup_mode 0 and 1 compile the
        # same kernel — history_canon_pairs(table=True)), so without
        # this arm the 0-vs-1 candidates would tie and the tuner could
        # persist `off` by timing noise, silently disabling the sort
        # ladder's measured escalation-avoidance win and the seen memo.
        n_sort = ctx.n(200, 60)
        hs = gen_register_history(random.Random(SEED_DEDUP + 1),
                                  n_ops=n_sort, n_procs=8, value_range=1,
                                  p_info=15.0 / n_sort)
        enc_s = encode_register_history(hs, k_slots=32)
        ks = wgl2.sort_k_slots(enc_s)
        self.rs_sort = encode_return_steps(
            reslot_events(enc_s, ks) if enc_s.k_slots != ks else enc_s)
        self.model = ctx.model

    def tiles(self) -> int:
        lim = limits()
        w = self.cfg.n_masks // 32
        return max(1, w // lim.sparse_tile_words)

    def candidates(self, knob: str) -> list[int] | None:
        if knob == "dedup_mode":
            return [0, 1, 2]
        if knob == "dedup_hash_slots":
            # Bracket THIS geometry's tile count: the memo's engage /
            # fail-open decision is what the candidates toggle.
            t = self.tiles()
            return sorted({max(64, t // 2), max(64, t), max(64, 2 * t),
                           4096})
        if knob == "dedup_min_frontier":
            return [0, 16, 64, 256]
        return None

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import wgl2, wgl3

        def both():
            wgl3.check_steps3_long(self.rs, self.model, self.cfg)
            wgl2.check_steps_resumable(self.rs_sort, self.model,
                                       f_cap=64)

        return _with_overrides(overrides, both, self.ctx.repeats)


class ElleProbe:
    """Elle transitive-closure engine knobs (ops/cycles.py /
    ops/cycles_tiled.py / stream/elle.py) on fixed-seed fixtures: a
    corpus of small random dependency graphs (the batched corpus-of-
    graphs lane), one big BLOCK-STRUCTURED sparse graph (contiguous
    per-key chains — real empty tiles for the tiled kernel's occupancy
    work list to skip), and a fixed serial txn stream for the
    streaming flush cadence. Every route is verdict-exact (the closure
    fixpoint is unique), so the search picks whatever measures
    fastest."""

    knobs = ("elle_dense_max_nodes", "elle_tile", "elle_batch_floor",
             "elle_density_threshold_pct", "elle_stream_flush")

    def __init__(self, ctx: ProbeContext):
        import numpy as np

        from ..utils.fuzz import append_txn_ops, gen_append_txns

        self.ctx = ctx
        rng = np.random.default_rng(SEED_ELLE)
        # Small-graph corpus: the batched bucketed launches.
        self.small = []
        for _ in range(max(8, ctx.n(48, 8))):
            n = int(rng.integers(16, max(32, ctx.n(300, 40))))
            a = rng.random((n, n)) < 3.0 / n
            np.fill_diagonal(a, False)
            self.small.append(a)
        # One big block-diagonal sparse graph: per-key chains with a
        # few intra-block cross edges — the tiled kernel's regime.
        nb = max(600, ctx.n(5000, 600))
        blk = 100
        big = np.zeros((nb, nb), bool)
        for b0 in range(0, nb - 1, blk):
            hi = min(nb, b0 + blk)
            for i in range(b0, hi - 1):
                big[i, i + 1] = True
            extra = rng.integers(b0, hi, size=(max(2, blk // 8), 2))
            for s, d in extra:
                if s < d:
                    big[s, d] = True
        self.big = big
        # Streaming fixture: a fixed serial append-txn op stream.
        import random as _random

        self.ops = append_txn_ops(gen_append_txns(
            _random.Random(SEED_ELLE), n_txns=ctx.n(1500, 150),
            n_keys=8, max_len=2))

    def candidates(self, knob: str) -> list[int] | None:
        if knob == "elle_tile":
            return [128, 256, 512]
        if knob == "elle_dense_max_nodes":
            # Bracket the big fixture's node count: the dense-vs-
            # decomposed routing decision is what candidates toggle.
            n = self.big.shape[0]
            return sorted({max(128, n // 4), max(128, n // 2), n, 2048})
        return None

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..ops import cycles

        if knob == "elle_stream_flush":
            from ..checkers.elle import ElleChecker
            from ..stream.elle import ElleStreamSession

            checker = ElleChecker()

            def replay():
                session = ElleStreamSession(checker)
                for op in self.ops:
                    session.feed(op)
                res = session.finalize()
                assert res, "elle stream probe fixture must stream"
                return res

            return _with_overrides(overrides, replay, self.ctx.repeats)
        if knob == "elle_batch_floor":
            return _with_overrides(
                overrides, lambda: cycles.cycle_masks_batch(self.small),
                self.ctx.repeats)
        if knob in ("elle_tile", "elle_density_threshold_pct"):
            from ..ops import cycles_tiled

            return _with_overrides(
                overrides,
                lambda: cycles_tiled.cycle_mask_tiled(self.big),
                self.ctx.repeats)
        # elle_dense_max_nodes: the auto route end to end on the big
        # graph — dense squaring below the crossover, decomposition
        # above it.
        return _with_overrides(
            overrides, lambda: cycles.cycle_mask(self.big),
            self.ctx.repeats)


class PodProbe:
    """Pod-scaling knobs (ISSUE 17) on a fixed ragged corpus through
    the mesh-sharded batch lane: `encode_mode` trades host encode + big
    packed-table H2D against the on-device expansion; `shard_bucket_mode`
    toggles the LPT shard packing; `pod_pipeline_depth` sets how many
    launches the dispatch window keeps in flight. All three only earn
    their keep on real multi-device meshes — measuring HERE (the current
    platform's mesh, virtual or not) is the point, exactly like the
    pipeline group."""

    knobs = ("encode_mode", "pod_pipeline_depth", "shard_bucket_mode")

    def __init__(self, ctx: ProbeContext):
        import jax

        from ..ops.encode import encode_register_history
        from ..utils.fuzz import gen_register_history

        if jax.device_count() < 2:
            raise ProbeUnavailable(
                "pod probe needs a multi-device mesh (the knobs are "
                "no-ops on one device)")
        self.ctx = ctx
        rng = random.Random(SEED_POD)
        n_hist = ctx.n(128, 16)
        hi = ctx.n(240, 48)
        self.encs = [encode_register_history(
            gen_register_history(rng, n_ops=rng.randrange(10, hi),
                                 n_procs=8, p_info=0.002), k_slots=32)
            for _ in range(n_hist)]

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        from ..parallel import dense as pdense

        return _with_overrides(
            overrides,
            lambda: pdense.check_batch_sharded(self.encs, self.ctx.model),
            self.ctx.repeats)


class SpillProbe:
    """Out-of-core spill-tier knobs (ISSUE 20): a fixed multi-segment
    long-haul mini-lane (stream/longhaul.py) replayed through an active
    scratch SpillDir. host_spill_mode off/auto/force trades disk I/O
    against host RSS; spill_compress_mode trades canon-quotient encode
    cycles against checkpoint bytes; the RSS budget and encode-cache
    cap steer the in-RAM window and GC cadence. Every mode is
    verdict-exact (store/spill.py), so the search may pick whatever
    measures fastest on this host's disk."""

    knobs = ("host_spill_mode", "host_rss_budget_mb",
             "spill_compress_mode", "encode_cache_cap_mb")

    def __init__(self, ctx: ProbeContext):
        self.ctx = ctx
        self.events = ctx.n(60_000, 6_000)
        self.seg_events = max(1024, ctx.n(8192, 1024))

    def measure(self, knob: str, overrides: dict[str, int]) -> float:
        import shutil
        import tempfile

        from ..store import spill
        from ..stream import longhaul

        def lane():
            td = tempfile.mkdtemp(prefix="jepsen-spill-probe-")
            try:
                with spill.spilling(td):
                    res = longhaul.run_longhaul(
                        self.ctx.model, events=self.events,
                        seg_events=self.seg_events, seed=SEED_SPILL,
                        resume=False)
                assert res["survived"], \
                    "spill probe fixture must survive"
                return res
            finally:
                shutil.rmtree(td, ignore_errors=True)

        return _with_overrides(overrides, lane, self.ctx.repeats)


class ProbeUnavailable(RuntimeError):
    """This probe group cannot run on this backend (recorded as skipped,
    never an error — a CPU tune simply has no pallas lane)."""


# Group name -> probe class; the search instantiates lazily (fixture
# encoding costs host time) and in this order.
PROBES = {
    "dense_sweep": DenseSweepProbe,
    "sparse": SparseProbe,
    "sched": SchedProbe,
    "pipeline": PipelineProbe,
    "pallas": PallasProbe,
    "stream": StreamProbe,
    "dedup": DedupProbe,
    "elle": ElleProbe,
    "pod": PodProbe,
    "spill": SpillProbe,
}
