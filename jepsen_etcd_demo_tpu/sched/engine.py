"""Length-bucketed corpus scheduler — the batched-checking throughput engine.

The batched entry points used to pad EVERY history in a corpus to the
longest member's step count (wgl3.batch_steps3: one shared r_cap), so a
corpus of mostly-short histories paid the long tail's padding on every
lane, and every distinct corpus shape compiled its own kernel. This
module is the scheduler PR 1's step-padding gauge argued for:

  * histories are grouped into {2^k, 1.5*2^k} PADDED-LENGTH BUCKETS of
    their return-step counts (floor tunable via
    limits().step_bucket_floor), bounding per-bucket padding waste to
    <1.5x and capping distinct jit compilations per kernel to the bucket
    count;
  * the batch axis is bucketed too (all-pad histories, stripped from
    results), so corpora of varying size reuse the same compiled shapes;
  * launches are dispatched ASYNC and fetched at drain: while the device
    runs bucket N, the host stacks/transfers bucket N+1 (the corpus-level
    face of the double-buffered chunk pipelining in ops/wgl2+wgl3);
  * resolved checker callables live in the sched kernel LRU
    (compile_cache.py) keyed by (kernel, model, bucket-shape), with
    hit/miss accounting behind the bench's cache_hit_rate field.

Verdicts are bit-identical to the unbatched path: bucket pads are
all-pad scan steps (targets = -1) that the kernels skip by construction,
and batch pads are all-pad histories stripped before assembly
(tests/test_sched.py pins equivalence on golden + fuzz corpora).
"""

from __future__ import annotations

import threading
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Sequence

import numpy as np

from .. import obs
from ..ops.limits import limits


def assign_step_buckets(step_counts: Sequence[int]) -> list[int]:
    """Padded-length bucket per entry — a pure function of the counts and
    the active limits profile, so bucket assignment is deterministic and
    order-independent (same count -> same bucket wherever it sits in the
    corpus)."""
    from ..ops import wgl3

    floor = limits().step_bucket_floor
    return [wgl3.step_bucket(int(n), floor=floor) for n in step_counts]


def lpt_shard_order(step_counts: Sequence[int], n_shards: int
                    ) -> list[int]:
    """Deterministic LPT (longest-processing-time) bin packing of a
    padded launch's histories into the mesh's contiguous per-shard
    blocks, balanced by REAL step count — the shard-aware half of the
    bucketed scheduler (limits().shard_bucket_mode).

    The sharded routes split the [B] axis into n_shards equal
    CONTIGUOUS blocks (_shard_real_steps is the accounting twin), so
    batch ORDER is the packing degree of freedom: the legacy
    append-pads-at-the-end order loads the leading shards with every
    real step while the trailing shards sweep all-pad lanes (on one
    8-device run, real steps per shard [3913, ..., 2305, 0, 0]). This
    permutation assigns histories longest-first to the least-loaded
    shard with remaining capacity (ties -> lowest shard id), then
    restores ascending original order within each shard, so equal-work
    shards retire their bucket together and the mesh stops idling
    behind one straggler.

    Returns the permutation `perm` such that position j of the packed
    launch holds original entry perm[j]; identity when the batch does
    not split evenly (mirroring _shard_real_steps' degraded contract) or
    there is nothing to balance. Pure and deterministic — same counts,
    same shard count, same order — so verdicts and compiled shapes are
    independent of packing (tests/test_pod_scaling.py pins determinism
    across mesh shapes)."""
    n = len(step_counts)
    if n_shards <= 1 or n == 0 or n % n_shards:
        return list(range(n))
    cap = n // n_shards
    order = sorted(range(n), key=lambda i: (-int(step_counts[i]), i))
    loads = [0] * n_shards
    fill: list[list[int]] = [[] for _ in range(n_shards)]
    for i in order:
        s = min((j for j in range(n_shards) if len(fill[j]) < cap),
                key=lambda j: (loads[j], j))
        fill[s].append(i)
        loads[s] += int(step_counts[i])
    for block in fill:
        block.sort()
    return [i for block in fill for i in block]


def _shard_real_steps(step_counts: Sequence[int], n_shards: int
                      ) -> list[int]:
    """Per-shard real step totals of a padded launch whose [B] axis
    splits into n_shards equal contiguous blocks; one total when the
    batch does not split evenly."""
    b = len(step_counts)
    if n_shards <= 1 or b % n_shards:
        return [int(sum(step_counts))]
    per = b // n_shards
    return [int(sum(step_counts[i * per:(i + 1) * per]))
            for i in range(n_shards)]


def _batch_bucket(n: int, cap: int) -> int:
    """Batch-axis bucket: {2^k, 1.5*2^k} growth from the batch floor,
    capped by the launch-size cap. The sharding-multiple round-up
    happens at the call site AFTER bucketing, because the multiple must
    be derived from the BUCKETED size (a bucket can inflate a 1-history
    part past 1, flipping the launcher onto the sharded kernel)."""
    from ..ops import wgl3

    b = min(wgl3.step_bucket(n, floor=limits().batch_bucket_floor), cap)
    return max(b, n)


def _dense_bucket_launcher(model, cfg, b: int, r: int):
    """Resolved packed checker for one (batch, step) bucket shape,
    through the KernelPlan layer (plan/dispatch.py plan_dense_batch —
    the one copy of the sharded-vs-local and pallas-vs-XLA routing this
    function used to duplicate): run(tabs, act, tgt) -> DEVICE packed
    i32 rows. The single-device pallas route emits i32[b, 5]
    (wgl3.PACKED_FIELDS); the XLA routes emit i32[b, 6]
    (wgl3.PACKED_FIELDS_XLA — the live-tile telemetry column rides
    along). The drain unpacks through wgl3.unpack_np, which accepts
    both widths — that dual-width contract is the one jtflow pins
    (doc/analysis.md "Contracts"). The plan's cache key carries the
    mesh identity, so an elastic re-shard between runs can only MISS
    the kernel LRU, never serve a stale compiled launch.
    Returns (run, plan) — the plan's label is the kernel name and its
    mesh gives the launch's shard count."""
    from .. import plan as kplan

    p = kplan.plan_dense_batch(model, cfg, n_steps=r, batch=b)
    return kplan.resolve(p), p


def _launch_multiple(model, cfg, b: int, r: int) -> int:
    """The [B]-axis multiple a launch of this shape must pad to."""
    from .. import plan as kplan

    return kplan.launch_multiple(model, cfg, n_steps=r, batch=b)


class _Stats:
    """Per-call corpus accounting. Mutations take the instance lock:
    check_corpus itself records from one thread, but the serve daemon
    (serve/scheduler.py) folds several calls' stats concurrently with
    its dispatch thread and the obs counters ride along — hit/bucket
    accounting must not tear under concurrent submitters (ISSUE 13
    thread-safety pass)."""

    def __init__(self):
        from ..obs.sync import maybe_wrap

        self._lock = maybe_wrap(threading.Lock(),
                                "sched.engine._Stats._lock")
        self.steps_real = 0
        self.steps_padded = 0
        self.launches = 0
        self.buckets: dict[int, int] = {}
        self.sweep_steps_sparse = 0
        self.sweep_steps_dense = 0
        self.configs_pruned = 0
        self.sparse_overflow_rounds = 0

    def record_sweep(self, result: dict) -> None:
        """Fold a long-sweep result's sparse-engine record (ops/
        wgl3_sparse.py) and frontier-dedup accounting (ops/canon.py)
        into the corpus stats — the scheduler's half of the bench/CLI
        sweep exposure."""
        sweep = result.get("sweep")
        dedup = result.get("dedup")
        with self._lock:
            if isinstance(sweep, dict):
                self.sweep_steps_sparse += int(sweep.get("steps_sparse", 0))
                self.sweep_steps_dense += int(sweep.get("steps_dense", 0))
                self.sparse_overflow_rounds += int(
                    sweep.get("overflow_rounds", 0))
            if isinstance(dedup, dict):
                self.configs_pruned += int(dedup.get("configs_pruned", 0))

    def record_launch(self, real: int, b: int, r: int,
                      shard_real: Sequence[int] | None = None) -> None:
        """One launch's steps; `shard_real` (the real steps of each of
        the mesh's shards) on the sharded route, where every shard waits
        for the one with the most real steps."""
        padded = b * r
        with self._lock:
            self.steps_real += real
            self.steps_padded += padded
            self.launches += 1
            self.buckets[r] = self.buckets.get(r, 0) + 1
        m = obs.get_metrics()
        m.counter("sched.steps_real").add(real)
        m.counter("sched.steps_padded").add(padded)
        m.counter("sched.launches").add(1)
        if shard_real:
            m.counter("sched.shard_steps_max").add(
                len(shard_real) * max(shard_real))
            m.counter("sched.shard_launches").add(1)
        if real:
            m.gauge("sched.padding_waste_ratio").set(padded / real)

    def to_dict(self) -> dict:
        with self._lock:
            out = {
                "launches": self.launches,
                "buckets": sorted(self.buckets.items()),
                "steps_real": self.steps_real,
                "steps_padded": self.steps_padded,
                "padding_waste": (round(
                    self.steps_padded / self.steps_real, 4)
                    if self.steps_real else 0.0),
                "sweep_steps_sparse": self.sweep_steps_sparse,
                "sweep_steps_dense": self.sweep_steps_dense,
                "configs_pruned": self.configs_pruned,
                "sparse_overflow_rounds": self.sparse_overflow_rounds,
            }
        return out


#: The numeric stats-dict fields a multi-call consumer folds (the CLI
#: corpus verb sums across models; the campaign engine sums across
#: check waves). One list so the two cannot drift from to_dict().
FOLDABLE_STATS = ("launches", "steps_real", "steps_padded",
                  "sweep_steps_sparse", "sweep_steps_dense",
                  "configs_pruned", "sparse_overflow_rounds")


def fold_stats(total: dict, stats: dict) -> dict:
    """Accumulate one check_corpus stats dict into a running total
    (missing keys initialize to 0; padding_waste is derived by the
    consumer from the folded step counters)."""
    for f in FOLDABLE_STATS:
        total[f] = total.get(f, 0) + int(stats.get(f, 0) or 0)
    return total


def check_corpus(encs: Sequence, model=None, f_cap: int = 256
                 ) -> tuple[list[dict], str, dict]:
    """Check a corpus of encoded histories through the bucketed scheduler;
    returns (per-history results aligned with `encs`, kernel_name —
    "mixed" when histories split across backends, stats dict).

    Routing policy is SHARED with ops/wgl3_pallas.check_batch_encoded_auto
    (partition_dense / run_long_dense / ladder_tail live there, in one
    copy) — the scheduler only changes HOW the dense majority is padded
    and launched, never which kernel checks what."""
    from ..ops import wgl3, wgl3_pallas
    from ..ops.encode import (encode_return_steps, reslot_events,
                              stack_return_steps)

    if model is None:
        from ..models import CASRegister

        model = CASRegister()
    stats = _Stats()
    if len(encs) <= 1:
        # Single histories keep the auto router's full treatment (the
        # oracle latency route included) — nothing to bucket.
        results, kernel = wgl3_pallas.check_batch_encoded_auto(encs, model)
        return results, kernel, stats.to_dict()

    with obs.get_tracer().span("sched.check_corpus",
                               histories=len(encs)) as sp:
        # Backend health (obs/health.py): passive ok/failure notes from
        # the launch drain below.
        supervisor = obs.health.get_supervisor()
        results: list[Any] = [None] * len(encs)
        kernels: set[str] = set()

        with obs.phase("sched.pack"):
            dense_idx, general_idx = wgl3_pallas.partition_dense(encs,
                                                                 model)
            cfg = None
            if dense_idx:
                k = max(wgl3.tight_k_slots(encs[i]) for i in dense_idx)
                cfg = wgl3.dense_config(
                    model, k, max(encs[i].max_value for i in dense_idx))
                if cfg is None:
                    # Individually feasible but not under one shared
                    # geometry: ladder each (rare extreme — same policy
                    # as the auto router).
                    general_idx = sorted(general_idx + dense_idx)
                    dense_idx = []
            lim = limits()
            # Step counts from the event rows; the short lane expands its
            # return steps per launch, straight into the stacked arrays.
            n_steps = {i: _return_count(encs[i]) for i in dense_idx}
            long_idx, short_idx = [], []
            for i in dense_idx:
                (long_idx if n_steps[i] > lim.long_scan_max
                 else short_idx).append(i)
            # The bucketed batched lanes: group by padded step length.
            buckets: dict[int, list[int]] = {}
            for i, r in zip(short_idx,
                            assign_step_buckets(
                                [n_steps[i] for i in short_idx])):
                buckets.setdefault(r, []).append(i)

        if dense_idx:
            # Long histories: host-chunked (now pipelined) sweeps, one at
            # a time — arrays are never stacked. Eligible geometries ride
            # the sparse active-tile engine automatically
            # (wgl3.check_steps3_long -> sparse_plan); the per-mode step
            # counts land in the stats dict.
            for i in long_idx:
                e = encs[i]
                one = wgl3_pallas.run_long_dense(
                    encode_return_steps(
                        reslot_events(e, k) if e.k_slots != k else e),
                    model, cfg)
                results[i] = one
                kernels.add(one["kernel"])
                stats.record_sweep(one)

            # Every bucket's launches dispatch async and are fetched at
            # drain.
            def _fetch_launch(entry):
                part, part_encs, dev, perm = entry
                try:
                    with obs.phase("sched.fetch_wait"):
                        fetched = np.asarray(dev)
                except Exception as e:
                    # The drain fetch is where a dead backend finally
                    # surfaces for async launches — tell the supervisor
                    # before propagating.
                    supervisor.note_failure(f"{type(e).__name__}: {e}",
                                            source="sched.dispatch")
                    raise
                with obs.phase("sched.unpack"):
                    if perm is None:
                        rows = fetched[:len(part)]
                    else:
                        # Shard packing permuted the batch: row j holds
                        # original lane perm[j]; invert to read the real
                        # histories back in part order.
                        rows = fetched[np.argsort(perm)[:len(part)]]
                    out = wgl3.unpack_np(rows)
                    for i, one in zip(part, wgl3.assemble_batch_results(
                            out, part_encs, cfg)):
                        results[i] = one

            # In-flight launch window (plan/dispatch.py LaunchPipeline,
            # depth = limits().pod_pipeline_depth): bucket N+1's host
            # stack + H2D staging overlaps bucket N's device execute,
            # and undrained device results stay bounded — the corpus-
            # level form of the long sweep's double buffering.
            from ..plan import LaunchPipeline

            pipe = LaunchPipeline(resolve=_fetch_launch)
            for r in sorted(buckets):
                idxs = buckets[r]
                # Launch-size cap: stacked bytes for one launch stay
                # inside the tested-good element budget.
                per_hist = max(1, r * (cfg.k_slots * 5 + 1))
                chunk = max(1, lim.stack_element_budget // per_hist)
                for c0 in range(0, len(idxs), chunk):
                    with obs.phase("sched.pack"):
                        part = idxs[c0:c0 + chunk]
                        part_encs = [encs[i] for i in part]
                        # Bucket FIRST, then derive the sharding multiple
                        # from the bucketed size: the launcher picks the
                        # sharded kernel by the PADDED batch, so a part the
                        # bucket inflates past 1 must pad to the device
                        # multiple even though the raw part would have run
                        # single-history (a batch_bucket_floor that is not a
                        # multiple of the device count — any tuned floor on a
                        # pod — crashed here otherwise).
                        b0 = _batch_bucket(len(part), chunk)
                        mult = _launch_multiple(model, cfg, b0, r)
                        b = (b0 + mult - 1) // mult * mult
                        run, plan_obj = _dense_bucket_launcher(model, cfg,
                                                               b, r)
                        # Batch pads are all-pad (0-step) lanes.
                        counts = [n_steps[i] for i in part] + [0] * (
                            b - len(part))
                        real = sum(counts)
                        perm = None
                        shard_real = None
                        n_shards = plan_obj.n_shards
                        if n_shards > 1:
                            if lim.shard_bucket_mode:
                                # Shard-aware packing: permute the padded
                                # batch so contiguous per-shard blocks carry
                                # balanced REAL steps (pads interleave
                                # instead of stacking on the tail shards).
                                perm = lpt_shard_order(counts, n_shards)
                                if perm == list(range(b)):
                                    perm = None
                                else:
                                    counts = [counts[j] for j in perm]
                            shard_real = _shard_real_steps(counts, n_shards)
                        arrays = wgl3.stage_steps3(*stack_return_steps(
                            part_encs, k, r, batch=b, order=perm), real)
                    with obs.phase("sched.dispatch"):
                        dev = run(*arrays)
                    pipe.submit((part, part_encs, dev, perm))
                    stats.record_launch(real, b, r, shard_real)
                    kernels.add(plan_obj.label)
            pipe.drain()
            if pipe.dispatched:
                supervisor.note_ok(source="sched.dispatch")

        if general_idx:
            _check_general(encs, general_idx, model, results, kernels,
                           f_cap)

        sp.set(launches=stats.launches,
               buckets=len(stats.buckets))
        kernel = kernels.pop() if len(kernels) == 1 else (
            "mixed" if kernels else "none")
        return results, kernel, stats.to_dict()


def _return_count(e) -> int:
    """An encoded history's return steps, counted from its event rows."""
    from ..ops.encode import EV_RETURN

    return int(np.count_nonzero(e.events[: e.n_events, 0] == EV_RETURN))


def _check_general(encs, general_idx, model, results, kernels,
                   f_cap: int) -> None:
    """The non-dense partition (wide pending sets / huge values): the
    batched sort-kernel tiers, grouped by return-count bucket so a
    corpus's short sort histories don't pad to its longest, then the
    per-history exact ladder for whatever the tiers couldn't settle —
    the same tail policy as check_batch_encoded_auto."""
    from ..ops import wgl3_pallas

    groups: dict[int, list[int]] = {}
    for i, r in zip(general_idx,
                    assign_step_buckets(
                        [_return_count(encs[i]) for i in general_idx])):
        groups.setdefault(r, []).append(i)
    overflow_seeds: list[tuple[int, int]] = []   # (idx, seed f_cap)
    too_long_all: list[int] = []
    for r in sorted(groups):
        overflowed, too_long, top = wgl3_pallas._batch_general(
            encs, groups[r], model, results, kernels, f_cap=f_cap)
        overflow_seeds.extend(
            (i, wgl3_pallas.LADDER_SEED_FACTOR * top) for i in overflowed)
        too_long_all.extend(too_long)
    wgl3_pallas.ladder_tail(encs, model, results, kernels, too_long_all,
                            overflow_seeds)


# -- async submit/await face (ISSUE 13) -------------------------------------
#
# check_corpus is re-entrant (per-call _Stats, the locked kernel LRU,
# thread-safe obs registries), but the device itself is a serial
# resource: concurrent submitters gain nothing by racing dispatches and
# can interleave compile traces. submit_corpus serializes every
# submitter through ONE process-wide single-worker executor — the serve
# daemon's dispatch loop, tests, and ad-hoc callers all await the same
# queue, so a launch in flight is never preempted by another thread's.

_executor_lock = threading.Lock()
_executor: ThreadPoolExecutor | None = None


def corpus_executor() -> ThreadPoolExecutor:
    """The process-wide single-worker executor corpus launches serialize
    on (created on first use; daemon threads, so interpreter shutdown is
    never blocked on a drained queue)."""
    global _executor
    with _executor_lock:
        if _executor is None:
            # jtlint: disable=JTL505 -- process-lifetime singleton by
            # design (docstring above): one daemon worker thread that
            # serializes every corpus submitter for the life of the
            # process; there is no later point to shut it down from,
            # and daemon=True means it never blocks interpreter exit.
            _executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="sched-corpus")
        return _executor


def submit_corpus(encs: Sequence, model=None, f_cap: int = 256) -> Future:
    """Async submit/await face of :func:`check_corpus`: returns a
    Future resolving to the same (results, kernel, stats) tuple.
    Submissions from any thread serialize on :func:`corpus_executor`,
    so concurrent callers (the serve daemon's coalesced batches, a
    bench arm, a test) never race device dispatches."""
    return corpus_executor().submit(check_corpus, encs, model, f_cap)
