"""Kernel deployment limits — one tunable profile, not inlined constants.

The dense/sort/pallas kernels' program-size and allocation thresholds
live here as ONE dataclass instead of magic numbers in library control
flow (round-2 review, VERDICT.md weak #4).

Three kinds of fields, flagged per-field via ``field(metadata=...)`` and
surfaced in doc/perf.md's reference table (tools/check_limits_doc.py
enforces the tag + safe range on every field):
  * [envelope] — program-size and allocation envelope of one launch
    (scan length, sort rows, stacked-batch elements, SMEM words). Set
    conservatively; raising one on a roomier device is the safe
    direction only where the chip's compiler still accepts the launch
    (tests/test_tpu_compile.py compiles the main-path kernels for a
    described v5e). The autotuner (tune/) never probes these past their
    default in the RISKY direction.
  * [arch]     — derived from TPU architecture (VMEM block budget, SMEM
    size, unroll cost) or a semantic mode switch. Portable across
    deployments of the same chip family; not searched by default.
  * [tunable]  — pure performance knobs (chunk sizes, bucket floors,
    crossovers, pipeline depths) whose best value is a property of the
    MACHINE, measured by ``jepsen-tpu tune`` and persisted per
    ``(backend, device kind, device count)`` (tune/profile.py).

Resolution precedence, per field (doc/perf.md "Autotuning"):

    JEPSEN_TPU_LIMIT_<FIELD> env  >  set_limits()  >  tuned profile
                                  >  dataclass default

``limits()`` returns the resolved instance; ``limits_provenance()`` says
where each field's value came from (``env``/``set``/``tuned``/
``default``) — ``tools/print_profile.py`` dumps both. A malformed env
override (non-int, or outside the field's safe range) raises
:class:`LimitsEnvError` naming the variable and the accepted range, at
import/reload time — loudly, not as a bare ``ValueError`` from ``int()``.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields, replace

# Provenance kinds for the doc/tooling contract (tools/check_limits_doc.py
# asserts every field's doc row carries its tag + safe range).
KINDS = ("envelope", "arch", "tunable")


def _f(default: int, kind: str, lo: int, hi: int, *, group: str | None = None,
       conservative: str | None = None):
    """A KernelLimits field: default + machine-readable tuning metadata.

    kind         — worker/arch/tunable (module docstring).
    (lo, hi)     — the SAFE range: env overrides and tuner candidates are
                   validated against it.
    group        — probe group the autotuner measures this knob under
                   (tune/probes.py); None = not searched.
    conservative — for [envelope] fields the tuner may still touch: "down"
                   means only values <= default are safe to probe ("up"
                   the reverse). The search clamps candidates accordingly.
    """
    assert kind in KINDS, kind
    return field(default=default, metadata={
        "kind": kind, "range": (lo, hi), "group": group,
        "conservative": conservative})


@dataclass(frozen=True)
class KernelLimits:
    # [envelope] Largest dense table (S * 2^K cells) the DEFAULT dense
    # kernel builds per history. Past K ~ 17 the live frontier is
    # invariably tiny relative to the lattice (the sort kernel wins), and
    # a K=20 dense chunk measured ~35 s per 4k steps — one program that
    # long blocks the device for every other caller.
    dense_cell_budget: int = _f(1 << 20, "envelope", 1 << 8, 1 << 30)
    # [envelope] Relaxed cell budget for the CHUNKED dense rung (host-
    # driven loop of small scans; each program stays short, so only
    # allocation size limits the table).
    dense_cell_budget_chunked: int = _f(1 << 26, "envelope", 1 << 8, 1 << 32,
                                        group="dense_sweep",
                                        conservative="down")
    # [envelope] Step-axis chunk for the host-driven long-scan loop: 16k
    # steps keeps each scan program short. Probed by the dense_sweep
    # tune group in the conservative (smaller) direction only.
    long_scan_chunk: int = _f(16384, "envelope", 256, 1 << 20,
                              group="dense_sweep", conservative="down")
    # [envelope] Longest single scan program the non-chunked XLA path
    # emits.
    long_scan_max: int = _f(32768, "envelope", 1024, 1 << 20)
    # [envelope] Sort rows (f_cap * (k_slots + 1) keys) per launch.
    sort_row_budget: int = _f(1 << 21, "envelope", 1 << 10, 1 << 28)
    # [envelope] Element budget for a stacked batch launch of the sort
    # kernel (keeps host->device transfers a few hundred MB).
    stack_element_budget: int = _f(1 << 26, "envelope", 1 << 12, 1 << 32)
    # [arch] The pallas kernel unrolls the slot sweep K times and carries a
    # u32[S, 2^(K-5)] table in VMEM; K=16 is 64 KiB of table and a sane
    # compile time.
    max_k_pallas: int = _f(16, "arch", 5, 20, group="pallas",
                           conservative="down")
    # [arch] Return steps per colmask block: 512 x (8,128) u32 = 2 MiB,
    # double-buffered well inside the 16 MiB VMEM budget. Probed by the
    # pallas tune group where Mosaic compiles.
    pallas_step_chunk: int = _f(512, "arch", 64, 4096, group="pallas")
    # [envelope] Steps per pallas launch of one history; longer histories
    # run as resumable windows of this many steps (check_steps3_long_
    # pallas). 16k steps is 64 KiB of SMEM targets.
    max_r_pallas: int = _f(16384, "envelope", 256, 1 << 20)
    # [arch] SMEM int32 words one pallas launch may fill (wgl3_pallas.
    # smem_words: the lane-padded targets table + lengths + results).
    # A v5e core has 1 MiB = 2^18 words; 4096 words stay free for
    # Mosaic's own scalars. Bigger batches split into several launches.
    max_prefetch_pallas: int = _f((1 << 18) - 4096, "arch", 1 << 10, 1 << 18)
    # [tunable] Event-count crossover below which a SINGLE history on a
    # live TPU backend routes to the exact host oracle instead of a
    # device launch: the dispatch+fetch round trip exceeds the oracle's
    # whole runtime at tutorial scale. -1 (default) = MEASURED per
    # platform at first use (ops/calibrate.py: dispatch floor x oracle
    # events/s, persisted in the tuned profile); 0 = never route
    # (bench.py pins 0 for its kernel lanes); >0 = fixed crossover.
    # Batches are never routed regardless.
    oracle_crossover_events: int = _f(-1, "tunable", -1, 1 << 16)
    # [arch] Concurrency ceiling for the oracle route: the frontier can
    # hold up to 2^pending configurations per state, so a wide-pending
    # history must take the capped/budgeted device ladder even when its
    # event count is tiny. 12 pending ops bounds the closure at ~4k
    # masks/state — comfortably inside the config budget below.
    oracle_route_max_pending: int = _f(12, "arch", 1, 20)
    # [arch] Transition-attempt budget for a routed oracle run; on
    # expiry the route abandons the host search and falls through to the
    # device ladder (ADVICE r4: no unbounded exponential host search on
    # the product path). ~2M step_py calls is <1 s of host time.
    oracle_config_budget: int = _f(2_000_000, "arch", 1, 1 << 28)
    # [arch] Histories per pallas program in the grouped batch kernel
    # (tables stacked on a leading group axis; amortizes per-step
    # instruction overhead — measured 1.6-2.1x end-to-end / ~2.3x
    # kernel-side at G=16 on v5e, plateau past 16). 0 or 1 disables
    # grouping; batches smaller than the group stay per-history.
    pallas_group: int = _f(16, "arch", 0, 64)
    # [tunable] Floor of the step-axis length buckets the corpus scheduler
    # (sched/engine.py) and the scan-length bucketing (wgl3.step_bucket)
    # pad to. {2^k, 1.5*2^k} buckets bound per-bucket padding waste to
    # <1.5x and distinct jit compilations per kernel to the bucket count;
    # a lower floor trades a few extra compilations for tighter padding
    # on short-history corpora. 32 chosen from the step-padding gauge
    # (PR 1); the sched tune group measures the padding-vs-compile
    # tradeoff per machine.
    step_bucket_floor: int = _f(32, "tunable", 8, 512, group="sched")
    # [tunable] Floor of the batch-axis buckets the scheduler pads
    # launches to (with all-pad histories, targets=-1 — stripped from
    # results).
    batch_bucket_floor: int = _f(8, "tunable", 1, 128, group="sched")
    # [tunable] In-flight chunks of the double-buffered resumable sort
    # sweep (ops/wgl2.py check_steps_resumable): chunk N+1 dispatches
    # before chunk N's overflow flag is fetched, hiding the per-chunk
    # host<->device round trip. 1 restores the fully synchronous loop;
    # deeper pipelines only buy anything on high-latency
    # backends — which is exactly what the pipeline tune group measures.
    sched_pipeline_depth: int = _f(2, "tunable", 1, 8, group="pipeline")
    # [tunable] Death-poll interval (in chunks) of the pipelined dense
    # long sweep (wgl3.check_steps3_long without a time budget): the
    # early-exit fetch costs a host round trip per poll, so the pipeline
    # only syncs every N chunks; dead chunks in between are near-free
    # (empty closures).
    sched_poll_chunks: int = _f(8, "tunable", 1, 64, group="pipeline")
    # [tunable] History-encoding placement (ops/encode.py routing to the
    # device encoder kernel, ops/encode_device.py): 0 = auto (device on
    # the mesh-sharded batch lane where the packed-table H2D dominates,
    # host elsewhere), 1 = host always, 2 = device whenever the geometry
    # fits a jittable event bucket. Rows are bit-identical in every mode
    # (tests/test_pod_scaling.py pins host/device differentials), so
    # this is purely a transfer/fusion placement choice — the pod tune
    # group measures which side of the H2D boundary wins per machine.
    encode_mode: int = _f(0, "tunable", 0, 2, group="pod")
    # [tunable] In-flight launch window of the pod dispatch pipeline
    # (plan/dispatch.py LaunchPipeline): bucket launch N+1's host prep +
    # H2D staging overlaps launch N's device execute, bounding both the
    # speculative depth and the undrained device-result memory. 1
    # restores the fetch-after-every-launch synchronous loop; the old
    # unbounded drain-at-end behaviour is depth >= the launch count.
    pod_pipeline_depth: int = _f(4, "tunable", 1, 8, group="pod")
    # [tunable] Shard-aware bucketing (sched/engine.py + parallel/
    # dense.py): 1 = split sharded launches into per-step-length buckets
    # and LPT-pack histories into contiguous per-shard blocks balanced
    # by REAL step count, so one ragged straggler no longer pads the
    # whole mesh (the MULTICHIP_r06 smoking gun); 0 = legacy one-bucket
    # corpus padding. Verdicts are bit-identical either way — packing
    # permutes launch order only, never the per-history scan.
    shard_bucket_mode: int = _f(1, "tunable", 0, 1, group="pod")
    # [arch] Entry capacity of the scheduler's in-process kernel LRU
    # (sched/compile_cache.py, keyed by (kernel, model, bucket shape)).
    kernel_cache_entries: int = _f(256, "arch", 16, 4096)
    # [arch] Words of the packed table per occupancy tile of the sparse
    # active-tile sweep engine (ops/wgl3_sparse.py). Power of two; one
    # tile is TILE*32 configs per state row. 8 words (256 configs/state)
    # keeps the occupancy bitmap tiny (W/8 bits) while a gathered tile
    # is still a meaningful vector width.
    sparse_tile_words: int = _f(8, "arch", 1, 64)
    # [tunable] Live-tile density (percent of tiles occupied) above which
    # a closure round runs the DENSE sweep instead of gather->expand->
    # scatter — the direction-optimizing switch (Beamer et al., SC'12):
    # past ~1/4 occupancy the gather/scatter overhead exceeds the work
    # skipped. Applies per round, so a frontier that fills up mid-step
    # crosses over mid-sweep (and back) with no host involvement. The
    # sparse tune group measures the real crossover per machine (PR 3
    # hardcoded a CPU measurement).
    sparse_density_threshold_pct: int = _f(25, "tunable", 1, 100,
                                           group="sparse")
    # [tunable] Static capacity (in tiles) of the sparse engine's gather
    # work list. XLA shapes are static, so the gathered frontier is
    # padded to this many tiles; a round whose live-tile count exceeds
    # it falls back to the dense sweep for that round (never drops
    # configs). Per-round sparse cost is O(cap * tile_words), so the
    # cap bounds worst-case sparse work regardless of K.
    sparse_worklist_cap: int = _f(512, "tunable", 64, 8192)
    # [tunable] Minimum tile count (W / sparse_tile_words) before the
    # sparse engine engages in AUTO mode: below the crossover the dense
    # sweep's straight-line vector code beats the gather/nonzero/scatter
    # overhead even at <1% occupancy. The default (2048 tiles = K >= 19
    # at the default 8-word tile) encodes ONE CPU measurement; the
    # sparse tune group sweeps live-tile density per machine. A TPU's
    # VPU widens the dense side's advantage, so raising this on real
    # hardware is the conservative direction; sparse_mode=2 forces the
    # engine on regardless for measurement.
    sparse_min_tiles: int = _f(2048, "tunable", 1, 1 << 20, group="sparse")
    # [arch] Sweep-mode override for the dense lattice kernels:
    # 0 = auto (sparse engine on eligible geometries, per-round density
    # switch), 1 = dense-only (sparse engine off), 2 = prefer-sparse
    # (density threshold ignored; the work-list capacity still forces
    # dense rounds on overflow — configs are never dropped). 2 is the
    # bench/test lane for exercising the sparse path deterministically.
    sparse_mode: int = _f(0, "arch", 0, 2)
    # [tunable] Frontier dedup/canonicalization mode (ops/canon.py):
    # 0 = auto — canonicalize where frontier size directly drives cost
    # (the resumable sort ladder, wgl2.check_steps_resumable: measured
    # 4x on symmetry-heavy histories via avoided capacity escalations)
    # plus the sparse engine's per-tile seen memo; the packed-TABLE
    # sweeps (dense/sparse/lattice) stay canon-free — their sweep cost
    # is fixed in the table size, so the pass is pure overhead there
    # unless measured otherwise.
    # 1 = off (every kernel byte-identical to the pre-dedup build).
    # 2 = force — the table sweeps canonicalize too (the bench/test
    # lane, or a tuned profile on a machine where the `dedup` probe
    # measured it faster). Exact in every mode: canonicalization is a
    # verdict-preserving quotient (doc/perf.md "Frontier dedup"), so
    # the tuner may search it freely.
    dedup_mode: int = _f(0, "tunable", 0, 2, group="dedup")
    # [tunable] Slot capacity of the sparse engine's device-side `seen`
    # memo (one consumed-popcount slot per occupancy tile, direct
    # indexed — collision-free by construction). Geometries with more
    # tiles than slots FAIL OPEN to no-memo (every live tile re-swept,
    # exactly the pre-dedup behavior) so verdicts stay exact; the memo
    # array costs 4 bytes/slot of device memory per compiled geometry.
    dedup_hash_slots: int = _f(4096, "tunable", 64, 1 << 20, group="dedup")
    # [tunable] Converged-frontier size below which the per-step TABLE
    # canonicalization pass is skipped: the pass costs a few table
    # gathers per symmetry pair, which tiny frontiers never repay.
    # Skipping is always sound (canonicalization is an optimization,
    # not a correctness pass); orthogonal to dedup_mode.
    dedup_min_frontier: int = _f(64, "tunable", 0, 1 << 20, group="dedup")
    # [arch] Route override for the elle transitive-closure engine
    # (ops/cycles.py): 0 = auto (dense squaring below
    # elle_dense_max_nodes; component decomposition + bucketed batch +
    # tiled work-list kernel above), 1 = dense-only (the seed [N, N]
    # matrix-squaring path regardless of size — the bench's baseline
    # arm), 2 = prefer-tiled (the blocked work-list kernel even for
    # small graphs — the bench/test lane for exercising the tiled path
    # deterministically). Exact in every mode: the closure fixpoint is
    # unique, so anomaly verdicts never depend on the route.
    elle_mode: int = _f(0, "arch", 0, 2)
    # [tunable] Node-count crossover below which a single dependency
    # graph routes to the dense [N, N] matrix-squaring kernel: under it
    # the straight-line MXU/BLAS closure beats the decompose/gather
    # overhead; above it the graph is decomposed into weak components
    # checked batched (small) or tiled (large). 2048 encodes ONE CPU
    # measurement; the elle tune probe group measures it per machine.
    elle_dense_max_nodes: int = _f(2048, "tunable", 128, 1 << 16,
                                   group="elle")
    # [tunable] Tile edge of the blocked transitive-closure kernel
    # (ops/cycles_tiled.py); rounded to a multiple of 128 (the MXU/lane
    # geometry). Smaller tiles sharpen occupancy skipping on very
    # sparse closures, larger tiles amortize per-product dispatch.
    elle_tile: int = _f(256, "tunable", 128, 1024, group="elle")
    # [tunable] Batch-axis bucket floor of the corpus-of-graphs closure
    # launches (ops/cycles.py reach_and_cycles_batch): graphs grouped
    # into padded-size buckets pad their batch axis to {2^k, 1.5*2^k}
    # buckets from this floor, so corpora of varying graph counts reuse
    # the same compiled vmapped shapes (the sched bucket discipline
    # applied to dependency graphs).
    elle_batch_floor: int = _f(8, "tunable", 1, 128, group="elle")
    # [tunable] Static capacity (in tile products) of the tiled closure
    # kernel's gather work list. XLA shapes are static, so each sparse
    # round pads its eligible (i, k, j) product set to this many
    # entries; a round whose eligible count exceeds it runs the dense
    # block sweep for that round instead (never drops reachability).
    elle_worklist_cap: int = _f(4096, "tunable", 64, 1 << 16)
    # [tunable] Eligible-product density (percent of nb^3 block
    # products live) above which a tiled closure round runs the dense
    # block sweep instead of gather->matmul->scatter — the
    # direction-optimizing crossover of the wgl3_sparse engine applied
    # to the closure's block products, taken per round.
    elle_density_threshold_pct: int = _f(35, "tunable", 1, 100,
                                         group="elle")
    # [envelope] Padded-cell ceiling (n_pad^2) for one device closure
    # launch, dense or tiled: past it the f32 reachability matrix
    # outgrows what a single launch should allocate, and the closure
    # routes to the exact host Tarjan/SCC oracle instead (same
    # verdicts, no device allocation). 2^28 cells = 16384^2 = 1 GiB
    # f32. The floor sits BELOW the smallest padded graph (128^2 =
    # 2^14) so the oracle route can be force-pinned for certification
    # (the bench elle lane's "tarjan" arm).
    elle_cell_budget: int = _f(1 << 28, "envelope", 1 << 12, 1 << 34,
                               conservative="down")
    # [tunable] Completed txns per incremental dependency-graph
    # re-check of the streaming elle session (stream/elle.py): smaller
    # flushes tighten the --fail-fast falsification bound, larger ones
    # amortize the incremental closure launches.
    elle_stream_flush: int = _f(64, "tunable", 1, 1 << 16, group="elle")
    # [tunable] Return steps per streamed check chunk (stream/engine.py):
    # the stable-prefix dispatcher accumulates this many stable return
    # steps before feeding one resumable dense chunk to the device.
    # Smaller chunks start overlapping with the live run earlier and
    # tighten the fail-fast detection bound; larger chunks amortize
    # per-dispatch overhead (one jitted launch per chunk). Verdicts are
    # chunk-size-independent (the carry chains exactly).
    stream_flush_ops: int = _f(256, "tunable", 8, 1 << 16, group="stream")
    # [tunable] Death-poll bound of the streaming dispatcher: at most
    # this many chunks are dispatched between fetches of the frontier's
    # death flag, so the falsification LAG behind the live run is
    # bounded by stream_max_lag_chunks * stream_flush_ops return steps.
    # 1 polls every chunk (fastest --fail-fast, one host<->device round
    # trip per chunk); deeper lets the async dispatch pipeline run
    # ahead between syncs.
    stream_max_lag_chunks: int = _f(4, "tunable", 1, 64, group="stream")
    # [tunable] Max-linger of the serve daemon's continuous-batching
    # scheduler (serve/scheduler.py): after the first pending request
    # arrives, the dispatcher waits up to this many milliseconds for
    # more requests to coalesce into the same bucketed launch before
    # dispatching. 0 = dispatch immediately (no cross-request
    # coalescing beyond what is already queued); larger values trade
    # per-request latency for batch fill — the capacity-planning knob
    # (doc/serve.md ties it to the sched bucket fill).
    serve_coalesce_ms: int = _f(10, "tunable", 0, 1000)
    # [tunable] Most requests one coalesced serve batch may carry; the
    # dispatcher drains the per-tenant queues weighted-fair up to this
    # many per launch cycle (the batch still splits into sched's
    # {2^k, 1.5*2^k} bucket launches downstream).
    serve_max_batch: int = _f(64, "tunable", 1, 4096)
    # [arch] Per-tenant bound of admitted-but-unfinished serve requests
    # (queued + in a dispatching batch): a tenant at the bound has new
    # submissions rejected (HTTP 429) until verdicts drain — the
    # admission-control half of the serve daemon's backpressure
    # (supervisor state drives the other half: shed/503).
    serve_max_inflight: int = _f(256, "arch", 1, 4096)
    # [tunable] Replica count the fleet supervisor (serve/fleet.py)
    # spawns when `jepsen-tpu serve --fleet` is not given an explicit
    # --replicas: how many `serve --check` daemons share the traffic
    # behind the shape-affine router. The right value is a property of
    # the MACHINE (cores / chips per replica), not the code — more
    # replicas buy isolation of each shard's kernel LRU at the cost of
    # per-replica batch fill (doc/serve.md "Fleet").
    fleet_replicas: int = _f(2, "tunable", 1, 64)
    # [arch] Router spillover policy when a routed replica is
    # unavailable (serve/router.py): 0 = affine-with-spillover (walk
    # the rendezvous preference order past unhealthy/failed replicas —
    # the default), 1 = strict affinity (no spillover; 503 when the
    # owning replica cannot take the request), 2 = random routing
    # (shape affinity off — the bench's comparison arm and the
    # locality-off escape hatch).
    fleet_spillover_mode: int = _f(0, "arch", 0, 2)
    # [arch] Salt mixed into the router's rendezvous hash
    # (serve/router.py routing_key -> replica scores): changing it
    # re-deals the shape->replica placement wholesale, which is the
    # operational lever for breaking a pathological placement (one
    # replica owning every hot bucket) without restarting the fleet.
    # Same salt fleet-wide or routing is not a function.
    fleet_hash_salt: int = _f(0, "arch", 0, 1 << 30)
    # [tunable] Host spill routing for the out-of-core checking tier
    # (store/spill.py): 0 = auto (spill encoded chunks / frontier
    # checkpoints to disk only when the estimated working set exceeds
    # host_rss_budget_mb), 1 = off (everything stays in RAM — the seed
    # behaviour), 2 = force (every checkpoint/chunk goes through the
    # spill tier — the bench/test lane). Verdicts are bit-identical in
    # every mode: the spill tier moves bytes, never meaning.
    host_spill_mode: int = _f(0, "tunable", 0, 2, group="spill")
    # [tunable] Host-RAM working-set budget (MiB) of the out-of-core
    # tier: the bounded in-RAM window of spilled encoded chunks and
    # frontier checkpoints evicts to disk past this budget, and the
    # long-haul bench lane pins its RSS-growth ceiling to it.
    host_rss_budget_mb: int = _f(4096, "tunable", 64, 1 << 20,
                                 group="spill")
    # [tunable] Spilled frontier-checkpoint compression (store/spill.py
    # FrontierCodec): 0 = auto (canon-quotient per-class counts when the
    # frontier is canonical, raw packed rows otherwise), 1 = raw always,
    # 2 = force-canonical (refuse the raw fallback — the codec test
    # lane). Decompression is bit-identical in every mode; a payload
    # that fails its digest reads as absent (recompute), never as data.
    spill_compress_mode: int = _f(0, "tunable", 0, 2, group="spill")
    # [tunable] On-disk size cap (MiB) of the content-addressed encode
    # cache (store/encode_cache.py): past it, store() garbage-collects
    # least-recently-used entries (mtime order) until under the cap.
    # 0 disables collection (the seed's unbounded growth).
    encode_cache_cap_mb: int = _f(2048, "tunable", 0, 1 << 20,
                                  group="spill")


def field_meta() -> dict[str, dict]:
    """Machine-readable tuning metadata per field: {name: {kind, range,
    group, conservative, default}} — the doc lint's and the autotuner's
    single source of truth for tags and search bounds."""
    out = {}
    for f in fields(KernelLimits):
        out[f.name] = dict(f.metadata) | {"default": f.default}
    return out


class LimitsEnvError(ValueError):
    """A JEPSEN_TPU_LIMIT_<FIELD> override that cannot apply: non-integer
    or outside the field's safe range. The message names the env var and
    the accepted range so the operator can fix it without reading code."""


def env_var(name: str) -> str:
    return f"JEPSEN_TPU_LIMIT_{name.upper()}"


def _parse_env() -> dict[str, int]:
    """Validated env overrides. Loud failure (satellite of ISSUE 4): a
    malformed value must name the variable and the accepted range, not
    surface as a bare ValueError from int()."""
    overrides: dict[str, int] = {}
    for f in fields(KernelLimits):
        var = env_var(f.name)
        raw = os.environ.get(var)
        if raw is None:
            continue
        lo, hi = f.metadata["range"]
        try:
            # Plain decimal first (accepts zero-padded "010" like the
            # pre-ISSUE-4 parser did), then prefixed literals (0x…).
            val = int(raw)
        except ValueError:
            try:
                val = int(raw, 0)
            except ValueError:
                raise LimitsEnvError(
                    f"{var}={raw!r} is not an integer (accepted range "
                    f"for {f.name}: {lo}..{hi})") from None
        if not lo <= val <= hi:
            raise LimitsEnvError(
                f"{var}={val} is outside the safe range for {f.name}: "
                f"{lo}..{hi} (doc/perf.md 'KernelLimits reference')")
        overrides[f.name] = val
    return overrides


# -- resolution state -------------------------------------------------------
#
# _ENV     validated env overrides, parsed at import (and on _reload()).
# _SET     the programmatic profile installed by set_limits(), or None.
# _TUNED   the persisted tuned profile's field dict for this platform, or
#          None when not yet loaded (lazy — loading may need a jax
#          backend, see tune/profile.py), or {} when loaded-and-absent.
# _LIMITS  the memoized resolved instance (invalidated on any change).

_ENV: dict[str, int] = _parse_env()
_SET: KernelLimits | None = None
_TUNED: dict[str, int] | None = None
_LIMITS: KernelLimits | None = None


def _tuned_overrides() -> dict[str, int]:
    """The tuned profile's overrides for this platform, loaded lazily on
    the first resolution that CAN determine them. tune/profile.py only
    touches a jax backend when a profile FILE exists (an operator ran
    `jepsen-tpu tune` on this machine), so processes on machines with no
    profile never risk initializing a wedged backend from here. While
    the answer is UNDETERMINED (a profile file exists but jax is not
    imported yet, so the platform key cannot resolve), nothing is cached
    — a limits() call made before backend init must not freeze an empty
    tuned set for the process lifetime (tuned_limits() returns None for
    that case, {} for a definitive no-profile answer)."""
    global _TUNED
    if _TUNED is None:
        try:
            from ..tune import profile as _profile

            tuned = _profile.tuned_limits()
        except Exception:
            # The tuned profile is an optimization, never a failure mode
            # (a torn file / unimportable jax must not break limits()).
            tuned = {}
        if tuned is None:
            return {}            # undetermined: retry on a later call
        _TUNED = dict(tuned)
    return _TUNED


def _resolve() -> KernelLimits:
    base = _SET if _SET is not None else \
        replace(KernelLimits(), **_tuned_overrides())
    return replace(base, **_ENV) if _ENV else base


def limits() -> KernelLimits:
    """The active limits profile, resolved with precedence
    env > set_limits() > tuned profile > dataclass default. The
    resolution is memoized only once the tuned-profile question is
    settled (or a set_limits profile shadows it) — see
    _tuned_overrides."""
    global _LIMITS
    if _LIMITS is not None:
        return _LIMITS
    lim = _resolve()
    if _SET is not None or _TUNED is not None:
        _LIMITS = lim
    return lim


def limits_provenance() -> dict[str, str]:
    """Where each resolved field's value came from: "env" (a
    JEPSEN_TPU_LIMIT_* override), "set" (set_limits() installed a value
    differing from the default), "tuned" (the persisted tuned profile),
    or "default" (the dataclass default). Surfaced by
    tools/print_profile.py, the bench records, and run telemetry."""
    lim = limits()
    out = {}
    for f in fields(KernelLimits):
        if f.name in _ENV:
            out[f.name] = "env"
        elif _SET is not None:
            out[f.name] = ("set" if getattr(lim, f.name) != f.default
                           else "default")
        elif f.name in (_TUNED or {}):
            out[f.name] = "tuned"
        else:
            out[f.name] = "default"
    return out


def set_limits(lim: KernelLimits | None) -> KernelLimits | None:
    """Install a programmatic profile (tests / embedding runtimes);
    returns the PREVIOUS programmatic profile — None when there was none
    — so the save/restore idiom ``prev = set_limits(x); ...;
    set_limits(prev)`` restores the exact prior state (in particular it
    does NOT freeze a resolved snapshot that would mask a tuned profile
    loaded later). Env overrides still win over the installed instance
    (precedence above); the tuned profile does not apply while a
    set_limits profile is active — the caller chose a complete instance.
    ``None`` clears the programmatic profile, re-enabling tuned-profile
    resolution. When an env override SHADOWS a differing installed value
    (e.g. a bench pin under an exported JEPSEN_TPU_LIMIT_*), that is
    logged once per field — a measurement pin being silently ignored is
    exactly the surprise the precedence doc alone doesn't prevent."""
    global _SET, _LIMITS
    prev = _SET
    _SET = lim
    _LIMITS = None
    if lim is not None and _ENV:
        shadowed = [f for f, v in _ENV.items() if getattr(lim, f) != v]
        new = [f for f in shadowed if f not in _WARNED_SHADOWED]
        if new:
            _WARNED_SHADOWED.update(new)
            import logging

            logging.getLogger(__name__).warning(
                "set_limits value(s) shadowed by env overrides "
                "(precedence env > set_limits): %s",
                ", ".join(f"{f} ({env_var(f)}={_ENV[f]})"
                          for f in sorted(new)))
    return prev


_WARNED_SHADOWED: set = set()


def _reload() -> None:
    """Re-parse env and drop the memoized resolution AND the cached tuned
    profile (tests, and tune/profile.py after persisting a new profile).
    Raises LimitsEnvError on malformed env, like import does."""
    global _ENV, _TUNED, _LIMITS
    _ENV = _parse_env()
    _TUNED = None
    _LIMITS = None
