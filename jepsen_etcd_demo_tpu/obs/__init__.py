"""obs — harness-wide telemetry: span tracing + metrics + per-run artifacts.

The harness used to be a black box: the only observability was post-hoc
charts over the op history, so a wedged backend had nothing to say
about where time went.
This package is the in-band answer — stdlib-only, near-zero-cost when
idle:

  * obs/trace.py   — span tracer (context-manager API, monotonic-ns,
                     thread/async-safe) -> `telemetry.jsonl`
  * obs/metrics.py — counters/gauges/histograms -> `metrics.json`
  * this module    — the capture stack wiring instrumentation points to
                     the active run, kernel compile/execute attribution,
                     the host phases of a check (`phase`), garbage-
                     collection and compile counters, and the env-gated
                     jax.profiler trace.

Usage pattern: layers call `get_tracer()` / `get_metrics()` at the
point of instrumentation; both return no-op singletons unless a
`capture()` is active, so library use (imports, ad-hoc checker calls)
records nothing and pays one list-index per call. The runner
(runner/core.py run_test) and the benchmark (benchmark/run.py) open
captures; the runner's capture writes `telemetry.jsonl` +
`metrics.json` into the run dir next to history.jsonl/results.json.

Env vars:
  JEPSEN_TPU_TELEMETRY=0   disable capture entirely (spans/metrics
                           become no-ops; no artifacts are written)
  JEPSEN_TPU_JAX_TRACE=1   additionally capture a jax.profiler trace of
                           the check phase into <run_dir>/jax_trace/
                           (view with tensorboard/xprof); the host
                           phases show on its host plane

Live export (obs/export.py): `obs.subscribe()` streams span/event/
metric records as they are appended (the web layer's /live SSE feed),
`obs.render_prometheus(...)` renders a registry snapshot as Prometheus
text for /metrics. Backend health (obs/health.py):
`health.get_supervisor()` is the process-wide healthy/degraded/wedged
state machine behind /healthz and the bench record.

Well-known metric keys (pre-registered at zero by capture(), so they
are never absent from metrics.json or the bench's kernel_phases):
  wgl.compile_s      summed first-call wall of each compiled kernel
                     geometry (jit tracing+compilation is synchronous on
                     the first call, so this is compile-dominated)
  wgl.execute_s      summed steady-state kernel call wall (dispatch +
                     any in-call fetch; a lower bound on device time for
                     async backends)
  encode.encode_s    host-side history->tensor encoding seconds
  wgl.frontier_peak  gauge; max over checks of the search's live-config
                     high-water mark (kernel_phases reports its max)
  <phase>_s          host seconds of each phase in PHASES, counted only
                     while it is the outermost open phase (disjoint)
  gc.collect_s       seconds of garbage collections that started inside
                     a phase (inside a check)
  compile.*          jax trace / lower / backend-compile seconds and
                     persistent-cache misses (COMPILE_COUNTERS)
  check.split.*      records routed by key and records copied as
                     unwrapped Ops (SPLIT_COUNTERS)
  sched.shard_*      batch-sharded launches and their largest shard's
                     real steps x the shard count (SHARD_COUNTERS)
"""

from __future__ import annotations

import collections
import contextvars
import gc
import os
import sys
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Callable, Iterator, Optional

from .metrics import MetricsRegistry, read_metrics
from .trace import Tracer, read_jsonl
from . import export                               # noqa: E402
from . import health                               # noqa: E402
from .export import render_prometheus, subscribe   # noqa: F401

TELEMETRY_FILE = "telemetry.jsonl"
METRICS_FILE = "metrics.json"

# The bench/e2e contract keys: pre-registered at zero on every capture.
# The jtflow "metrics preregistered" hooks below declare the
# pre-registration set to the flow pass (JTL405): a key the snapshot
# readers (kernel_phases / sched_stats / sweep_stats) fetch but no
# capture pre-registers would be ABSENT — not zero — on quiet runs,
# breaking the "zeros permitted, never absent" artifact contract.
# jtflow: metrics preregistered
PHASE_COUNTERS = ("wgl.compile_s", "wgl.execute_s", "encode.encode_s")
# jtflow: metrics preregistered
PHASE_GAUGE = "wgl.frontier_peak"
# Corpus-scheduler accounting (sched/): padded-vs-real step counters
# behind the bench's padding_waste field and the kernel-LRU hit/miss
# counters behind cache_hit_rate — pre-registered so the artifacts carry
# zeros, never absences, even for runs that never launch a batch.
# jtflow: metrics preregistered
SCHED_COUNTERS = ("sched.steps_real", "sched.steps_padded",
                  "sched.launches",
                  # Histories the launch pack expanded in one lockstep
                  # pass, and histories it had to reslot first
                  # (ops/encode.py stack_return_steps).
                  "sched.pack.batched_histories", "sched.pack.reslotted",
                  "sched.cache_hits", "sched.cache_misses",
                  "encode.cache_hits", "encode.cache_misses")
# Sparse active-tile sweep engine (ops/wgl3_sparse.py) accounting:
# per-mode step counters plus the live-tile occupancy gauge — pre-
# registered so every dense-kernel run's metrics.json carries them
# (zeros permitted, never absent; the web UI renders both).
# jtflow: metrics preregistered
SWEEP_COUNTERS = ("wgl.sweep_steps_sparse", "wgl.sweep_steps_dense",
                  "wgl.sweep_checks_sparse", "wgl.sweep_checks_dense",
                  "wgl.sweep_checks_mixed",
                  # ISSUE 10: configs removed by frontier
                  # canonicalization (ops/canon.py) and the previously-
                  # silent work-list-overflow dense rounds
                  # (ops/wgl3_sparse.py).
                  "wgl.configs_pruned", "wgl.sparse_overflow_rounds")
# jtflow: metrics preregistered
SWEEP_GAUGE = "wgl.live_tile_ratio"
# Frontier-dedup effectiveness: pruned / pre-canon configs over the
# canon-applied steps of a check (ops/canon.py; zeros-never-absent like
# every sweep key).
# jtflow: metrics preregistered
DEDUP_GAUGE = "wgl.frontier_dedup_ratio"
# Elle transitive-closure engine (ops/cycles.py / ops/cycles_tiled.py /
# stream/elle.py, ISSUE 11): per-route graph counts (dense squaring /
# vmapped batch / tiled work-list / host-oracle fallback), launch and
# tiled-round accounting, and the streaming session's txn/re-check
# counters — pre-registered so every capture's metrics.json carries
# them (zeros permitted, never absent; elle_stats() is the bench/web
# reader).
# jtflow: metrics preregistered
ELLE_COUNTERS = ("elle.graphs_dense", "elle.graphs_batched",
                 "elle.graphs_tiled", "elle.graphs_oracle",
                 "elle.closure_launches", "elle.tiled_rounds_sparse",
                 "elle.tiled_rounds_dense", "elle.stream_txns",
                 "elle.stream_rechecks")
# Batched-launch fill ratio (real graphs / padded batch) and the tiled
# kernel's last eligible-product density — the elle engine's occupancy
# telemetry.
# jtflow: metrics preregistered
ELLE_GAUGES = ("elle.batch_fill", "elle.tile_density")
# Streaming check engine (stream/engine.py): fraction of return steps
# swept while the run was still live, and the watermark's lag behind
# the recorder (history entries recorded but not yet stable) — pre-
# registered so every run's metrics.json carries them (zeros permitted,
# never absent; a post-hoc run simply records zeros).
# jtflow: metrics preregistered
STREAM_GAUGES = ("stream.overlap_ratio", "stream.watermark_lag")
# Checking-as-a-service daemon (serve/, ISSUE 13): request/batch/
# admission accounting of the continuous-batching scheduler — requests
# admitted, coalesced batch launches, requests that shared a batch with
# another request, work shed to the CPU oracle path while degraded,
# rejections (admission bound / wedged backend), webhook deliveries —
# pre-registered so every capture's metrics.json carries them (zeros
# permitted, never absent; serve_stats() is the bench/web reader).
# jtflow: metrics preregistered
SERVE_COUNTERS = ("serve.requests", "serve.batches",
                  "serve.coalesced_requests", "serve.shed_cpu",
                  "serve.rejected_inflight", "serve.rejected_wedged",
                  "serve.webhooks")
# Queue depth at dispatch time and the coalesced batch's fill (requests
# per batch over serve_max_batch) — the serve daemon's occupancy
# telemetry on /metrics and the /live page.
# jtflow: metrics preregistered
SERVE_GAUGES = ("serve.queue_depth", "serve.batch_fill")
# End-to-end request latency (submit -> verdict, seconds) across every
# tenant; the exporter renders p50/p95/p99 quantile lines.
# jtflow: metrics preregistered
SERVE_HISTOGRAM = "serve.request_latency_s"
# Tracer truncation: a truncated telemetry.jsonl is visible in metrics
# too, not only the footer.
# jtflow: metrics preregistered
TRACE_COUNTERS = ("trace.dropped_records",)
# The host phases of a check, each opened once per block of a call,
# never per key or per op (`phase`). PHASE_SECONDS[i] is PHASES[i]'s
# counter: seconds while that phase is the outermost open one, so the
# counters are disjoint and their sum never exceeds the wall.
PHASES = ("check.split", "check.encode", "sched.pack", "sched.dispatch",
          "sched.fetch_wait", "sched.unpack", "check.witness",
          "check.release")
# jtflow: metrics preregistered
PHASE_SECONDS = ("check.split_s", "check.encode_s", "sched.pack_s",
                 "sched.dispatch_s", "sched.fetch_wait_s",
                 "sched.unpack_s", "check.witness_s", "check.release_s")
_PHASE_INDEX = {name: i for i, name in enumerate(PHASES)}
# The per-key split (checkers/independent.py): records routed to a key,
# and records copied as unwrapped Ops (split_by_key, or a key that
# reaches a single-history check). Their ratio is the copy share.
# jtflow: metrics preregistered
SPLIT_COUNTERS = ("check.split.records_routed",
                  "check.split.records_copied")
# The batch-sharded launches of the corpus scheduler (sched/engine.py):
# for each, the shard count x the largest shard's real steps (every shard
# waits for that one), and the launches themselves. Over
# sched.steps_real the first is the shard waste: 1.0 when every shard
# sweeps equal real work.
# jtflow: metrics preregistered
SHARD_COUNTERS = ("sched.shard_steps_max", "sched.shard_launches")
# Garbage-collection seconds (a gc.callbacks hook while a capture is
# open) of collections that start inside a phase. They overlap the
# phases: never part of their sum.
# jtflow: metrics preregistered
GC_COUNTER = "gc.collect_s"
# jax's compile events (one jax.monitoring listener per process):
# tracing, lowering to MLIR, and the backend compile — which includes
# loads from the persistent compilation cache — plus the persistent
# cache's misses (jax records one per entry it writes).
# jtflow: metrics preregistered
COMPILE_COUNTERS = ("compile.trace_s", "compile.lower_s",
                    "compile.backend_s", "compile.cache_misses")
_COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": 0,
                   "/jax/core/compile/jaxpr_to_mlir_module_duration": 1,
                   "/jax/core/compile/backend_compile_duration": 2}
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
# Backend health supervisor (obs/health.py): 0 healthy / 1 degraded /
# 2 wedged, set on every transition.
# jtflow: metrics preregistered
HEALTH_GAUGE = "health.state"
# Runtime lock-order sanitizer (obs/sync.py, JEPSEN_TPU_SYNC_TRACE=1):
# wrapped-lock acquisitions and distinct witnessed order edges, folded
# in by sync.publish_metrics() — zeros (sanitizer off) permitted, never
# absent.
# jtflow: metrics preregistered
SYNC_COUNTERS = ("sync.lock_acquisitions", "sync.order_edges")
# Scenario factory (campaign/, ISSUE 15): executed specs, fail-fast
# aborted live runs, per-key checks, falsifying runs, ddmin shrinker
# candidate checks + batched launches, banked minimal witnesses, and
# the regression-corpus replay accounting — pre-registered so every
# capture's metrics.json carries them (zeros permitted, never absent;
# campaign_stats() is the bench/web reader).
# jtflow: metrics preregistered
CAMPAIGN_COUNTERS = ("campaign.specs", "campaign.aborted_runs",
                     "campaign.keys_checked",
                     "campaign.keys_skipped_hard",
                     "campaign.runs_falsified",
                     "campaign.shrink_checks", "campaign.shrink_launches",
                     "campaign.banked", "campaign.replayed",
                     "campaign.replay_failures")
# Occupancy/effectiveness gauges: distinct anomaly signatures the last
# triage pass produced, the last shrink's minimal/original op ratio,
# and end-to-end scenario throughput.
# jtflow: metrics preregistered
CAMPAIGN_GAUGES = ("campaign.unique_signatures", "campaign.shrink_ratio",
                   "campaign.specs_per_sec")
# Serve SLO gauges (serve/scheduler.py RollingWindow): rolling-window
# p50/p99 request latency and the burn rate (breach fraction over the
# error budget) — the /live SLO cells.
# jtflow: metrics preregistered
SLO_GAUGES = ("serve.slo_p50_s", "serve.slo_p99_s",
              "serve.slo_burn_rate")
# Fleet router (serve/router.py + serve/fleet.py, ISSUE 18): requests
# admitted by the shape-affine router, spillover re-routes past an
# unavailable replica, upstream forward failures, no-replica-available
# rejections, and completed zero-downtime restarts — pre-registered so
# every capture's metrics.json carries them (zeros permitted, never
# absent; fleet_stats() is the bench/web reader).
# jtflow: metrics preregistered
FLEET_COUNTERS = ("fleet.requests", "fleet.spillover",
                  "fleet.replica_errors", "fleet.rejected",
                  "fleet.restarts")
# Fleet occupancy: replicas registered with the router and how many of
# them are currently routable (ready + not degraded/wedged/down).
# jtflow: metrics preregistered
FLEET_GAUGES = ("fleet.replicas", "fleet.replicas_ready")
# Out-of-core spill tier (store/spill.py + store/encode_cache.py GC,
# ISSUE 20): disk-tier transfer counts and bytes in each direction,
# in-RAM window evictions, encode-cache LRU collections — pre-
# registered so every capture's metrics.json carries them (zeros
# permitted, never absent; longhaul_stats() is the bench/web reader).
# jtflow: metrics preregistered
SPILL_COUNTERS = ("spill.writes", "spill.reads",
                  "spill.bytes_written", "spill.bytes_read",
                  "spill.evictions", "encode.cache_evictions")
# Spill-tier occupancy: last measured checkpoint compression ratio
# (raw packed bytes / stored bytes; >1 means the canon-quotient codec
# beat raw) and the long-haul lane's peak RSS growth in MiB.
# jtflow: metrics preregistered
SPILL_GAUGES = ("spill.compress_ratio", "spill.peak_rss_mb")

_NULL_TRACER = Tracer(enabled=False)
_NULL_METRICS = MetricsRegistry(enabled=False)


class Capture:
    """One active telemetry scope: a tracer + registry pair, optionally
    bound to an output directory the artifacts land in on exit."""

    def __init__(self, out_dir: Optional[str | Path] = None,
                 enabled: bool = True):
        self.enabled = enabled
        self.out_dir = Path(out_dir) if out_dir is not None else None
        self.tracer = Tracer(enabled=enabled)
        self.metrics = MetricsRegistry(enabled=enabled)
        # Seconds of each collection the gc hook saw, not yet added to
        # gc.collect_s: the hook may not take the registry lock (a
        # collection can start inside any allocation, including one
        # made while this thread holds it), so fold_gc moves them.
        self.gc_pending: collections.deque = collections.deque()
        if enabled:
            for name in PHASE_COUNTERS + SCHED_COUNTERS + SWEEP_COUNTERS \
                    + TRACE_COUNTERS + ELLE_COUNTERS + SERVE_COUNTERS \
                    + SYNC_COUNTERS + CAMPAIGN_COUNTERS + FLEET_COUNTERS \
                    + SPILL_COUNTERS + PHASE_SECONDS + COMPILE_COUNTERS \
                    + SPLIT_COUNTERS + SHARD_COUNTERS:
                self.metrics.counter(name)
            self.metrics.counter(GC_COUNTER)
            for name in ELLE_GAUGES + SERVE_GAUGES + CAMPAIGN_GAUGES \
                    + SLO_GAUGES + FLEET_GAUGES \
                    + SPILL_GAUGES:
                self.metrics.gauge(name)
            self.metrics.histogram(SERVE_HISTOGRAM)
            self.metrics.gauge(PHASE_GAUGE)
            self.metrics.gauge(SWEEP_GAUGE)
            self.metrics.gauge(DEDUP_GAUGE)
            self.metrics.gauge(HEALTH_GAUGE)
            for name in STREAM_GAUGES:
                self.metrics.gauge(name)
            # Live-export wiring (obs/export.py): appended trace records
            # stream to bus subscribers in exact append order, and a
            # dropped record increments trace.dropped_records the moment
            # it happens (the tracer's meta/footer carry the final
            # count; the metric makes truncation visible live).
            self.tracer.listener = export.bus_publish
            self.tracer.drop_counter = \
                self.metrics.counter("trace.dropped_records")

    def fold_gc(self) -> None:
        """Add the gc hook's pending seconds to gc.collect_s."""
        s = 0.0
        try:
            while True:
                s += self.gc_pending.popleft()
        except IndexError:
            pass
        if s:
            self.metrics.counter(GC_COUNTER).add(s)

    def write(self) -> None:
        if not self.enabled or self.out_dir is None:
            return
        try:
            self.out_dir.mkdir(parents=True, exist_ok=True)
            self.tracer.write(self.out_dir / TELEMETRY_FILE)
            self.metrics.write(self.out_dir / METRICS_FILE)
        except OSError:
            # Telemetry is an observability aid, never a failure mode:
            # a read-only or vanished store dir must not fail the run.
            pass


_lock = threading.Lock()
_stack: list[Capture] = []


def telemetry_enabled() -> bool:
    return os.environ.get("JEPSEN_TPU_TELEMETRY", "1").lower() \
        not in ("0", "false", "no", "off")


# jtsan: returns=Tracer
def get_tracer() -> Tracer:
    """The active capture's tracer, or a no-op singleton."""
    stack = _stack
    return stack[-1].tracer if stack else _NULL_TRACER


# jtsan: returns=MetricsRegistry
def get_metrics() -> MetricsRegistry:
    """The active capture's metrics registry, or a no-op singleton."""
    stack = _stack
    return stack[-1].metrics if stack else _NULL_METRICS


def capture_active() -> bool:
    """True while some capture is installed (a run is in flight) — the
    /healthz `run_in_flight` field."""
    return bool(_stack)


@contextmanager
def capture(out_dir: Optional[str | Path] = None) -> Iterator[Capture]:
    """Install a fresh tracer+registry as the active telemetry sinks;
    on exit, restore the previous ones and (when `out_dir` is given)
    write telemetry.jsonl + metrics.json there. Nesting shadows: the
    innermost capture receives the records (one capture per run)."""
    cap = Capture(out_dir, enabled=telemetry_enabled())
    if not cap.enabled:
        yield cap
        return
    if "jax" in sys.modules:
        _jax()
    with _lock:
        _stack.append(cap)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)
    try:
        yield cap
    finally:
        with _lock:
            if cap in _stack:
                _stack.remove(cap)
            if not _stack and _on_gc in gc.callbacks:
                gc.callbacks.remove(_on_gc)
        cap.fold_gc()
        cap.write()


# -- host phases, garbage collection, compiles -----------------------------

_JAX = None             # the jax module, once a capture has needed it
_phase_open: contextvars.ContextVar[bool] = contextvars.ContextVar(
    "jepsen_tpu_phase", default=False)


def _jax():
    """jax, imported at the first capture that needs it, with the
    process's one compile listener registered then."""
    global _JAX
    if _JAX is None:
        import jax

        jax.monitoring.register_event_duration_secs_listener(
            _on_compile_duration)
        jax.monitoring.register_event_listener(_on_compile_event)
        _JAX = jax
    return _JAX


def _on_compile_duration(event: str, duration: float, **_) -> None:
    i = _COMPILE_EVENTS.get(event)
    stack = _stack
    if i is not None and stack:
        stack[-1].metrics.counter(COMPILE_COUNTERS[i]).add(duration)


def _on_compile_event(event: str, **_) -> None:
    stack = _stack
    if event == _CACHE_MISS_EVENT and stack:
        stack[-1].metrics.counter("compile.cache_misses").add(1)


_gc_t0: Optional[float] = None   # start of the collection being counted
_gc_note = None         # the open gc.collect annotation, if any


def _on_gc(stage: str, info: dict) -> None:
    """gc.callbacks hook, installed while a capture is open. A
    collection that starts inside a phase — inside a check, not in the
    caller's own work between checks — adds its seconds to gc.collect_s
    (Capture.gc_pending, folded in at the end of that outermost phase)
    and, for generations 1 and 2, opens a `gc.collect` annotation on
    the profiler's host plane (generation 0 collects far too often to
    annotate)."""
    global _gc_t0, _gc_note
    if stage == "start":
        if not _phase_open.get():
            _gc_t0 = None
            return
        _gc_t0 = time.perf_counter()
        if _JAX is not None and info.get("generation", 0) >= 1:
            _gc_note = _JAX.profiler.TraceAnnotation("gc.collect")
            _gc_note.__enter__()
        return
    if _gc_t0 is None:
        return
    dt = time.perf_counter() - _gc_t0
    if _gc_note is not None:
        _gc_note.__exit__(None, None, None)
        _gc_note = None
    stack = _stack
    if stack:
        stack[-1].gc_pending.append(dt)


@contextmanager
def phase(name: str) -> Iterator[None]:
    """One host phase of a check (`name` from PHASES), opened once per
    block of a call. Under a capture: a telemetry.jsonl span, a
    jax.profiler annotation on the host plane (the device trace's
    clock), and — when no other phase is open in this context — its
    seconds into the phase's counter, so phase counters never overlap.
    With no capture open it records nothing and imports nothing."""
    i = _PHASE_INDEX.get(name)
    if i is None:
        raise ValueError(f"unknown phase {name!r}; phases: {PHASES}")
    stack = _stack
    if not stack:
        yield
        return
    cap = stack[-1]
    outer = not _phase_open.get()
    token = _phase_open.set(True)
    t0 = time.perf_counter()
    try:
        with cap.tracer.span(name), _jax().profiler.TraceAnnotation(name):
            yield
    finally:
        if outer:
            cap.metrics.counter(PHASE_SECONDS[i]).add(
                time.perf_counter() - t0)
            if cap.gc_pending:
                cap.fold_gc()
        _phase_open.reset(token)


# -- kernel phase attribution ----------------------------------------------

def _lower_ahead(fn: Callable, args, kwargs) -> None:
    """Lower a jitted kernel before its first call; the call then reuses
    this lowering instead of lowering on its own path. Kept for set-up
    time: on a TPU v5e, under one measuring script, the warm-up call of
    a 1024-key independent check took 23.0-24.5 s without it and
    16.6-17.3 s with it (the call's own lowering of each of its six
    pallas launchers took 1.2-2.5 s, this one 0.8-1.0 s; why is not
    known). Nothing runs, so donated operands stay alive. A callable
    that cannot be lowered this way (not jitted, traced arguments) is
    left to its call, which raises what is wrong."""
    lower = getattr(fn, "lower", None)
    if lower is None:
        return
    try:
        lower(*args, **kwargs)
    except Exception:
        pass


def instrument_kernel(name: str, fn: Callable) -> Callable:
    """Wrap a jit-compiled kernel callable for compile/execute
    attribution. The FIRST call of a jitted function runs tracing + XLA
    compilation synchronously before dispatch, so its wall time is
    compile-dominated; later calls are steady-state dispatch. The
    wrapper's first-call flag lives with the wrapped fn in the kernel
    caches (ops/wgl2.py / wgl3.py / wgl3_pallas.py _CACHE), so the
    granularity is exactly one flag per compiled geometry — and a
    capture opened after the geometry warmed correctly records only
    execute time (the compile happened outside the run).

    Steady-state times are dispatch wall, NOT device time: kernels
    dispatch asynchronously and callers rely on that (the chunked
    sweeps pipeline windows), so the wrapper never blocks on results.
    Device-true timings are the env-gated jax.profiler trace's job.

    Under a capture, the first call lowers the jitted callable ahead of
    the call, outside the timed region (`_lower_ahead`)."""
    state = {"first": True}

    def wrapped(*args, **kwargs):
        first = state["first"]
        m = get_metrics()
        if first and m.enabled:
            _lower_ahead(fn, args, kwargs)
        t0 = time.monotonic()
        out = fn(*args, **kwargs)
        dt = time.monotonic() - t0
        if first:
            state["first"] = False
            m.counter("wgl.compile_s").add(dt)
            m.counter("wgl.compile_calls").add(1)
            # jtlint: disable=JTL107 -- bounded family: kernel names are
            # the fixed static set of instrument_kernel call sites.
            m.histogram(f"wgl.compile_s.{name}").observe(dt)
            get_tracer().event("wgl.compile", kernel=name,
                               seconds=round(dt, 6))
        else:
            m.counter("wgl.execute_s").add(dt)
            m.counter("wgl.execute_calls").add(1)
            # jtlint: disable=JTL107 -- bounded family: kernel names are
            # the fixed static set of instrument_kernel call sites.
            m.histogram(f"wgl.execute_s.{name}").observe(dt)
        return out

    wrapped.__name__ = f"instrumented_{name}"
    return wrapped


def record_check_result(res: dict) -> None:
    """Fold one WGL check result's search metrics into the registry:
    frontier occupancy high-water mark and configs explored (the §5.1
    unit of search work)."""
    m = get_metrics()
    try:
        mf = float(res.get("max_frontier"))
    except (TypeError, ValueError):
        mf = -1.0
    if mf >= 0:
        m.gauge(PHASE_GAUGE).set(mf)
    try:
        cfgs = float(res.get("configs_explored"))
    except (TypeError, ValueError):
        cfgs = 0.0
    if cfgs > 0:
        m.counter("wgl.configs_explored").add(cfgs)
    # Sparse-sweep telemetry (ops/wgl3_sparse.py): live-tile occupancy
    # of the converged tables and which sweep mode the steps ran under.
    # Batched launches report the occupancy column but always sweep
    # dense; the long sweeps report exact per-mode step counts.
    try:
        ratio = float(res.get("live_tile_ratio"))
    except (TypeError, ValueError):
        ratio = -1.0
    if ratio >= 0:
        m.gauge(SWEEP_GAUGE).set(ratio)
    sweep = res.get("sweep")
    if isinstance(sweep, dict):
        mode = sweep.get("mode")
        if mode in ("sparse", "dense", "mixed"):
            # jtlint: disable=JTL107 -- bounded family: mode is checked
            # against the closed {sparse, dense, mixed} set on the line
            # above; all three names are pre-registered by capture().
            m.counter(f"wgl.sweep_checks_{mode}").add(1)
        for key in ("steps_sparse", "steps_dense"):
            try:
                v = int(sweep.get(key, 0))
            except (TypeError, ValueError):
                v = 0
            if v > 0:
                # jtlint: disable=JTL107 -- bounded family: key iterates
                # the closed two-element tuple above; both names are
                # pre-registered by capture().
                m.counter(f"wgl.sweep_{key}").add(v)
        try:
            ovf = int(sweep.get("overflow_rounds", 0))
        except (TypeError, ValueError):
            ovf = 0
        if ovf > 0:
            # The previously-silent sparse fallback (ISSUE 10): rounds
            # where work-list overflow forced a dense sweep.
            m.counter("wgl.sparse_overflow_rounds").add(ovf)
    elif ratio >= 0:
        # A dense batched launch: no sweep record, but the measured
        # occupancy proves it ran the dense kernels.
        m.counter("wgl.sweep_checks_dense").add(1)
    # Frontier canonicalization accounting (ops/canon.py): configs
    # removed by the symmetry-reduction pass and its effectiveness
    # ratio over the canon-applied steps.
    dedup = res.get("dedup")
    if isinstance(dedup, dict):
        try:
            pruned = int(dedup.get("configs_pruned", 0))
        except (TypeError, ValueError):
            pruned = 0
        if pruned > 0:
            m.counter("wgl.configs_pruned").add(pruned)
        try:
            dr = float(dedup.get("frontier_dedup_ratio"))
        except (TypeError, ValueError):
            dr = -1.0
        if dr >= 0:
            m.gauge(DEDUP_GAUGE).set(dr)


def active_profile_hash() -> str:
    """The active tuning profile's short hash (tune/profile.py), or
    "default". Never initializes a jax backend (the profile key resolves
    only when jax is already imported) and never raises — safe to stamp
    on the bench's degraded/unreachable-backend records."""
    try:
        from ..tune import profile

        return profile.profile_hash()
    except Exception:
        return "default"


def kernel_phases(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The bench's kernel-phase breakdown, from a registry snapshot.
    With no registry (backend unreachable, telemetry disabled) every
    timing field is zero — the contract is "zeros permitted, never
    absent". `profile_hash` identifies the tuning profile the process
    resolved (ISSUE 4: every bench record names its profile, the
    degraded path included — "default" when none applies)."""
    out = {"compile_s": 0.0, "execute_s": 0.0, "encode_s": 0.0,
           "frontier_peak": 0, "profile_hash": active_profile_hash()}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> float:
        rec = snap.get(key)
        return round(rec["value"], 4) if rec \
            and rec.get("type") == "counter" else 0.0

    out["compile_s"] = counter_value("wgl.compile_s")
    out["execute_s"] = counter_value("wgl.execute_s")
    out["encode_s"] = counter_value("encode.encode_s")
    fp = snap.get(PHASE_GAUGE)
    if fp and fp.get("max") is not None:
        out["frontier_peak"] = int(fp["max"])
    return out


def sched_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The corpus scheduler's bench contract fields, from a registry
    snapshot: padding_waste (padded/real steps over every scheduled
    launch in the capture) and cache_hit_rate (kernel-LRU hits over
    lookups). Zeros when no registry / no launches — like
    kernel_phases, the contract is "zeros permitted, never absent"."""
    out = {"padding_waste": 0.0, "cache_hit_rate": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> float:
        rec = snap.get(key)
        return rec["value"] if rec \
            and rec.get("type") == "counter" else 0.0

    real = counter_value("sched.steps_real")
    padded = counter_value("sched.steps_padded")
    if real:
        out["padding_waste"] = round(padded / real, 4)
    hits = counter_value("sched.cache_hits")
    lookups = hits + counter_value("sched.cache_misses")
    if lookups:
        out["cache_hit_rate"] = round(hits / lookups, 4)
    return out


def sweep_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The sparse-sweep engine's bench/web contract fields, from a
    registry snapshot: the live-tile-ratio gauge (last/min/max) and the
    per-mode step/check counters. Zeros when no registry / no dense runs
    — the contract is "zeros permitted, never absent"."""
    out = {"live_tile_ratio": 0.0, "steps_sparse": 0, "steps_dense": 0,
           "checks_sparse": 0, "checks_dense": 0, "checks_mixed": 0,
           "configs_pruned": 0, "sparse_overflow_rounds": 0,
           "frontier_dedup_ratio": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> int:
        rec = snap.get(key)
        return int(rec["value"]) if rec \
            and rec.get("type") == "counter" else 0

    out["steps_sparse"] = counter_value("wgl.sweep_steps_sparse")
    out["steps_dense"] = counter_value("wgl.sweep_steps_dense")
    out["checks_sparse"] = counter_value("wgl.sweep_checks_sparse")
    out["checks_dense"] = counter_value("wgl.sweep_checks_dense")
    out["checks_mixed"] = counter_value("wgl.sweep_checks_mixed")
    out["configs_pruned"] = counter_value("wgl.configs_pruned")
    out["sparse_overflow_rounds"] = \
        counter_value("wgl.sparse_overflow_rounds")
    g = snap.get(SWEEP_GAUGE)
    if g and g.get("last") is not None:
        out["live_tile_ratio"] = round(float(g["last"]), 4)
    g = snap.get(DEDUP_GAUGE)
    if g and g.get("last") is not None:
        out["frontier_dedup_ratio"] = round(float(g["last"]), 4)
    return out


def elle_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The elle closure engine's bench/web contract fields, from a
    registry snapshot: per-route graph counts, launch/round accounting,
    the streamed-session counters, and the occupancy gauges. Zeros when
    no registry / no elle checks — like every reader here, the contract
    is "zeros permitted, never absent"."""
    out = {"graphs_dense": 0, "graphs_batched": 0, "graphs_tiled": 0,
           "graphs_oracle": 0, "closure_launches": 0,
           "tiled_rounds_sparse": 0, "tiled_rounds_dense": 0,
           "stream_txns": 0, "stream_rechecks": 0,
           "batch_fill": 0.0, "tile_density": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> int:
        rec = snap.get(key)
        return int(rec["value"]) if rec \
            and rec.get("type") == "counter" else 0

    out["graphs_dense"] = counter_value("elle.graphs_dense")
    out["graphs_batched"] = counter_value("elle.graphs_batched")
    out["graphs_tiled"] = counter_value("elle.graphs_tiled")
    out["graphs_oracle"] = counter_value("elle.graphs_oracle")
    out["closure_launches"] = counter_value("elle.closure_launches")
    out["tiled_rounds_sparse"] = counter_value("elle.tiled_rounds_sparse")
    out["tiled_rounds_dense"] = counter_value("elle.tiled_rounds_dense")
    out["stream_txns"] = counter_value("elle.stream_txns")
    out["stream_rechecks"] = counter_value("elle.stream_rechecks")
    g = snap.get("elle.batch_fill")
    if g and g.get("last") is not None:
        out["batch_fill"] = round(float(g["last"]), 4)
    g = snap.get("elle.tile_density")
    if g and g.get("last") is not None:
        out["tile_density"] = round(float(g["last"]), 4)
    return out


def serve_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The serve daemon's bench/web contract fields (serve/, ISSUE 13),
    from a registry snapshot: request/batch/admission counters, the
    queue-depth/batch-fill occupancy gauges, and the request-latency
    quantiles. Zeros when no registry / no served requests — like every
    reader here, the contract is "zeros permitted, never absent"."""
    out = {"requests": 0, "batches": 0, "coalesced_requests": 0,
           "shed_cpu": 0, "rejected_inflight": 0, "rejected_wedged": 0,
           "webhooks": 0, "queue_depth": 0, "batch_fill": 0.0,
           "latency_p50_s": 0.0, "latency_p99_s": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> int:
        rec = snap.get(key)
        return int(rec["value"]) if rec \
            and rec.get("type") == "counter" else 0

    out["requests"] = counter_value("serve.requests")
    out["batches"] = counter_value("serve.batches")
    out["coalesced_requests"] = counter_value("serve.coalesced_requests")
    out["shed_cpu"] = counter_value("serve.shed_cpu")
    out["rejected_inflight"] = counter_value("serve.rejected_inflight")
    out["rejected_wedged"] = counter_value("serve.rejected_wedged")
    out["webhooks"] = counter_value("serve.webhooks")
    g = snap.get("serve.queue_depth")
    if g and g.get("last") is not None:
        out["queue_depth"] = int(g["last"])
    g = snap.get("serve.batch_fill")
    if g and g.get("last") is not None:
        out["batch_fill"] = round(float(g["last"]), 4)
    h = snap.get("serve.request_latency_s")
    if h and h.get("p50") is not None:
        out["latency_p50_s"] = round(float(h["p50"]), 6)
        out["latency_p99_s"] = round(float(h.get("p99") or 0.0), 6)
    return out


def fleet_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The fleet router's bench/web contract fields (serve/router.py,
    ISSUE 18), from a registry snapshot: routed/spillover/error/reject
    counters, completed zero-downtime restarts, and the replica
    occupancy gauges. Zeros when no registry / no router — like every
    reader here, the contract is "zeros permitted, never absent"."""
    out = {"requests": 0, "spillover": 0, "replica_errors": 0,
           "rejected": 0, "restarts": 0, "replicas": 0,
           "replicas_ready": 0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()
    for key, name in (("requests", "fleet.requests"),
                      ("spillover", "fleet.spillover"),
                      ("replica_errors", "fleet.replica_errors"),
                      ("rejected", "fleet.rejected"),
                      ("restarts", "fleet.restarts")):
        rec = snap.get(name)
        if rec and rec.get("type") == "counter":
            out[key] = int(rec["value"])
    for key, name in (("replicas", "fleet.replicas"),
                      ("replicas_ready", "fleet.replicas_ready")):
        g = snap.get(name)
        if g and g.get("last") is not None:
            out[key] = int(g["last"])
    return out


def longhaul_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The out-of-core spill tier's bench/web contract fields
    (store/spill.py + the encode-cache GC, ISSUE 20), from a registry
    snapshot: disk-tier transfer counts/bytes both directions, window
    and cache evictions, the last measured checkpoint compression
    ratio, and the long-haul lane's peak RSS growth. Zeros when no
    registry / nothing spilled — like every reader here, the contract
    is "zeros permitted, never absent"."""
    out = {"spill_writes": 0, "spill_reads": 0,
           "spill_bytes_written": 0, "spill_bytes_read": 0,
           "spill_evictions": 0, "cache_evictions": 0,
           "compress_ratio": 0.0, "peak_rss_mb": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()
    for key, name in (("spill_writes", "spill.writes"),
                      ("spill_reads", "spill.reads"),
                      ("spill_bytes_written", "spill.bytes_written"),
                      ("spill_bytes_read", "spill.bytes_read"),
                      ("spill_evictions", "spill.evictions"),
                      ("cache_evictions", "encode.cache_evictions")):
        rec = snap.get(name)
        if rec and rec.get("type") == "counter":
            out[key] = int(rec["value"])
    for key, name in (("compress_ratio", "spill.compress_ratio"),
                      ("peak_rss_mb", "spill.peak_rss_mb")):
        g = snap.get(name)
        if g and g.get("last") is not None:
            out[key] = round(float(g["last"]), 6)
    return out


def campaign_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The scenario factory's bench/web contract fields (campaign/,
    ISSUE 15), from a registry snapshot: spec/abort/check/falsification
    counters, shrinker accounting, bank and replay counters, and the
    signature/ratio/throughput gauges. Zeros when no registry / no
    campaign ran — like every reader here, the contract is "zeros
    permitted, never absent"."""
    out = {"specs": 0, "aborted_runs": 0, "keys_checked": 0,
           "keys_skipped_hard": 0, "runs_falsified": 0,
           "shrink_checks": 0, "shrink_launches": 0,
           "banked": 0, "replayed": 0, "replay_failures": 0,
           "unique_signatures": 0, "shrink_ratio": 0.0,
           "specs_per_sec": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> int:
        rec = snap.get(key)
        return int(rec["value"]) if rec \
            and rec.get("type") == "counter" else 0

    out["specs"] = counter_value("campaign.specs")
    out["aborted_runs"] = counter_value("campaign.aborted_runs")
    out["keys_checked"] = counter_value("campaign.keys_checked")
    out["keys_skipped_hard"] = \
        counter_value("campaign.keys_skipped_hard")
    out["runs_falsified"] = counter_value("campaign.runs_falsified")
    out["shrink_checks"] = counter_value("campaign.shrink_checks")
    out["shrink_launches"] = counter_value("campaign.shrink_launches")
    out["banked"] = counter_value("campaign.banked")
    out["replayed"] = counter_value("campaign.replayed")
    out["replay_failures"] = counter_value("campaign.replay_failures")
    g = snap.get("campaign.unique_signatures")
    if g and g.get("last") is not None:
        out["unique_signatures"] = int(g["last"])
    g = snap.get("campaign.shrink_ratio")
    if g and g.get("last") is not None:
        out["shrink_ratio"] = round(float(g["last"]), 4)
    g = snap.get("campaign.specs_per_sec")
    if g and g.get("last") is not None:
        out["specs_per_sec"] = round(float(g["last"]), 2)
    return out


# -- env-gated jax.profiler capture ----------------------------------------

def jax_trace_enabled() -> bool:
    return os.environ.get("JEPSEN_TPU_JAX_TRACE", "").lower() \
        in ("1", "true", "yes", "on")


@contextmanager
def maybe_jax_trace(out_dir: Optional[str | Path]) -> Iterator[None]:
    """jax.profiler.trace into <out_dir>/jax_trace when the env gate
    (JEPSEN_TPU_JAX_TRACE=1) is set and a run dir exists; a plain no-op
    otherwise — including when jax itself is unimportable or the
    profiler refuses (profiling is never a failure mode)."""
    if out_dir is None or not jax_trace_enabled():
        yield
        return
    ctx = None
    try:
        import jax

        ctx = jax.profiler.trace(str(Path(out_dir) / "jax_trace"))
        ctx.__enter__()
    except Exception:
        ctx = None
    try:
        yield
    finally:
        if ctx is not None:
            try:
                ctx.__exit__(None, None, None)
            except Exception:
                pass
