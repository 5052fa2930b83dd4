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
                     and the env-gated jax.profiler trace.

Usage pattern: layers call `get_tracer()` / `get_metrics()` at the
point of instrumentation; both return no-op singletons unless a
`capture()` is active, so library use (imports, ad-hoc checker calls)
records nothing and pays one list-index per call. The runner
(runner/core.py run_test) and the bench (bench.py) open captures; the
runner's capture writes `telemetry.jsonl` + `metrics.json` into the run
dir next to history.jsonl/results.json.

Env vars:
  JEPSEN_TPU_TELEMETRY=0   disable capture entirely (spans/metrics
                           become no-ops; no artifacts are written)
  JEPSEN_TPU_JAX_TRACE=1   additionally capture a jax.profiler trace of
                           the check phase into <run_dir>/jax_trace/
                           (view with tensorboard/xprof)
  JEPSEN_TPU_KERNEL_COST=0 disable the per-kernel XLA cost_analysis /
                           device-memory capture on first calls
                           (kernel_phases flops/bytes stay zero)

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
"""

from __future__ import annotations

import os
import threading
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Callable, Iterator, Optional

from .metrics import MetricsRegistry, read_metrics
from .trace import Tracer, read_jsonl
from . import export                               # noqa: E402
from . import health                               # noqa: E402
from . import ledger                               # noqa: E402
from .export import render_prometheus, subscribe   # noqa: F401
from .ledger import Ledger                         # noqa: F401

TELEMETRY_FILE = "telemetry.jsonl"
METRICS_FILE = "metrics.json"
KERNEL_COST_ENV = "JEPSEN_TPU_KERNEL_COST"

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
# Deep kernel attribution (ISSUE 8): XLA cost_analysis totals captured
# by instrument_kernel at lower time, plus the device-memory high-water
# mark — behind kernel_phases' flops / bytes / device_mem_peak fields.
# Tracer truncation (trace.dropped_records) rides along so a truncated
# telemetry.jsonl is visible in metrics too, not only the footer.
# jtflow: metrics preregistered
COST_COUNTERS = ("wgl.flops", "wgl.bytes_accessed",
                 "trace.dropped_records")
# jtflow: metrics preregistered
COST_GAUGE = "wgl.device_mem_peak"
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
# Scaling ledger (obs/ledger.py, ISSUE 16): launch-level time
# attribution folded live into the capture's registry — launches,
# per-bucket seconds (encode / H2D / compile / useful execute / bucket
# padding / straggler wait / host dispatch gap) and H2D bytes — behind
# obs.ledger_stats(), the bench record's `ledger` object and the
# /metrics jepsen_tpu_ledger_* families. Pre-registered so the
# artifacts carry zeros, never absences, even for runs that never
# launch (the degraded bench paths included).
# jtflow: metrics preregistered
LEDGER_COUNTERS = ("ledger.launches", "ledger.encode_s", "ledger.h2d_s",
                   "ledger.h2d_bytes", "ledger.compile_s",
                   "ledger.execute_s", "ledger.padding_s",
                   "ledger.straggler_s", "ledger.dispatch_gap_s",
                   "ledger.spill_read_s", "ledger.spill_write_s")
# Last-launch occupancy: real/padded step fill and real/padded batch
# fill of the most recent decomposed launch.
# jtflow: metrics preregistered
LEDGER_GAUGES = ("ledger.step_fill", "ledger.batch_fill")
# Serve SLO gauges (obs/ledger.py RollingWindow): rolling-window
# p50/p99 request latency and the burn rate (breach fraction over the
# error budget) — the /live SLO cells and ledger_stats' slo_* fields.
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
_NULL_LEDGER = Ledger(enabled=False)


class Capture:
    """One active telemetry scope: a tracer + registry pair, optionally
    bound to an output directory the artifacts land in on exit."""

    def __init__(self, out_dir: Optional[str | Path] = None,
                 enabled: bool = True, with_ledger: bool = True):
        self.enabled = enabled
        self.out_dir = Path(out_dir) if out_dir is not None else None
        self.tracer = Tracer(enabled=enabled)
        self.metrics = MetricsRegistry(enabled=enabled)
        # The scaling ledger (obs/ledger.py): in-memory always; file-
        # backed (ledger-<proc>.jsonl next to telemetry.jsonl, via a
        # writer thread joined on write()) when the capture has a run
        # dir. `with_ledger=False` is the bench's overhead-control arm.
        self.ledger = Ledger(out_dir=self.out_dir, metrics=self.metrics,
                             enabled=enabled and with_ledger)
        if enabled:
            for name in PHASE_COUNTERS + SCHED_COUNTERS + SWEEP_COUNTERS \
                    + COST_COUNTERS + ELLE_COUNTERS + SERVE_COUNTERS \
                    + SYNC_COUNTERS + CAMPAIGN_COUNTERS \
                    + LEDGER_COUNTERS + FLEET_COUNTERS \
                    + SPILL_COUNTERS:
                self.metrics.counter(name)
            for name in ELLE_GAUGES + SERVE_GAUGES + CAMPAIGN_GAUGES \
                    + LEDGER_GAUGES + SLO_GAUGES + FLEET_GAUGES \
                    + SPILL_GAUGES:
                self.metrics.gauge(name)
            self.metrics.histogram(SERVE_HISTOGRAM)
            self.metrics.gauge(PHASE_GAUGE)
            self.metrics.gauge(SWEEP_GAUGE)
            self.metrics.gauge(DEDUP_GAUGE)
            self.metrics.gauge(COST_GAUGE)
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

    def write(self) -> None:
        # Join the ledger writer thread first (idempotent) so
        # ledger-<proc>.jsonl is complete before anyone merges it.
        self.ledger.close()
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


# jtsan: returns=Ledger
def get_ledger() -> Ledger:
    """The active capture's scaling ledger, or a no-op singleton."""
    stack = _stack
    return stack[-1].ledger if stack else _NULL_LEDGER


def capture_active() -> bool:
    """True while some capture is installed (a run is in flight) — the
    /healthz `run_in_flight` field."""
    return bool(_stack)


@contextmanager
def capture(out_dir: Optional[str | Path] = None, *,
            with_ledger: bool = True) -> Iterator[Capture]:
    """Install a fresh tracer+registry as the active telemetry sinks;
    on exit, restore the previous ones and (when `out_dir` is given)
    write telemetry.jsonl + metrics.json + ledger-<proc>.jsonl there.
    Nesting shadows: the innermost capture receives the records (one
    capture per run). `with_ledger=False` disables only the scaling
    ledger — the bench's ledger-overhead control arm."""
    cap = Capture(out_dir, enabled=telemetry_enabled(),
                  with_ledger=with_ledger)
    if not cap.enabled:
        yield cap
        return
    with _lock:
        _stack.append(cap)
    try:
        yield cap
    finally:
        with _lock:
            if cap in _stack:
                _stack.remove(cap)
        cap.write()


# -- kernel phase attribution ----------------------------------------------

def kernel_cost_enabled() -> bool:
    return os.environ.get(KERNEL_COST_ENV, "1").lower() \
        not in ("0", "false", "no", "off")


def _capture_kernel_cost(name: str, fn: Callable, args, kwargs,
                         m: MetricsRegistry) -> None:
    """Deep attribution for one about-to-compile kernel geometry: lower
    the jitted callable (tracing only — no XLA compile, no execution,
    donation-safe because nothing runs) and fold its
    ``cost_analysis()`` flops / bytes-accessed estimates into the
    registry, then note the backend's device-memory high-water mark.
    Pure observability: ANY failure (a non-jit callable, a backend
    without cost analysis, a CPU without memory_stats) is swallowed and
    the pre-registered zeros stand."""
    try:
        lowered = fn.lower(*args, **kwargs)
        ca = lowered.cost_analysis() or {}
        flops = float(ca.get("flops", 0.0) or 0.0)
        nbytes = float(ca.get("bytes accessed", 0.0) or 0.0)
        if flops > 0:
            m.counter("wgl.flops").add(flops)
            # jtlint: disable=JTL107 -- bounded family: kernel names are
            # the fixed static set of instrument_kernel call sites; the
            # exporter folds them into one labeled Prometheus family.
            m.gauge(f"wgl.kernel_flops.{name}").set(flops)
        if nbytes > 0:
            m.counter("wgl.bytes_accessed").add(nbytes)
            # jtlint: disable=JTL107 -- bounded family: kernel names are
            # a fixed static set (same argument as wgl.kernel_flops).
            m.gauge(f"wgl.kernel_bytes.{name}").set(nbytes)
    except Exception:
        pass
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        peak = float(stats.get("peak_bytes_in_use",
                               stats.get("bytes_in_use", 0)) or 0)
        if peak > 0:
            m.gauge(COST_GAUGE).set(peak)
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
    Device-true timings are the env-gated jax.profiler trace's job."""
    state = {"first": True}

    def wrapped(*args, **kwargs):
        first = state["first"]
        m = get_metrics()
        if first and m.enabled and kernel_cost_enabled():
            # Deep attribution BEFORE the call (donated operands are
            # still alive): XLA cost_analysis flops/bytes + device
            # memory peak, outside the timed region so compile_s keeps
            # meaning "the first call's wall".
            _capture_kernel_cost(name, fn, args, kwargs, m)
        t0_ns = time.monotonic_ns()
        out = fn(*args, **kwargs)
        t1_ns = time.monotonic_ns()
        dt = (t1_ns - t0_ns) / 1e9
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
        # Scaling ledger (obs/ledger.py): the launch record, enriched
        # by whatever launch_context the call site opened (plan
        # identity, bucket shape, padding, shard layout).
        get_ledger().record_launch(name,
                                   "compile" if first else "execute",
                                   t0_ns, t1_ns)
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
    degraded path included — "default" when none applies). ISSUE 8
    grew the deep-attribution fields: `flops` / `bytes` (summed XLA
    cost_analysis estimates over every kernel geometry compiled under
    the capture) and `device_mem_peak` (the backend allocator's
    peak-bytes-in-use high-water mark) — zeros on backends that report
    neither, never absent."""
    out = {"compile_s": 0.0, "execute_s": 0.0, "encode_s": 0.0,
           "frontier_peak": 0, "flops": 0.0, "bytes": 0.0,
           "device_mem_peak": 0, "profile_hash": active_profile_hash()}
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
    out["flops"] = counter_value("wgl.flops")
    out["bytes"] = counter_value("wgl.bytes_accessed")
    fp = snap.get(PHASE_GAUGE)
    if fp and fp.get("max") is not None:
        out["frontier_peak"] = int(fp["max"])
    mem = snap.get(COST_GAUGE)
    if mem and mem.get("max") is not None:
        out["device_mem_peak"] = int(mem["max"])
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


def ledger_stats(metrics: Optional[MetricsRegistry] = None) -> dict:
    """The scaling ledger's bench/web contract fields (obs/ledger.py,
    ISSUE 16), from a registry snapshot: launch count, the per-bucket
    second totals (useful execute vs padding/straggler waste, encode,
    H2D, compile, host dispatch gap), H2D bytes, the last launch's
    fill gauges, and the serve daemon's rolling-window SLO gauges.
    Zeros when no registry / no launches — like every reader here, the
    contract is "zeros permitted, never absent"."""
    out = {"launches": 0, "encode_s": 0.0, "h2d_s": 0.0, "h2d_bytes": 0,
           "compile_s": 0.0, "execute_s": 0.0, "padding_s": 0.0,
           "straggler_s": 0.0, "dispatch_gap_s": 0.0,
           "spill_read_s": 0.0, "spill_write_s": 0.0,
           "step_fill": 0.0, "batch_fill": 0.0,
           "slo_p50_s": 0.0, "slo_p99_s": 0.0, "slo_burn_rate": 0.0}
    if metrics is None or not metrics.enabled:
        return out
    snap = metrics.snapshot()

    def counter_value(key: str) -> float:
        rec = snap.get(key)
        return round(rec["value"], 6) if rec \
            and rec.get("type") == "counter" else 0.0

    out["launches"] = int(counter_value("ledger.launches"))
    out["encode_s"] = counter_value("ledger.encode_s")
    out["h2d_s"] = counter_value("ledger.h2d_s")
    out["h2d_bytes"] = int(counter_value("ledger.h2d_bytes"))
    out["compile_s"] = counter_value("ledger.compile_s")
    out["execute_s"] = counter_value("ledger.execute_s")
    out["padding_s"] = counter_value("ledger.padding_s")
    out["straggler_s"] = counter_value("ledger.straggler_s")
    out["dispatch_gap_s"] = counter_value("ledger.dispatch_gap_s")
    out["spill_read_s"] = counter_value("ledger.spill_read_s")
    out["spill_write_s"] = counter_value("ledger.spill_write_s")
    for key, name in (("step_fill", "ledger.step_fill"),
                      ("batch_fill", "ledger.batch_fill"),
                      ("slo_p50_s", "serve.slo_p50_s"),
                      ("slo_p99_s", "serve.slo_p99_s"),
                      ("slo_burn_rate", "serve.slo_burn_rate")):
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
