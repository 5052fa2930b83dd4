"""Cross-tenant continuous-batching check scheduler — the serve core.

Every prior layer amortizes compiles and padding *within* one run: the
sched bucket engine within one corpus call, the warm-kernel LRU within
one process, the stream dispatcher within one live run. A CLI
invocation per client therefore re-pays the whole cold path per client.
This module is the same fix modern inference serving applies to LLM
decode: ONE persistent scheduler that coalesces concurrent requests
from *different* tenants into shared bucketed launches, so tenant N's
compile and bucket fill benefit tenant N+1 by construction —
``plan.cache_key()`` (PR 12) makes the sharing safe (a kernel resolved
for one tenant's bucket shape is exactly the kernel any tenant's
same-shape launch needs).

Mechanics:

  * **Coalescing queue** — requests land in per-tenant FIFO queues; the
    dispatch thread wakes on the first arrival, lingers up to
    ``limits().serve_coalesce_ms`` for more requests to coalesce
    (latency <-> batch-fill, the capacity-planning knob), then drains a
    batch of up to ``serve_max_batch`` requests **weighted-fair** across
    tenants (round-robin, ``weights[tenant]`` requests per turn) so a
    flooding tenant cannot starve a light one.
  * **Shared bucketed launches** — the coalesced batch goes through
    ``sched.submit_corpus`` (the async face of the PR 2 bucket engine):
    different tenants' same-bucket histories stack into ONE kernel
    launch, resolved via the KernelPlan dispatch spine against the
    process-wide warm-kernel LRU. Aggregate events/s under K concurrent
    clients approaches the single-client corpus-batch record because
    the daemon *is* the corpus batcher, fed by the network.
  * **Admission control** — at most ``serve_max_inflight`` admitted-but-
    unfinished requests per tenant; past the bound a submission is
    rejected (HTTP 429 upstream) instead of queueing unboundedly.
  * **Supervisor-driven backpressure** (obs/health.py): ``wedged``
    rejects new work outright (HTTP 503 + Retry-After) and parks the
    dispatcher — already-admitted requests drain when the backend
    recovers; ``degraded`` sheds work to the exact CPU oracle path
    (same algorithm, same verdicts, no device dispatch) instead of
    risking the sick backend; any dispatch failure on a healthy backend
    falls back to the oracle for that batch and notes the failure.

Verdicts are bit-identical to ``jepsen-tpu analyze`` on the same
histories: the batched path IS the post-hoc corpus path (test_sched.py
equivalence), and the oracle shed runs the same WGL algorithm on host
(tests/test_serve.py pins both).
"""

from __future__ import annotations

import itertools
import os
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from .. import obs
from ..obs import health
from ..obs.sync import maybe_wrap
from ..ops.limits import limits

# Retry-After seconds a wedged rejection advertises: long enough that a
# well-behaved client backs off past a probe cycle's worth of recovery
# chances, short enough to re-attach promptly after one.
RETRY_AFTER_S = 5

# Retry-After seconds a 429 in-flight-bound rejection advertises: the
# bound clears as soon as one batch drains (tens of ms on a warm
# kernel), so 1s is the floor a well-behaved client — and the fleet
# router's backoff (serve/router.py) — can act on.
RETRY_AFTER_INFLIGHT_S = 1

# Kernel label of the degraded-shed route (results / bench / web).
ORACLE_KERNEL = "cpu-oracle-shed"

# Most tenants whose recent-latency windows are retained (each window
# itself caps at 1024 samples) — like the queue/rotation eviction,
# client-supplied tenant ids must not grow process state unboundedly.
TENANT_LATENCY_TENANTS = 256

# The scenario factory's tenant id (campaign/engine.py submits its
# check waves here under route="serve"): campaign traffic rides the
# same WFQ rotation as everyone else — one turn per rotation like any
# tenant, so a million-scenario campaign cannot starve an interactive
# tenant, which is the whole point of submitting it AS a tenant.
CAMPAIGN_TENANT = "campaign"


# -- rolling-window SLO gauges ----------------------------------------------

def slo_target_s() -> float:
    """The serve SLO latency target (p99 threshold) in seconds."""
    try:
        return float(os.environ.get("JEPSEN_TPU_SERVE_SLO_P99_S", "1.0"))
    except ValueError:
        return 1.0


def slo_budget() -> float:
    """The SLO error budget: the tolerated breach fraction (burn rate
    1.0 means breaches exactly consume the budget)."""
    try:
        return float(os.environ.get("JEPSEN_TPU_SERVE_SLO_BUDGET",
                                    "0.01"))
    except ValueError:
        return 0.01


class RollingWindow:
    """A time-bounded latency window for the serve daemon's live SLO
    gauges: p50/p99 over the last window_s seconds (the cumulative
    request histogram can't forget, so a recovered daemon would wear
    its worst minute forever) plus the burn rate — the breach fraction
    over the error budget."""

    def __init__(self, window_s: float = 60.0, maxlen: int = 4096):
        self.window_s = window_s
        self.maxlen = maxlen
        self._items: list[tuple[float, float]] = []

    def observe(self, value: float, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        self._items.append((now, float(value)))
        self._prune(now)

    def _prune(self, now: float) -> None:
        cut = now - self.window_s
        i = 0
        n = len(self._items)
        while i < n and self._items[i][0] < cut:
            i += 1
        if i or n > self.maxlen:
            self._items = self._items[max(i, n - self.maxlen):]

    def values(self, now: Optional[float] = None) -> list[float]:
        self._prune(time.monotonic() if now is None else now)
        return [v for _, v in self._items]

    def quantiles(self, now: Optional[float] = None) \
            -> tuple[float, float]:
        vals = sorted(self.values(now))
        if not vals:
            return 0.0, 0.0

        def q(p: float) -> float:
            return vals[min(len(vals) - 1, int(p * len(vals)))]

        return q(0.50), q(0.99)

    def burn_rate(self, slo_s: Optional[float] = None,
                  budget: Optional[float] = None,
                  now: Optional[float] = None) -> float:
        vals = self.values(now)
        if not vals:
            return 0.0
        slo = slo_target_s() if slo_s is None else slo_s
        bud = slo_budget() if budget is None else budget
        breach = sum(1 for v in vals if v > slo) / len(vals)
        return round(breach / bud, 4) if bud > 0 else 0.0



class Rejected(Exception):
    """A submission the scheduler refused to admit. ``status`` is the
    HTTP code the daemon maps it to (429 admission bound / 503 wedged);
    ``retry_after_s`` is set for wedged rejections."""

    def __init__(self, reason: str, status: int,
                 retry_after_s: Optional[int] = None):
        super().__init__(reason)
        self.reason = reason
        self.status = status
        self.retry_after_s = retry_after_s


@dataclass
class ServeRequest:
    """One admitted check request riding the coalescing queue."""

    tenant: str
    model_name: str
    enc: Any                                   # EncodedHistory
    ops: Optional[list] = None                 # raw Op history (artifacts)
    webhook: Optional[str] = None
    id: str = field(default_factory=lambda: uuid.uuid4().hex)
    submitted_mono: float = field(default_factory=time.monotonic)
    done: threading.Event = field(default_factory=threading.Event)
    result: Optional[dict] = None
    error: Optional[str] = None

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self.done.wait(timeout)


class CoalescingScheduler:
    """The continuous-batching dispatcher (module docstring). One
    instance per daemon process; ``submit`` is called from any number of
    HTTP handler threads, everything else happens on the dispatch
    thread. Shared state is guarded by ONE condition (``_lock``)."""

    def __init__(self, coalesce_ms: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 max_inflight: Optional[int] = None,
                 weights: Optional[dict[str, int]] = None,
                 artifact_sink: Optional[Callable] = None,
                 webhook_sink: Optional[Callable] = None,
                 batch_telemetry: bool = False):
        self._coalesce_ms = coalesce_ms
        self._max_batch = max_batch
        self._max_inflight = max_inflight
        self._weights = dict(weights or {})
        # Both sinks run on the dispatch thread, after verdicts settle:
        # artifact_sink(requests, batch_tracer) persists store
        # artifacts, webhook_sink(request) delivers the verdict
        # callback.
        self._artifact_sink = artifact_sink
        self._webhook_sink = webhook_sink
        # batch_telemetry: record each batch under a PRIVATE tracer so
        # the per-request store artifacts carry the batch's span
        # record. Deliberately not a nested obs capture: the capture
        # stack is process-global, so nesting would shadow the
        # daemon's registry for every handler thread mid-batch (serve
        # counters and /metrics scrapes landing in a throwaway
        # registry) — kernel attribution and the serve.* series belong
        # on the daemon's own capture.
        self._batch_telemetry = batch_telemetry
        self._lock = maybe_wrap(
            threading.Condition(),
            "serve.scheduler.CoalescingScheduler._lock")
        # jtsan: guarded-by=self._lock
        self._queues: dict[str, deque[ServeRequest]] = {}
        self._rotation: deque[str] = deque()    # WFQ tenant turn order
        # jtsan: guarded-by=self._lock
        self._inflight: dict[str, int] = {}
        self._pending = 0
        self._models: dict[str, Any] = {}       # model name -> Model
        self._batch_ids = itertools.count(1)
        self._stop = threading.Event()
        # Dispatch-thread-only accounting (handler threads read it
        # through stats(), which copies under the lock).
        self._batches = 0
        self._requests_done = 0
        self._coalesced_requests = 0
        self._shed_cpu = 0
        self._fill_sum = 0.0
        self._tenant_latency: dict[str, deque] = {}
        # Rolling-window SLO gauges: p50/p99 over the last minute +
        # burn rate, updated by the dispatch thread only; stats() reads
        # the copied dict.
        self._slo_window = RollingWindow()
        self._slo = {"slo_p50_s": 0.0, "slo_p99_s": 0.0,
                     "slo_burn_rate": 0.0,
                     "slo_target_s": slo_target_s()}
        self._thread = threading.Thread(target=self._run,
                                        name="serve-dispatch", daemon=True)
        self._thread.start()

    # -- knobs (resolved late so env/tuned-profile overrides apply) ------
    def coalesce_s(self) -> float:
        ms = self._coalesce_ms if self._coalesce_ms is not None \
            else limits().serve_coalesce_ms
        return max(0.0, ms / 1000.0)

    def max_batch(self) -> int:
        return self._max_batch if self._max_batch is not None \
            else limits().serve_max_batch

    def max_inflight(self) -> int:
        return self._max_inflight if self._max_inflight is not None \
            else limits().serve_max_inflight

    # -- submit side (HTTP handler threads) ------------------------------
    def submit(self, tenant: str, enc, model_name: str = "cas-register",
               ops: Optional[list] = None,
               webhook: Optional[str] = None) -> ServeRequest:
        """Admit one request (or raise :class:`Rejected`). Returns the
        request handle; await the verdict with ``req.wait()`` /
        ``req.result``."""
        m = obs.get_metrics()
        sup = health.get_supervisor()
        if sup.snapshot()["state"] == health.WEDGED:
            m.counter("serve.rejected_wedged").add(1)
            raise Rejected(
                "backend wedged; shedding new work "
                f"(retry after {RETRY_AFTER_S}s)", 503,
                retry_after_s=RETRY_AFTER_S)
        req = ServeRequest(tenant=str(tenant), model_name=model_name,
                           enc=enc, ops=ops, webhook=webhook)
        with self._lock:
            if self._inflight.get(req.tenant, 0) >= self.max_inflight():
                m.counter("serve.rejected_inflight").add(1)
                raise Rejected(
                    f"tenant {req.tenant!r} at the in-flight bound "
                    f"({self.max_inflight()}); drain verdicts first", 429,
                    retry_after_s=RETRY_AFTER_INFLIGHT_S)
            q = self._queues.get(req.tenant)
            if q is None:
                q = self._queues[req.tenant] = deque()
                self._rotation.append(req.tenant)
            q.append(req)
            self._inflight[req.tenant] = \
                self._inflight.get(req.tenant, 0) + 1
            self._pending += 1
            depth = self._pending
            self._lock.notify_all()
        m.counter("serve.requests").add(1)
        m.gauge("serve.queue_depth").set(depth)
        return req

    def submit_many(self, tenant: str, encs, model_name: str = "cas-register"
                    ) -> list[ServeRequest]:
        """Admit a WAVE of same-tenant requests under one lock
        acquisition (the campaign's check batches: thousands of tiny
        histories, where per-submit lock churn and wakeups would
        dominate). All-or-nothing against the admission bound — a wave
        that would overrun ``serve_max_inflight`` is Rejected whole, so
        the caller chunks by ``max_inflight()`` and drains between
        waves exactly like any well-behaved tenant."""
        m = obs.get_metrics()
        sup = health.get_supervisor()
        if sup.snapshot()["state"] == health.WEDGED:
            m.counter("serve.rejected_wedged").add(len(encs))
            raise Rejected(
                "backend wedged; shedding new work "
                f"(retry after {RETRY_AFTER_S}s)", 503,
                retry_after_s=RETRY_AFTER_S)
        tenant = str(tenant)
        reqs = [ServeRequest(tenant=tenant, model_name=model_name, enc=e)
                for e in encs]
        with self._lock:
            if self._inflight.get(tenant, 0) + len(reqs) \
                    > self.max_inflight():
                m.counter("serve.rejected_inflight").add(len(reqs))
                raise Rejected(
                    f"tenant {tenant!r} wave of {len(reqs)} would "
                    f"overrun the in-flight bound "
                    f"({self.max_inflight()}); chunk and drain", 429,
                    retry_after_s=RETRY_AFTER_INFLIGHT_S)
            q = self._queues.get(tenant)
            if q is None:
                q = self._queues[tenant] = deque()
                self._rotation.append(tenant)
            q.extend(reqs)
            self._inflight[tenant] = \
                self._inflight.get(tenant, 0) + len(reqs)
            self._pending += len(reqs)
            depth = self._pending
            self._lock.notify_all()
        m.counter("serve.requests").add(len(reqs))
        m.gauge("serve.queue_depth").set(depth)
        return reqs

    def model_for(self, name: str):
        """Resolved (and cached) Model instance per model name. The
        dispatch thread and session-opening handler threads race here;
        binding setdefault's RETURN re-validates under the second
        acquisition, so both racers end up using the ONE instance the
        registry actually holds (jtsan JTL503 pinned the unbound form:
        each racer kept its own instance)."""
        with self._lock:
            mdl = self._models.get(name)
        if mdl is None:
            from ..models import get_model

            mdl = get_model(name)
            with self._lock:
                mdl = self._models.setdefault(name, mdl)
        return mdl

    # -- dispatch thread --------------------------------------------------
    def _run(self) -> None:
        while not self._stop.is_set():
            with self._lock:
                while self._pending == 0 and not self._stop.is_set():
                    self._lock.wait(0.5)
            if self._stop.is_set():
                return
            # Wedged park: admitted work is NOT shed — it re-attaches
            # and drains the moment the supervisor sees a success
            # (recovery is immediate in the state machine).
            sup = health.get_supervisor()
            while sup.snapshot()["state"] == health.WEDGED \
                    and not self._stop.is_set():
                self._stop.wait(0.05)
            if self._stop.is_set():
                return
            # Max-linger: wait for more tenants' requests to coalesce
            # into this batch, bounded by serve_coalesce_ms.
            deadline = time.monotonic() + self.coalesce_s()
            with self._lock:
                while self._pending < self.max_batch() \
                        and not self._stop.is_set():
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._lock.wait(remaining)
            # Re-check the park AFTER the linger too: a backend that
            # wedged while we coalesced must not receive the batch —
            # admitted work waits out the park and drains on recovery.
            while sup.snapshot()["state"] == health.WEDGED \
                    and not self._stop.is_set():
                self._stop.wait(0.05)
            if self._stop.is_set():
                return
            # Drain under its own acquisition (a submission racing in
            # between simply rides this batch or the next one).
            batch = self._drain_batch()
            if batch:
                self._dispatch(batch)

    def _drain_batch(self) -> list[ServeRequest]:
        """Weighted-fair drain: round-robin the tenant rotation, each
        turn taking up to ``weights[tenant]`` (default 1) queued
        requests, until the batch cap or the queues run dry. Tenants
        keep their rotation slot across batches, so a backlogged
        tenant's turn comes around exactly as often as an interactive
        one's."""
        cap = self.max_batch()
        batch: list[ServeRequest] = []
        with self._lock:
            turns_without_progress = 0
            while len(batch) < cap and self._pending > 0 \
                    and turns_without_progress < len(self._rotation):
                tenant = self._rotation[0]
                self._rotation.rotate(-1)
                q = self._queues.get(tenant)
                take = max(1, int(self._weights.get(tenant, 1)))
                took = 0
                while q and took < take and len(batch) < cap:
                    batch.append(q.popleft())
                    self._pending -= 1
                    took += 1
                turns_without_progress = 0 if took else \
                    turns_without_progress + 1
        return batch

    def _dispatch(self, batch: list[ServeRequest]) -> None:
        m = obs.get_metrics()
        batch_id = next(self._batch_ids)
        sup = health.get_supervisor()
        state = sup.snapshot()["state"]
        shed = state == health.DEGRADED
        route = "cpu-oracle" if shed else "jax"
        t0 = time.monotonic()
        # Per-batch artifact tracer: a PRIVATE tracer for the store
        # artifact's span record — deliberately NOT a nested capture on
        # the global stack, which would shadow the daemon's registry
        # for every handler thread mid-batch (submit()-side serve.*
        # counters and concurrent /metrics scrapes would land in — or
        # read — the ephemeral batch registry). Kernel attribution and
        # the serve.* series stay on the daemon's own capture.
        batch_tracer = obs.Tracer(enabled=True) \
            if self._batch_telemetry else None
        error: Optional[str] = None
        with obs.get_tracer().span("serve.batch", id=batch_id,
                                   size=len(batch), route=route):
            import contextlib

            span_cm = batch_tracer.span(
                "serve.batch", id=batch_id, size=len(batch),
                route=route) if batch_tracer is not None \
                else contextlib.nullcontext()
            with span_cm:
                try:
                    if shed:
                        results, kernel = self._check_oracle(batch)
                    else:
                        try:
                            results, kernel = self._check_jax(batch)
                        except Exception as e:
                            # A dispatch failure on a not-yet-degraded
                            # backend: tell the supervisor (sched's
                            # drain already did for fetch failures) and
                            # shed THIS batch to the oracle so admitted
                            # work still gets verdicts.
                            sup.note_failure(f"{type(e).__name__}: {e}",
                                             source="serve.dispatch")
                            results, kernel = self._check_oracle(batch)
                            shed = True
                            route = "cpu-oracle"
                except Exception as e:
                    # Even the oracle failed (or the shed path itself
                    # crashed): the dispatch thread must SURVIVE — mark
                    # every request errored, release its admission
                    # slot, and wake the waiter. A dead dispatch
                    # thread would leave the daemon accepting work
                    # that never gets verdicts.
                    import logging

                    error = f"{type(e).__name__}: {e}"
                    logging.getLogger(__name__).exception(
                        "serve batch %s failed on every route", batch_id)
                    route = "error"
                    kernel = "none"
                    results = [{"valid": None, "op_count":
                                int(req.enc.n_ops), "dead_step": -1,
                                "kernel": "none", "error": error}
                               for req in batch]
        wall = time.monotonic() - t0
        fill = len(batch) / self.max_batch()
        now = time.monotonic()
        for req, res in zip(batch, results):
            latency = now - req.submitted_mono
            if error is not None:
                req.error = error
            req.result = {
                **res,
                "request_id": req.id,
                "tenant": req.tenant,
                "model": req.model_name,
                "route": route,
                "kernel": res.get("kernel", kernel),
                "batch": {"id": batch_id, "size": len(batch),
                          "fill": round(fill, 4),
                          "coalesced": len(batch) > 1,
                          "wall_s": round(wall, 4)},
                "latency_s": round(latency, 4),
            }
            m.histogram("serve.request_latency_s").observe(latency)
            self._slo_window.observe(latency, now=now)
            # Under the lock: tenant_latencies()/stats() iterate this
            # dict from handler threads — an unlocked setdefault here
            # could resize it mid-iteration (jtsan JTL501 finding).
            with self._lock:
                lat = self._tenant_latency.setdefault(
                    req.tenant, deque(maxlen=1024))
                lat.append(latency)
        m.counter("serve.batches").add(1)
        if len(batch) > 1:
            m.counter("serve.coalesced_requests").add(len(batch))
        if shed:
            m.counter("serve.shed_cpu").add(len(batch))
        m.gauge("serve.batch_fill").set(fill)
        # The live SLO cells (/live): rolling-window
        # quantiles, not the cumulative histogram — a recovered daemon
        # must not wear its worst minute forever.
        p50, p99 = self._slo_window.quantiles(now=now)
        burn = self._slo_window.burn_rate(now=now)
        m.gauge("serve.slo_p50_s").set(round(p50, 6))
        m.gauge("serve.slo_p99_s").set(round(p99, 6))
        m.gauge("serve.slo_burn_rate").set(burn)
        with self._lock:
            self._batches += 1
            self._slo.update(slo_p50_s=round(p50, 6),
                             slo_p99_s=round(p99, 6),
                             slo_burn_rate=burn)
            self._requests_done += len(batch)
            self._fill_sum += fill
            if len(batch) > 1:
                self._coalesced_requests += len(batch)
            if shed:
                self._shed_cpu += len(batch)
            for req in batch:
                self._inflight[req.tenant] = \
                    max(0, self._inflight.get(req.tenant, 1) - 1)
                # Tenant-state eviction: client-supplied tenant ids
                # must not grow process state without bound — a tenant
                # with nothing queued and nothing in flight gives its
                # queue/rotation slot back (re-created on its next
                # submit; the latency window below is capped too).
                if not self._inflight.get(req.tenant) \
                        and not self._queues.get(req.tenant):
                    self._queues.pop(req.tenant, None)
                    self._inflight.pop(req.tenant, None)
                    try:
                        self._rotation.remove(req.tenant)
                    except ValueError:
                        pass
            while len(self._tenant_latency) > TENANT_LATENCY_TENANTS:
                self._tenant_latency.pop(
                    next(iter(self._tenant_latency)))
            m.gauge("serve.queue_depth").set(self._pending)
        # Waiters wake (and webhooks fire) BEFORE the store writes:
        # artifact I/O is batch-wide and must not ride every request's
        # latency — it only delays the dispatch thread's next coalesce
        # cycle, which the linger window absorbs.
        for req in batch:
            req.done.set()
            if req.webhook and self._webhook_sink is not None:
                # Counted before the sink starts its delivery thread, so
                # the count never trails a delivery the callee has seen.
                m.counter("serve.webhooks").add(1)
                self._webhook_sink(req)
        if self._artifact_sink is not None:
            try:
                self._artifact_sink(batch, batch_tracer)
            except Exception:
                import logging

                logging.getLogger(__name__).exception(
                    "serve artifact sink failed (verdicts unaffected)")

    def _check_jax(self, batch: list[ServeRequest]
                   ) -> tuple[list[dict], str]:
        """The shared-launch path: one sched corpus submission per model
        group (different tenants' histories stack into the same bucket
        launches), awaited through the async submit face."""
        from .. import sched

        results: list[Optional[dict]] = [None] * len(batch)
        kernels: set[str] = set()
        by_model: dict[str, list[int]] = {}
        for i, req in enumerate(batch):
            by_model.setdefault(req.model_name, []).append(i)
        for name in sorted(by_model):
            idxs = by_model[name]
            model = self.model_for(name)
            outs, kernel, _stats = sched.submit_corpus(
                [batch[i].enc for i in idxs], model).result()
            kernels.add(kernel)
            for i, one in zip(idxs, outs):
                results[i] = {
                    "valid": one.get("valid"),
                    "op_count": int(batch[i].enc.n_ops),
                    "dead_step": int(one.get("dead_step", -1)),
                    "kernel": one.get("kernel", kernel),
                }
        kernel = kernels.pop() if len(kernels) == 1 else "mixed"
        # check_corpus's alignment contract: one result per input, in
        # order. A dropped slot here would zip tenant A's verdict onto
        # tenant B's request — fail loudly instead (the caller's
        # dispatch-failure handler sheds the batch to the oracle).
        missing = [i for i, r in enumerate(results) if r is None]
        if missing:
            raise RuntimeError(
                f"corpus check returned no result for batch slots "
                f"{missing} — misaligned results would cross tenants")
        return results, kernel

    def _check_oracle(self, batch: list[ServeRequest]
                      ) -> tuple[list[dict], str]:
        """The degraded shed: the exact pure-Python WGL oracle — same
        algorithm, same verdicts, zero device dispatch (a sick backend
        is never touched by admitted work)."""
        from ..checkers.linearizable import _event_to_step
        from ..checkers.oracle import check_events_oracle

        results = []
        for req in batch:
            model = self.model_for(req.model_name)
            if req.enc.n_events == 0:
                results.append({"valid": True, "op_count": 0,
                                "dead_step": -1, "kernel": ORACLE_KERNEL})
                continue
            out = check_events_oracle(req.enc, model).to_dict()
            results.append({
                "valid": out["valid"],
                "op_count": int(req.enc.n_ops),
                "dead_step": _event_to_step(req.enc,
                                            out.pop("dead_event")),
                "kernel": ORACLE_KERNEL,
            })
        return results, ORACLE_KERNEL

    # -- introspection / lifecycle ----------------------------------------
    def stats(self) -> dict:
        """The /serve/stats + bench view (copied under the lock)."""
        from .. import sched

        with self._lock:
            per_tenant = {
                t: {"inflight": self._inflight.get(t, 0),
                    "queued": len(self._queues.get(t) or ()),
                    "served": len(self._tenant_latency.get(t) or ()),
                    "latency_p50_s": quantile(
                        self._tenant_latency.get(t), 0.50),
                    "latency_p99_s": quantile(
                        self._tenant_latency.get(t), 0.99)}
                for t in sorted(self._queues)}
            out = {
                "pending": self._pending,
                "batches": self._batches,
                "requests_done": self._requests_done,
                "coalesced_requests": self._coalesced_requests,
                "shed_cpu": self._shed_cpu,
                "batch_fill_avg": round(
                    self._fill_sum / self._batches, 4)
                if self._batches else 0.0,
                "coalesce_ms": int(self.coalesce_s() * 1000),
                "max_batch": self.max_batch(),
                "max_inflight": self.max_inflight(),
                "slo": dict(self._slo),
                "tenants": per_tenant,
            }
        out["kernel_cache"] = sched.kernel_cache().stats()
        out["health"] = health.get_supervisor().snapshot()["state"]
        return out

    def tenant_latencies(self) -> dict[str, list[float]]:
        """Per-tenant recent request latencies (bounded), for the
        /metrics tenant-labeled exposition lines."""
        with self._lock:
            return {t: list(d) for t, d in self._tenant_latency.items()}

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every admitted request has a verdict (bench's
        between-arm barrier). True when drained inside the timeout."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if self._pending == 0 \
                        and not any(self._inflight.values()):
                    return True
            time.sleep(0.005)
        return False

    def close(self) -> None:
        """Stop the dispatch thread (pending requests keep their queue
        state; a daemon shutdown follows with the process)."""
        self._stop.set()
        with self._lock:
            self._lock.notify_all()
        self._thread.join(timeout=5.0)


def quantile(values, q: float) -> float:
    """Empirical quantile over a bounded latency window — the ONE copy
    /serve/stats, the /metrics tenant summaries, and the bench lane
    share (drifting duplicates would make the same window report
    different quantiles per surface)."""
    if not values:
        return 0.0
    xs = sorted(values)
    i = min(len(xs) - 1, int(q * len(xs)))
    return round(xs[i], 6)
