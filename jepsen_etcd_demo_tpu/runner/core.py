"""run_test: setup → interpret generators → teardown → check → store.

Mirrors jepsen.core/run!'s structure (reference call stack SURVEY.md §3.1):
  1. OS + DB setup per node, concurrently (reference src/jepsen/etcdemo.clj:161,34-55)
  2. Worker tasks — `concurrency` clients + 1 nemesis — pull ops from the
     generator, invoke them, and record invoke/completion pairs
  3. DB teardown per node (:57-60), log collection (db/LogFiles, :62-64)
  4. checker.check over the recorded history (:115-119,165-167)
  5. persist everything under store/<name>/<ts>/ (§1 L1)

Worker/process model (jepsen semantics the checker depends on): each worker
thread runs one logical *process*. A process that completes an op :info is
considered crashed — it never invokes again; the worker reincarnates as
process + concurrency with a freshly opened client. This is what makes
:info ops "open forever" in the history.
"""

from __future__ import annotations

import asyncio
import logging
import random
import time
from typing import Any, Optional

from .. import obs
from ..clients.base import Client
from ..generators.core import (Gen, GenContext, Pending, Phases, NEMESIS,
                               SECOND)
from ..nemesis.base import Nemesis
from ..ops.op import Op, INVOKE
from ..control.runner import runner_for
from .history import HistoryRecorder

log = logging.getLogger(__name__)


class _RunState:
    """Shared state of the interpreter loop (single event loop — no locks)."""

    def __init__(self, recorder: HistoryRecorder, rng: random.Random):
        self.recorder = recorder
        self.rng = rng
        self.in_flight = 0
        # jtlint: disable=JTL202 -- lifetime argument: _RunState is
        # constructed inside interpret_generators (already on the run's
        # loop) and dies with the run; it can never see a second
        # asyncio.run. ADVICE r5's bug was a primitive CACHED across
        # runs (db/etcd.py), which this is not.
        self.wake = asyncio.Condition()

    async def notify(self):
        async with self.wake:
            self.wake.notify_all()


async def _worker(test: dict, gen: Gen, state: _RunState,
                  worker_id: int, concurrency: int,
                  client_proto: Optional[Client], nemesis: Optional[Nemesis]):
    """One worker: repeatedly ask the generator, invoke, record."""
    is_nemesis = worker_id < 0
    process: Any = NEMESIS if is_nemesis else worker_id
    client: Optional[Client] = None
    nodes = test["nodes"]

    async def ensure_client():
        nonlocal client
        if not is_nemesis and client is None:
            node = nodes[int(process) % len(nodes)]
            client = await client_proto.open(test, node)

    try:
        while True:
            ctx = GenContext(state.recorder.now(), process, state.rng, test)
            out = gen.next_for(ctx)
            if out is None:
                # Exhausted for us. A pending phase barrier may still open a
                # new phase; Phases returns Pending in that window, so a plain
                # None is final.
                return
            if isinstance(out, Pending):
                await _wait(state, out.wake, ctx.time)
                _maybe_open_barrier(gen, state)
                continue
            op: Op = out
            if op.type == "log":
                log.info("%s", op.value)
                continue
            op.process = process
            state.in_flight += 1
            state.recorder.append(op)
            metrics = obs.get_metrics()
            t_op = time.monotonic()
            try:
                if is_nemesis:
                    # A span per fault op (rare, long): the nemesis's own
                    # per-node fault events correlate to it by span id.
                    with obs.get_tracer().span(
                            f"nemesis.{op.f}",
                            nemesis=type(nemesis).__name__):
                        completion = await nemesis.invoke(test, op)
                else:
                    await ensure_client()
                    completion = await client.invoke(test, op)
            except Exception as e:  # client bug or unexpected edge: crash op
                log.exception("invoke crashed for %s", op)
                completion = Op(type="info", f=op.f, value=op.value,
                                process=process, error=f"crash: {e}")
            finally:
                state.in_flight -= 1
            completion.process = process
            state.recorder.append(completion)
            if not is_nemesis:
                # Counters + latency histogram, not per-op spans: client
                # ops are the hot path (rate * concurrency per second).
                # jtlint: disable=JTL107 -- bounded family: completion
                # .type is the closed jepsen op-type set {ok, fail,
                # info} (ops/op.py), three names total.
                metrics.counter(f"runner.ops_{completion.type}").add(1)
                metrics.histogram("runner.op_latency_s").observe(
                    time.monotonic() - t_op)
            if not is_nemesis and completion.type == "info":
                # Process crashed (indeterminate op): reincarnate.
                if client is not None:
                    try:
                        await client.close(test)
                    except Exception:
                        pass
                    client = None
                obs.get_tracer().event(
                    "worker.reincarnate", worker=worker_id,
                    dead_process=int(process),
                    new_process=int(process) + concurrency, f=op.f)
                metrics.counter("runner.reincarnations").add(1)
                process = int(process) + concurrency
            _maybe_open_barrier(gen, state)
            await state.notify()
    finally:
        if client is not None:
            try:
                await client.close(test)
            except Exception:
                pass


async def _wait(state: _RunState, wake: Optional[int], now: int):
    """Sleep until `wake` (relative ns) or until some completion/barrier
    changes the world."""
    if wake is not None:
        delay = max(0.0, (wake - now) / SECOND)
        await asyncio.sleep(min(delay, 0.5) if delay else 0.001)
        return
    async with state.wake:
        try:
            await asyncio.wait_for(state.wake.wait(), timeout=0.2)
        except asyncio.TimeoutError:
            pass


def _maybe_open_barrier(gen: Gen, state: _RunState):
    """Phase barrier: flip to the next phase once nothing is in flight
    (jepsen: all workers must finish phase N before N+1 starts)."""
    if isinstance(gen, Phases) and gen.barrier_pending() \
            and state.in_flight == 0:
        gen.barrier_done()


async def interpret_generators(test: dict, recorder: HistoryRecorder,
                               stop_check=None) -> list[Op]:
    """Run the generator interpreter loop to exhaustion; returns history.

    `stop_check` (--fail-fast, runner check-mode stream): a zero-arg
    callable polled every 50 ms; when it turns true the worker tasks are
    cancelled and the history recorded so far is returned — the
    streamed checker has already falsified the run, so finishing the
    generators would only burn wall clock on a known-invalid test."""
    concurrency = int(test.get("concurrency", 10))
    # Publish the RESOLVED value: thread-identity consumers (generators
    # mapping reincarnated process p + concurrency back to its worker
    # thread, e.g. EachThread/ConcurrentGenerator) must never re-apply
    # their own default.
    test["concurrency"] = concurrency
    rng = random.Random(test.get("seed", 0))
    state = _RunState(recorder, rng)
    gen = test["generator"]
    client_proto = test.get("client")
    nemesis = test.get("nemesis")

    tasks = [asyncio.create_task(
        _worker(test, gen, state, i, concurrency, client_proto, nemesis))
        for i in range(concurrency)]
    if nemesis is not None:
        tasks.append(asyncio.create_task(
            _worker(test, gen, state, -1, concurrency, None, nemesis)))
    if stop_check is None:
        await asyncio.gather(*tasks)
        return recorder.history

    stopped = False

    async def watch():
        nonlocal stopped
        while True:
            await asyncio.sleep(0.05)
            if stop_check():
                stopped = True
                break
            # A worker that crashed outright (not cancelled) must tear
            # the rest down NOW: gather(return_exceptions=True) below
            # would otherwise sit on the exception until every other
            # worker exhausts the generator — the full --time-limit the
            # post-mode gather() surfaces immediately. `stopped` stays
            # False, so the raise-after-gather path re-raises it.
            if any(t.done() and not t.cancelled()
                   and t.exception() is not None for t in tasks):
                break
        # Keep cancelling until every worker is actually done: a lone
        # cancel() can be silently swallowed when it races the worker's
        # own wait_for timeout in _wait (bpo-37658 — the inner waiter
        # completing during cancellation eats the CancelledError on
        # py<=3.10), which would leave gather() blocked until the
        # generator exhausts naturally — the full --time-limit the
        # abort exists to cut short.
        while any(not t.done() for t in tasks):
            for t in tasks:
                if not t.done():
                    t.cancel()
            await asyncio.sleep(0.05)

    watcher = asyncio.create_task(watch())
    try:
        results = await asyncio.gather(*tasks, return_exceptions=True)
    finally:
        watcher.cancel()
        try:
            await watcher
        except asyncio.CancelledError:
            pass
    for r in results:
        if isinstance(r, BaseException) \
                and not isinstance(r, asyncio.CancelledError):
            if stopped:
                # Workers torn down mid-await can surface secondary
                # errors; the abort verdict is already decided.
                log.warning("worker error during fail-fast abort: %r", r)
            else:
                raise r
    if stopped:
        log.info("=== fail-fast: streamed check falsified the run; "
                 "aborting with %d history entries", len(recorder.history))
    return recorder.history


async def _setup_run(test: dict
                     ) -> tuple[Optional[Client], Optional[Nemesis]]:
    """Node + client data-plane + nemesis setup (reference
    Client.setup!, set.clj:15-16) — the run-lifecycle prologue shared
    by run_test and run_workload."""
    await _setup_nodes(test)
    client_proto: Optional[Client] = test.get("client")
    if client_proto is not None:
        c = await client_proto.open(test, test["nodes"][0])
        await c.setup(test)
        await c.close(test)
    nemesis: Optional[Nemesis] = test.get("nemesis")
    if nemesis is not None:
        await nemesis.setup(test)
    return client_proto, nemesis


async def _teardown_run(test: dict, client_proto: Optional[Client],
                        nemesis: Optional[Nemesis], store_dir=None
                        ) -> None:
    """The matching epilogue: nemesis heal -> client data-plane
    teardown -> node teardown (with log download when a store dir is
    given). ONE copy of the ordering — a reorder here serves
    `jepsen-tpu test` and the campaign alike."""
    if nemesis is not None:
        await nemesis.teardown(test)
    if client_proto is not None:
        c = await client_proto.open(test, test["nodes"][0])
        await c.teardown(test)
        await c.close(test)
    await _teardown_nodes(test, store_dir)


async def run_workload(test: dict, recorder: HistoryRecorder,
                       stop_check=None) -> list[Op]:
    """The slim embedding path (campaign/engine.py): client/nemesis
    setup -> generator interpretation -> client/nemesis teardown,
    WITHOUT the store, telemetry capture, or check phase `run_test`
    wraps around it. Callers that run thousands of scenarios (the
    scenario factory) own those concerns in batch: one obs capture per
    campaign, one corpus-batched check per campaign — paying the
    per-run versions thousands of times over is exactly the overhead
    the campaign exists to amortize. The caller supplies the recorder
    (a virtual-clock one for deterministic sim runs) and the optional
    fail-fast `stop_check` (same contract as interpret_generators)."""
    client_proto, nemesis = await _setup_run(test)
    try:
        return await interpret_generators(test, recorder,
                                          stop_check=stop_check)
    finally:
        await _teardown_run(test, client_proto, nemesis)


async def _setup_nodes(test: dict):
    db = test.get("db")
    os_setup = test.get("os_setup")

    async def setup_one(node):
        r = runner_for(test, node)
        if os_setup is not None:
            await os_setup(r, node)
        if db is not None:
            await db.setup(test, r, node)

    await asyncio.gather(*(setup_one(n) for n in test["nodes"]))


async def _teardown_nodes(test: dict, store_dir=None):
    db = test.get("db")
    if db is None:
        return
    async def teardown_one(node):
        r = runner_for(test, node)
        if store_dir is not None:
            for remote in db.log_files(test, node):
                local = store_dir / f"{node}-{remote.rsplit('/', 1)[-1]}"
                dl = getattr(r, "download", None)
                if dl is not None:
                    await dl(remote, str(local))
        await db.teardown(test, r, node)
    await asyncio.gather(*(teardown_one(n) for n in test["nodes"]))


async def run_test(test: dict) -> dict:
    """Execute a full test; returns the result map (with "valid").

    Opens a telemetry capture for the run's lifetime: phase spans
    (setup/run/teardown/check/store), worker/kernel metrics, and nemesis
    fault events land in <run_dir>/telemetry.jsonl + metrics.json next
    to the other store artifacts (obs/__init__.py; JEPSEN_TPU_TELEMETRY=0
    disables)."""
    from ..store import Store

    store = None
    log_handler = None
    if test.get("store_root") is not None:
        store = Store(test["store_root"]).new_run(test.get("name", "test"))
        log_handler = _attach_file_log(store.path)
    with obs.capture(store.path if store else None):
        try:
            return await _run_test_inner(test, store)
        finally:
            # Detach per-run file handler so later runs in the same process
            # (--test-count > 1) don't keep appending to this run's
            # jepsen.log.
            if log_handler is not None:
                _detach_file_log(log_handler)


async def _run_test_inner(test: dict, store) -> dict:
    tracer = obs.get_tracer()
    log.info("=== %s: setting up %d nodes", test.get("name"),
             len(test["nodes"]))
    t0 = time.monotonic()
    with tracer.span("setup", nodes=len(test["nodes"]),
                     workload=str(test.get("workload", ""))):
        client_proto, nemesis = await _setup_run(test)

    log.info("=== running workload")
    # Streaming check mode (ISSUE 5): the recorder's listener feeds a
    # background session that watermark-encodes and chunk-dispatches the
    # stable prefix into the resumable dense sweep WHILE workers run;
    # the check phase below becomes drain + finalize. Post remains the
    # default with zero behavior change; a non-streamable checker
    # topology falls back to post (stream/engine.session_for_test).
    check_mode = str(test.get("check_mode") or "post").lower()
    fail_fast = bool(test.get("fail_fast"))
    session = None
    if check_mode == "stream":
        from ..stream import session_for_test

        session = session_for_test(test)
        if session is None:
            log.info("=== check-mode stream unavailable for this checker; "
                     "running post-hoc")
        elif fail_fast:
            # Keys the workload rotates away from would otherwise hold
            # their last partial chunk unswept until the final drain —
            # at production chunk sizes the abort could never fire.
            session.enable_eager_flush()
    recorder = HistoryRecorder(listener=session.feed if session else None)

    def stop_check():
        # Fail-fast trigger: the streamed frontier falsified the run.
        if session.falsified():
            session.aborted = True
            return True
        return False

    try:
        with tracer.span("run",
                         concurrency=int(test.get("concurrency", 10)),
                         check_mode="stream" if session else "post") as sp:
            history = await interpret_generators(
                test, recorder,
                stop_check=stop_check if (session and fail_fast) else None)
            sp.set(history_entries=len(history))
    finally:
        if session is not None:
            # Close the stream's overlap window; the drain continues on
            # its own thread underneath the teardown below.
            session.finish_input()
        with tracer.span("teardown"):
            await _teardown_run(test, client_proto, nemesis,
                                store.path if store else None)

    run_s = time.monotonic() - t0
    log.info("=== run complete: %d history entries in %.1fs; checking",
             len(history), run_s)

    checker = test.get("checker")
    opts = {"store_dir": str(store.path)} if store else {}
    # Check phase compiles WGL kernels into the one persistent compile
    # cache (sched/compile_cache.py; idempotent — a CLI-level enable
    # wins), so embedding callers of run_test get the cross-process
    # compile reuse too. A completed check is a passive health proof
    # (obs/health.py) feeding /healthz.
    from ..sched import enable_persistent_cache

    enable_persistent_cache()
    supervisor = obs.health.get_supervisor()
    with tracer.span("check") as sp, \
            obs.maybe_jax_trace(store.path if store else None):
        if session is not None:
            # Drain + finalize: most of the check already happened
            # during the run; valid streamed verdicts settle their keys
            # in the checkers below, invalid keys re-run post-hoc for
            # witness reconstruction.
            with tracer.span("check.stream_drain"):
                stream_results = session.finalize()
            if stream_results is not None:
                opts["stream_results"] = stream_results
        result = (checker.check(test, history, opts)
                  if checker is not None else {"valid": True})
        sp.set(valid=str(result.get("valid")),
               profile=obs.active_profile_hash())
        if checker is not None:
            supervisor.note_ok(source="runner.check")
    result.setdefault("op_count",
                      sum(1 for o in history if o.type == INVOKE))
    result["run_seconds"] = run_s
    result["check_mode"] = "stream" if session is not None else "post"
    if session is not None:
        result["stream"] = session.stats()
    # Which tuning profile the check resolved (ISSUE 4): hash + every
    # non-default KernelLimits field with its provenance tag — lands in
    # results.json so the web run index can say which profile produced
    # each verdict/throughput figure.
    try:
        from ..tune.profile import run_record

        result["profile"] = run_record()
    except Exception:
        pass   # profile stamping is observability, never a failure mode

    if store is not None:
        with tracer.span("store"):
            store.write_run(test, history, result)
        log.info("=== stored run at %s", store.path)
    log.info("=== valid: %s", result.get("valid"))
    return result


def _attach_file_log(store_path) -> logging.Handler:
    """Tee the framework log into the run dir (reference: logback writes
    jepsen.log into the store [dep], SURVEY.md §5.5). Caller must detach
    with _detach_file_log. The run log always captures INFO regardless of
    the embedding app's root level (the artifact must be useful even when
    the host process never configured logging)."""
    root = logging.getLogger()
    handler = logging.FileHandler(store_path / "jepsen.log")
    handler.setFormatter(logging.Formatter(
        "%(asctime)s %(levelname)s %(name)s: %(message)s"))
    handler.setLevel(logging.INFO)
    handler._prev_root_level = root.level  # restored on detach
    if root.getEffectiveLevel() > logging.INFO:
        root.setLevel(logging.INFO)
    root.addHandler(handler)
    return handler


def _detach_file_log(handler: logging.Handler) -> None:
    root = logging.getLogger()
    root.removeHandler(handler)
    root.setLevel(handler._prev_root_level)
    handler.close()
