"""Streaming check engine: stable-prefix chunk dispatch against the live run.

The harness used to run strictly ``run -> check``: the full history was
recorded, then the check phase encoded and swept it from scratch, so
end-to-end wall clock was run_time + check_time even though the WGL
chunked kernels are resumable (the frontier carry chains across chunk
launches). Lowe's P-compositionality / just-in-time linearization
observation applies here exactly: the sweep only ever needs a CLOSED
prefix of the history, and the prefix closes continuously while the run
is still going. This module streams it:

  * **Watermark** (ops/encode.py IncrementalEncoder): events become
    stable once their position precedes every still-open invoke — an
    op that will crash pins the watermark from its invoke until its
    ``:info`` completion is recorded, then is encoded pending-forever
    per WGL semantics. Ordering keys on the recorder's monotonic
    per-entry ``seq``, never wall clock.
  * **Incremental encoder**: stable events append to the packed rows /
    running slot-table snapshot instead of re-encoding the history.
  * **Chunk dispatcher** (KeyStream): every ``limits().stream_flush_ops``
    stable return steps form one chunk fed into the SAME resumable
    dense chunk kernel the post-hoc long sweep uses
    (wgl3._cached_chunk_run — donated carry, async dispatch), so the
    device pipelines chunk N+1's transfer behind chunk N, double-
    buffered against the live run on the host side by the consumer
    thread. The frontier's death flag is polled every
    ``limits().stream_max_lag_chunks`` chunks — the fail-fast bound.
  * **Geometry restarts**: the dense table's shape depends on
    (max_pending, max_value), which only GROW as the run proceeds.
    When a flush would outgrow the current DenseConfig, the engine
    re-derives the geometry and re-dispatches the (still cheap, early)
    stable prefix from scratch — O(log) restarts per run, after which
    the kernel shape is stable and every key shares the same compiled
    ``(cfg, chunk)`` entry through the wgl3 kernel cache (the sched
    engine's bucket discipline applied to streams).
  * **Multiplex** (StreamSession keyed mode): independent-key histories
    split per key incrementally (exactly checkers/independent.py
    split_by_key) and share the dispatcher thread + compiled chunk
    kernels.

Verdicts are BIT-IDENTICAL to the post-hoc path by construction: the
stable rows equal the post-hoc encoding's prefix (IncrementalEncoder
contract), chunk boundaries don't change the scan semantics (the carry
chains exactly; pads contribute nothing), and dead carries are sticky
(post-death chunks add zero configs), so survived / dead_step /
max_frontier / configs_explored all match the chunked dense sweep.
tests/test_stream.py pins this on golden + fuzz histories, crashed-op
pinning, fail-fast teardown, and a corpus multiplex.

The runner (runner/core.py) wires it end to end under
``--check-mode stream``: the recorder's listener feeds the session, the
check phase becomes drain + finalize, and valid streamed verdicts
settle their keys in the checkers (checkers/linearizable.py /
independent.py) — invalid keys re-run post-hoc for witness
reconstruction, so counterexample artifacts are unchanged.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Optional

import numpy as np

from .. import obs
from ..models.base import Model
from ..ops.encode import (EV_INVOKE, EV_RETURN, EncodeError,
                          IncrementalEncoder, EncodedHistory,
                          encode_return_steps)
from ..ops.limits import limits
from ..ops.op import INVOKE, Op

log = logging.getLogger(__name__)

_DONE = object()   # input-exhausted sentinel on the session queue

# The streamed chunk rung's kernel name (results / bench / web).
STREAM_KERNEL = "wgl3-dense-stream-chunked"


class KeyStream:
    """One key's streaming check: incremental encoder + running slot
    snapshot + chunk dispatch into a resumable dense frontier carry."""

    def __init__(self, model: Model, key: Any, k_slots: int = 32):
        self.model = model
        self.key = key
        self.k0 = k_slots
        self.encoder = IncrementalEncoder(model)
        self.cfg = None                # current DenseConfig (None = not yet)
        self.carry = None
        self.parts = None              # device-side partial sums [3]
        self.steps_done = 0            # return steps dispatched this epoch
        self.real_dispatched = 0       # real (non-pad) steps this epoch
        self.live_high = 0             # high-water of real steps dispatched live
        self.total_high = 0            # high-water of real steps dispatched
        self.chunks = 0
        self.restarts = 0
        self.dispatch_s = 0.0
        self._since_poll = 0
        self.last_flush = time.monotonic()
        self.dead = False
        self.infeasible: Optional[str] = None
        # Running slot snapshot over the stable rows, at a growable
        # capacity (snapshot semantics are width-independent: slots
        # beyond max_pending are inactive zeros).
        self._tab = np.zeros((8, 4), np.int32)
        self._act = np.zeros((8,), bool)
        # Buffered return steps awaiting a full chunk:
        # (tab snapshot [cap,4], active [cap], target slot).
        self._buf: list[tuple[np.ndarray, np.ndarray, int]] = []

    # -- feeding ----------------------------------------------------------
    def feed(self, op: Op, live: bool) -> None:
        self._advance(self.encoder.append(op), live)

    def _advance(self, rows, live: bool) -> None:
        if self.infeasible or not rows:
            return
        for kind, slot, f, a1, a2, rv in rows:
            if slot >= self._act.shape[0]:
                grow = max(8, slot + 1 - self._act.shape[0])
                self._tab = np.concatenate(
                    [self._tab, np.zeros((grow, 4), np.int32)])
                self._act = np.concatenate(
                    [self._act, np.zeros((grow,), bool)])
            if kind == EV_INVOKE:
                self._tab[slot] = (f, a1, a2, rv)
                self._act[slot] = True
            elif kind == EV_RETURN:
                # Snapshot just BEFORE processing the return: the
                # returning op itself counts active (encode.py
                # encode_return_steps contract). A dead frontier is
                # sticky — post-death steps would be no-op chunks, so
                # stop buffering them (the verdict is already final).
                if not self.dead:
                    self._buf.append((self._tab.copy(), self._act.copy(),
                                      int(slot)))
                self._act[slot] = False
        chunk = limits().stream_flush_ops
        while len(self._buf) >= chunk and not self.dead \
                and self.infeasible is None:
            if not self._ensure_geometry(live):
                return
            chunk = limits().stream_flush_ops   # _restart may consume buf
            if len(self._buf) < chunk:
                break
            steps, self._buf = self._buf[:chunk], self._buf[chunk:]
            self._dispatch(steps, live, pad_to=chunk)

    def flush_partial(self, live: bool) -> None:
        """Dispatch the buffered tail as one PADDED chunk without waiting
        for a full stream_flush_ops accumulation, then poll death
        immediately — the fail-fast lag bound for keys the workload has
        retired (their buffers would otherwise sit unswept until the
        final drain, so at production chunk sizes a falsified key could
        never trigger the abort). Bit-safe: pad steps are no-ops in the
        scan (make_step_fn3 gates every effect on target >= 0) and chunk
        indexing keys on real_dispatched, so later real steps keep their
        post-hoc indices."""
        if self.dead or self.infeasible or not self._buf:
            return
        if not self._ensure_geometry(live):
            return
        chunk = limits().stream_flush_ops
        while len(self._buf) >= chunk and not self.dead:
            steps, self._buf = self._buf[:chunk], self._buf[chunk:]
            self._dispatch(steps, live, pad_to=chunk)
        if self._buf and not self.dead:
            steps, self._buf = self._buf, []
            self._dispatch(steps, live, pad_to=chunk)
        self._poll_death()

    # -- geometry ---------------------------------------------------------
    def _needed_cfg(self):
        from ..ops import wgl3

        k = wgl3.tight_k_for_pending(self.encoder.max_pending)
        if self.cfg is not None:
            k = max(k, self.cfg.k_slots)
        return wgl3.dense_config(self.model, k, self.encoder.max_value,
                                 budget=limits().dense_cell_budget_chunked)

    def _ensure_geometry(self, live: bool) -> bool:
        """True when the current cfg covers the stable rows; restarts the
        sweep under a bigger geometry when they outgrew it; False (and
        marks infeasible) when no dense geometry serves them — the key
        falls back to the post-hoc ladder untouched."""
        need = self._needed_cfg()
        if need is None:
            self.infeasible = (
                f"dense geometry infeasible (max_pending="
                f"{self.encoder.max_pending}, max_value="
                f"{self.encoder.max_value})")
            self._buf = []
            return False
        if need != self.cfg:
            self._restart(need, live)
        return True

    def _restart(self, cfg, live: bool) -> None:
        """Re-derive the sweep under a new geometry: rebuild return steps
        from the stable rows (vectorized), reset the carry, re-dispatch
        the full chunks, re-buffer the tail. Cheap by construction —
        geometries only grow O(log) times, all early in a run."""
        from ..ops import wgl3

        if self.cfg is not None:
            self.restarts += 1
        self.cfg = cfg
        self.carry = wgl3._init_carry3(self.model, cfg)
        self.parts = None
        self.steps_done = 0
        self.real_dispatched = 0
        self.chunks = 0
        self._since_poll = 0
        self.dead = False
        rows = self.encoder.rows
        enc = EncodedHistory(
            events=np.asarray(rows, np.int32).reshape(-1, 6),
            n_events=len(rows), n_ops=self.encoder.n_ops,
            k_slots=cfg.k_slots, max_pending=self.encoder.max_pending,
            max_value=self.encoder.max_value)
        rs = encode_return_steps(enc)
        chunk = limits().stream_flush_ops
        full = rs.n_steps // chunk * chunk
        self._buf = [(rs.slot_tabs[i], rs.slot_active[i],
                      int(rs.targets[i])) for i in range(full, rs.n_steps)]
        for c0 in range(0, full, chunk):
            self._dispatch_arrays(
                rs.slot_tabs[c0:c0 + chunk], rs.slot_active[c0:c0 + chunk],
                rs.targets[c0:c0 + chunk], live=live, real=chunk)

    # -- dispatch ---------------------------------------------------------
    def _dispatch(self, steps, live: bool, pad_to: int) -> None:
        K = self.cfg.k_slots
        tabs = np.zeros((pad_to, K, 4), np.int32)
        act = np.zeros((pad_to, K), bool)
        tgt = np.full((pad_to,), -1, np.int32)
        for i, (t, a, s) in enumerate(steps):
            w = min(K, t.shape[0])
            tabs[i, :w] = t[:w]
            act[i, :w] = a[:w]
            tgt[i] = s
        self._dispatch_arrays(tabs, act, tgt, live, real=len(steps))

    def _dispatch_arrays(self, tabs, act, tgt, live: bool,
                         real: int) -> None:
        import jax.numpy as jnp

        chunk = tgt.shape[0]
        # Through the KernelPlan layer (plan/dispatch.py): always the
        # PLAIN (no-canonicalization) wgl3 chunk family — the frontier
        # dedup pass (ops/canon.py) needs to know which pending ops
        # never return in the REMAINING history, and a live stream
        # cannot know its future — an op pending now may still complete
        # later. Post-hoc sweeps of the same key run canon-free too for
        # short histories (batched kernels), so streamed and post-hoc
        # metrics stay bit-identical (plan_stream_chunk docstring).
        from .. import plan as kplan

        run = kplan.resolve(
            kplan.plan_stream_chunk(self.model, self.cfg, chunk))
        t0 = time.monotonic()
        with obs.get_tracer().span("stream.chunk", key=str(self.key),
                                   steps=real, live=bool(live)):
            # Chunks index by REAL steps dispatched, not padded: pad
            # steps are scan no-ops, so a padded partial chunk (eager
            # fail-fast flush) mid-stream leaves every later real step's
            # dead_step index exactly where the post-hoc encoding puts
            # it.
            self.carry, part = run(
                self.carry, jnp.asarray(tabs), jnp.asarray(act),
                jnp.asarray(tgt), jnp.int32(self.real_dispatched))
        self.dispatch_s += time.monotonic() - t0
        self.last_flush = t0
        # A successful chunk dispatch is a free backend-health proof
        # (obs/health.py): the consumer thread is one of the supervisor's
        # passive signal sources.
        obs.health.get_supervisor().note_ok(source="stream.dispatch")
        self.parts = part if self.parts is None else self.parts + part
        self.steps_done += chunk
        self.real_dispatched += real
        self.total_high = max(self.total_high, self.real_dispatched)
        if live:
            self.live_high = max(self.live_high, self.real_dispatched)
        self.chunks += 1
        self._since_poll += 1
        if self._since_poll >= limits().stream_max_lag_chunks:
            self._poll_death()

    def _poll_death(self) -> None:
        """Fetch the frontier's death flag; a dead carry is sticky, so
        buffered post-death steps are dropped (zero-config no-ops)."""
        self._since_poll = 0
        if self.carry is not None and not self.dead \
                and bool(np.asarray(self.carry.dead)):
            self.dead = True
            self._buf = []   # post-death chunks are no-ops; skip them

    # -- finalize ---------------------------------------------------------
    def finalize(self) -> Optional[dict]:
        """Drain + fetch: the streamed check result in the chunked dense
        sweep's schema (plus ``model`` / ``streamed`` / ``_enc``), or
        None when this key abandoned streaming (post-hoc takes over)."""
        from ..ops import wgl3
        from ..ops.wgl import verdict

        self._advance(self.encoder.finalize(), live=False)
        enc = self.encoder.encoded_history(self.k0)
        if self.infeasible is not None:
            return None
        if enc.n_events == 0:
            return {"valid": True, "op_count": 0, "model": self.model.name,
                    "streamed": True, "_enc": enc}
        if not self._ensure_geometry(live=False):
            return None
        if self._buf and not self.dead:
            chunk = limits().stream_flush_ops
            steps, self._buf = self._buf, []
            self._dispatch(steps, live=False,
                           pad_to=max(chunk, len(steps)))
        import jax.numpy as jnp

        parts = self.parts if self.parts is not None \
            else jnp.zeros((3,), jnp.float32)
        # The streamed chunks ran wgl3's resumable chunk kernel, so the
        # fetch row is 3 verdict fields + ITS declared partial layout.
        # jtflow: partials-from wgl3._chunk_fn
        packed = np.asarray(jnp.concatenate([
            jnp.stack([jnp.where(self.carry.dead, 0, 1),
                       self.carry.dead_step, self.carry.max_frontier]),
            jnp.clip(parts, 0, 2**31 - 1).astype(jnp.int32)]))
        out = {
            "survived": bool(packed[0]),
            "overflow": False,
            "dead_step": int(packed[1]),
            "max_frontier": int(packed[2]),
            "configs_explored": int(packed[3]),
        }
        out["sweep"] = wgl3.sweep_summary(self.cfg, live_sum=float(packed[4]),
                                          real_steps=int(packed[5]))
        out["live_tile_ratio"] = out["sweep"]["live_tile_ratio"]
        out["valid"] = verdict(out)
        obs.record_check_result(out)
        out.update(op_count=enc.n_ops, kernel=STREAM_KERNEL,
                   model=self.model.name,
                   table_cells=self.cfg.n_states * self.cfg.n_masks,
                   streamed=True)
        out["_enc"] = enc
        return out


class StreamSession:
    """The run-facing half: a queue + consumer thread multiplexing the
    recorder's live op feed into per-key KeyStreams.

    ``feed`` (the HistoryRecorder listener) is O(enqueue); all encoding
    and device work happens on the consumer thread, concurrently with
    the event loop's workers — that concurrency IS the overlap. The
    check phase calls :meth:`finalize` (drain + fetch); ``--fail-fast``
    polls :meth:`falsified` from the runner."""

    def __init__(self, model: Model, keyed: bool, k_slots: int = 32):
        self.model = model
        self.keyed = keyed
        self.k0 = k_slots
        # Fail-fast abort latch. An Event, not a bare bool: the runner's
        # watcher (event-loop thread) sets it while the consumer thread
        # is mid-dispatch — the consumer and the finalize path both key
        # off it to STOP dispatching (see finalize: an aborted session
        # must not launch its buffered tails).
        self._abort = threading.Event()
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._streams: dict[Any, KeyStream] = {}
        self._key_of_process: dict[Any, Any] = {}
        self._falsified: dict[Any, int] = {}
        self._broken: Optional[str] = None
        self._run_live = threading.Event()
        self._run_live.set()
        self._done_sent = False
        self._eager_flush_s: Optional[float] = None
        self._fed = 0
        self._lag_max = 0
        self._encode_s = 0.0
        self._results: Optional[dict] = None
        self._thread = threading.Thread(target=self._consume,
                                        name="stream-check", daemon=True)
        self._thread.start()

    @property
    def aborted(self) -> bool:
        return self._abort.is_set()

    @aborted.setter
    def aborted(self, value: bool) -> None:
        if value:
            self._abort.set()
        else:
            self._abort.clear()

    # -- event-loop side --------------------------------------------------
    def feed(self, op: Op) -> None:
        """HistoryRecorder listener: enqueue and return."""
        if op.process == "nemesis":
            return
        self._q.put(op)

    def finish_input(self) -> None:
        """The run is over: anything dispatched after this no longer
        counts as overlap, and the consumer thread exits once the queue
        drains. Idempotent."""
        self._run_live.clear()
        if not self._done_sent:
            self._done_sent = True
            self._q.put(_DONE)

    def enable_eager_flush(self, interval_s: float = 0.5) -> None:
        """Fail-fast mode (runner/core.py): partial-flush any key whose
        buffer has sat idle for interval_s, so a falsified key the
        workload already rotated away from still triggers the abort
        within ~interval_s instead of waiting for a full
        stream_flush_ops chunk that will never arrive. Costs at most
        one padded chunk launch per key per interval; verdicts stay
        bit-identical (KeyStream.flush_partial)."""
        self._eager_flush_s = float(interval_s)

    def falsified(self) -> bool:
        """True once any key's streamed frontier died — the --fail-fast
        trigger (detection lag is bounded by stream_max_lag_chunks
        chunks of stream_flush_ops steps; with eager flush enabled,
        additionally by ~the flush interval for idle keys)."""
        return bool(self._falsified)

    # -- consumer thread --------------------------------------------------
    def _consume(self) -> None:
        while True:
            try:
                op = self._q.get(timeout=self._eager_flush_s)
            except queue.Empty:
                # Idle with eager flush on: sweep stale key buffers so a
                # quiet (or rotated-away) falsified key still trips the
                # fail-fast watcher.
                try:
                    self._flush_stale(live=self._run_live.is_set())
                except Exception as e:
                    self._broken = f"{type(e).__name__}: {e}"
                    log.exception("streaming eager flush crashed; "
                                  "falling back to post-hoc")
                continue
            if op is _DONE:
                return
            if self._broken is not None:
                continue   # drain cheaply; post-hoc owns the check now
            if self._abort.is_set():
                # Fail-fast already fired: the verdict is decided and
                # the runner is tearing the workers down. Dispatching
                # the still-queued tail would launch more chunks whose
                # spans land after the run span closed — and an abort
                # landing mid-dispatch used to leave the final partial
                # chunk's span in exactly that orphaned state. Drain
                # cheaply instead; post-hoc owns every verdict now.
                continue
            t0 = time.monotonic()
            try:
                self._feed_one(op, live=self._run_live.is_set())
            except (EncodeError, ValueError) as e:
                # A shape streaming can't handle (malformed pairing, a
                # non-(key, value) independent op): abandon the WHOLE
                # session — the post-hoc checker will see the same
                # history and fail (or cope) exactly as it does today.
                self._broken = f"{type(e).__name__}: {e}"
                log.warning("streaming check abandoned: %s", self._broken)
            except Exception as e:   # never let the checker thread die silently
                self._broken = f"{type(e).__name__}: {e}"
                log.exception("streaming check crashed; falling back "
                              "to post-hoc")
                # An unexplained dispatch-path crash is a backend health
                # signal; the supervisor decides whether it accumulates
                # to degraded/wedged.
                obs.health.get_supervisor().note_failure(
                    self._broken, source="stream.consumer")
            finally:
                self._encode_s += time.monotonic() - t0
                self._fed += 1
        # not reached

    def _feed_one(self, op: Op, live: bool) -> None:
        if self.keyed:
            routed = self._route(op)
            if routed is None:
                return
            key, sub = routed
        else:
            key, sub = None, op
        ks = self._streams.get(key)
        if ks is None:
            ks = self._streams[key] = KeyStream(self.model, key, self.k0)
        ks.feed(sub, live)
        lag = ks.encoder.lag()
        self._lag_max = max(self._lag_max, lag)
        obs.get_metrics().gauge("stream.watermark_lag").set(lag)
        self._note_dead(key, ks)
        if self._eager_flush_s is not None:
            self._flush_stale(live)

    def _flush_stale(self, live: bool) -> None:
        """Eager-flush keys whose buffers sat idle past the interval
        (enable_eager_flush); O(keys) per sweep, each stale key costs at
        most one padded chunk launch per interval."""
        if self._eager_flush_s is None or self._abort.is_set():
            return
        cutoff = time.monotonic() - self._eager_flush_s
        for key, ks in self._streams.items():
            if ks._buf and ks.last_flush < cutoff:
                ks.flush_partial(live)
                self._note_dead(key, ks)

    def _note_dead(self, key, ks: KeyStream) -> None:
        if ks.dead and key not in self._falsified:
            self._falsified[key] = int(np.asarray(ks.carry.dead_step)) \
                if ks.carry is not None else -1
            obs.get_tracer().event("stream.falsified", key=str(key),
                                   dead_step=self._falsified[key])

    def _route(self, op: Op):
        """checkers/independent.py split_by_key, one op at a time."""
        if op.type == INVOKE:
            if not (isinstance(op.value, tuple) and len(op.value) == 2):
                raise ValueError(
                    f"independent history op without (key, value) tuple: "
                    f"{op}")
            k, v = op.value
            self._key_of_process[op.process] = k
        else:
            k = self._key_of_process.pop(op.process, None)
            if k is None:
                return None
            v = op.value[1] if (isinstance(op.value, tuple)
                                and len(op.value) == 2) else op.value
        return k, Op(type=op.type, f=op.f, value=v, process=op.process,
                     time=op.time, index=op.index, error=op.error,
                     seq=op.seq)

    # -- check-phase side -------------------------------------------------
    def finalize(self) -> Optional[dict]:
        """Join the consumer, finalize every key stream, publish the
        telemetry gauges. Returns {key: streamed result} (None when the
        session abandoned streaming entirely). Idempotent."""
        if self._results is not None:
            return self._results or None
        self.finish_input()
        self._thread.join()
        metrics = obs.get_metrics()
        results: dict[Any, dict] = {}
        if self._abort.is_set():
            # Fail-fast teardown (ISSUE 15 satellite): the run was
            # aborted because some key's streamed frontier died — the
            # post-hoc checker re-checks the recorded history whole, so
            # per-key finalize work here is pure waste. Worse than
            # waste: every key with a buffered tail would dispatch one
            # more padded chunk, emitting a telemetry span AFTER the
            # run span closed (the abort routinely lands mid-dispatch),
            # and a campaign's thousands of aborted runs turned those
            # orphan spans into tracer-cap truncation-footer noise.
            # Abandon every tail instead: no further dispatches, no new
            # spans — and a partial-prefix sweep must not settle a key
            # as valid anyway (the prefix proves nothing about the
            # whole history), so returning NO streamed results is the
            # only sound choice. tests/test_campaign.py pins both the
            # no-new-spans and the no-settle halves.
            self._finalize_stats(metrics, abandoned=len(self._streams))
            self._results = {}
            return None
        if self._broken is None:
            for key, ks in self._streams.items():
                t0 = time.monotonic()
                try:
                    res = ks.finalize()
                except Exception as e:
                    log.exception("stream finalize failed for key %r", key)
                    res = None
                self._encode_s += time.monotonic() - t0
                if res is not None:
                    results[key] = res
                    enc = res.get("_enc")
                    if enc is not None and enc.n_events \
                            and res.get("valid") is True:
                        # The post-hoc encode these keys skipped (web's
                        # check-eps column derives event counts from
                        # encode.event_bytes). Only VALID verdicts
                        # settle (checkers/linearizable._stream_result);
                        # invalid keys re-run post-hoc, whose
                        # encode_events counts the same history itself.
                        metrics.counter("encode.event_bytes").add(
                            int(enc.events[: enc.n_events].nbytes))
                        metrics.counter("encode.histories").add(1)
        self._finalize_stats(metrics, streamed_keys=len(results))
        self._results = results
        return results or None

    def _finalize_stats(self, metrics, streamed_keys: int = 0,
                        abandoned: int = 0) -> None:
        """Publish the session gauges + build the results.json stream
        record — shared by the normal and the aborted finalize paths.
        The consumer-thread wall minus the time spent inside chunk
        dispatches (those already land in wgl.compile_s/execute_s via
        instrument_kernel) is the honest host-encode share."""
        dispatch_s = sum(ks.dispatch_s for ks in self._streams.values())
        encode_s = max(0.0, self._encode_s - dispatch_s)
        metrics.counter("encode.encode_s").add(encode_s)
        self._encode_host_s = encode_s
        total = sum(ks.total_high for ks in self._streams.values())
        live = sum(ks.live_high for ks in self._streams.values())
        overlap = live / total if total else 0.0
        metrics.gauge("stream.overlap_ratio").set(overlap)
        self._stats = {
            "overlap_ratio": round(overlap, 4),
            "keys": len(self._streams),
            "streamed_keys": streamed_keys,
            "chunks": sum(ks.chunks for ks in self._streams.values()),
            "restarts": sum(ks.restarts for ks in self._streams.values()),
            "steps_total": int(total),
            "steps_overlapped": int(live),
            "watermark_lag_max": int(self._lag_max),
            "encode_s": round(encode_s, 4),
            "dispatch_s": round(dispatch_s, 4),
            "failfast_aborted": self.aborted,
        }
        if abandoned:
            # How many keys' buffered tails the abort abandoned — the
            # fail-fast accounting the campaign report surfaces.
            self._stats["abandoned_keys"] = abandoned
        if self._broken:
            self._stats["fallback"] = self._broken

    def stats(self) -> dict:
        """The results.json ``stream`` record (finalize() must have run)."""
        stats = dict(getattr(self, "_stats", {}))
        stats["failfast_aborted"] = self.aborted
        return stats


def session_for_test(test: dict):
    """Build the streaming session for a composed test, or None when its
    checker topology is not streamable (no jax Linearizable or elle
    checker, or a model whose prepare_history rewrites the history
    statefully — the stream feeds RAW ops, so only identity-translation
    models qualify). The caller falls back to post-hoc checking, with
    zero behavior change. Transactional topologies (an ElleChecker in
    the tree) stream through the incremental dependency-graph session
    (stream/elle.py) instead of the WGL chunk dispatcher."""
    found = _find_streamable(test.get("checker"))
    if found is None:
        from .elle import ElleStreamSession, find_elle_checker

        elle = find_elle_checker(test.get("checker"))
        if elle is not None:
            return ElleStreamSession(elle)
        return None
    lin, keyed = found
    if type(lin.model).prepare_history is not Model.prepare_history:
        log.info("check-mode stream: model %r translates histories; "
                 "falling back to post-hoc", lin.model.name)
        return None
    return StreamSession(lin.model, keyed=keyed, k_slots=lin.k_slots)


def _find_streamable(checker) -> Optional[tuple]:
    """Walk the checker tree for the first jax-backed Linearizable:
    (lin, keyed) — keyed when it sits under an IndependentChecker."""
    from ..checkers.compose import Compose
    from ..checkers.independent import IndependentChecker
    from ..checkers.linearizable import Linearizable

    if isinstance(checker, Linearizable):
        return (checker, False) if checker.backend == "jax" else None
    if isinstance(checker, IndependentChecker):
        sub = _find_streamable(checker.sub_checker)
        return (sub[0], True) if sub is not None else None
    if isinstance(checker, Compose):
        for sub in checker.checkers.values():
            found = _find_streamable(sub)
            if found is not None:
                return found
    return None
