"""Hermetic in-process KV "cluster" with injectable consistency bugs.

The reference has no hermetic backend at all — every run needs a real 5-node
etcd cluster (SURVEY.md §4). This build adds one so the full pipeline
(generator → client → history → checker) runs in CI: a fake replicated
register store exposing the same 5-call surface the demo uses through
verschlimmbesserung (connect/get/reset/cas/swap — reference
src/jepsen/etcdemo.clj:79-98, set.clj:13-29), plus fault hooks the fake
nemesis drives.

Fault model:
  * Partition: the store tracks a set of "isolated" nodes. A client bound to
    an isolated node gets Timeout on every op (indeterminate — the op is
    counted as possibly-applied with probability `partial_apply_prob`,
    exercising the :info open-forever path end to end).
  * Injectable bugs (to prove the checkers DETECT badness, SURVEY.md §4):
      stale_read_prob      — non-quorum reads may return a stale snapshot
                             (quorum reads are always linearizable, matching
                             etcd's q=true semantics the -q flag toggles,
                             reference src/jepsen/etcdemo.clj:88,179)
      lost_write_prob      — acked writes that never took effect
      duplicate_cas_prob   — a failed CAS that actually applied (acked :fail
                             but took effect), the inverse indeterminacy
"""

from __future__ import annotations

import asyncio
import random
from typing import Any, Optional

from .base import (IndeterminateDequeue, NotFound, RetriesExhausted,
                   Timeout)


class FakeKVStore:
    """The simulated cluster: one logical linearizable register map, plus a
    bounded history of past snapshots for stale reads."""

    def __init__(self, nodes: Optional[list[str]] = None,
                 seed: int = 0,
                 stale_read_prob: float = 0.0,
                 lost_write_prob: float = 0.0,
                 duplicate_cas_prob: float = 0.0,
                 reorder_prob: float = 0.0,
                 duplicate_delivery_prob: float = 0.0,
                 partial_apply_prob: float = 0.5,
                 op_delay_s: float = 0.0,
                 timeout_s: float = 5.0):
        self.nodes = nodes or ["n1", "n2", "n3", "n4", "n5"]
        self.data: dict[str, Any] = {}
        self.queues: dict[str, list[Any]] = {}
        self.snapshots: list[dict[str, Any]] = []
        self.isolated: set[str] = set()
        self.rng = random.Random(seed)
        self.stale_read_prob = stale_read_prob
        self.lost_write_prob = lost_write_prob
        self.duplicate_cas_prob = duplicate_cas_prob
        self.reorder_prob = reorder_prob
        self.duplicate_delivery_prob = duplicate_delivery_prob
        self.partial_apply_prob = partial_apply_prob
        self.op_delay_s = op_delay_s
        # How long a client waits on a partitioned node before the op
        # reports a timeout: the reference client's 5 s. 0 (tests only)
        # reports at once, which lets one thread pile up an indeterminate
        # op per generator tick for the whole partition.
        self.timeout_s = timeout_s
        # jtlint: disable=JTL202 -- lifetime argument: a FakeKVStore is
        # built per test iteration (compose.fake_test constructs a fresh
        # one inside each cmd_test loop turn), so this lock never
        # survives into a second asyncio.run. If the fake ever becomes
        # long-lived, key it by running loop like db/etcd._install_lock.
        self.lock = asyncio.Lock()

    # -- fault hooks (driven by the fake nemesis) -------------------------
    def isolate(self, nodes: set[str]):
        self.isolated = set(nodes)

    def heal(self):
        self.isolated = set()

    def _snapshot(self):
        self.snapshots.append(dict(self.data))
        if len(self.snapshots) > 64:
            self.snapshots.pop(0)

    async def _time_out(self) -> None:
        if self.timeout_s:
            await asyncio.sleep(self.timeout_s)

    async def _enter(self, node: str):
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())
        if node in self.isolated:
            # Partitioned node: the op MAY still land (it raced the
            # partition). Apply-then-timeout gives the checker real
            # indeterminacy to reason about.
            await self._time_out()
            raise Timeout(f"node {node} partitioned")

    # -- the 5-call surface ----------------------------------------------
    async def get(self, node: str, key: str, quorum: bool = False) -> Any:
        await self._enter(node)
        async with self.lock:
            if (not quorum and self.snapshots
                    and self.rng.random() < self.stale_read_prob):
                snap = self.rng.choice(self.snapshots)
                return snap.get(key)
            return self.data.get(key)

    async def reset(self, node: str, key: str, value: Any) -> None:
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        async with self.lock:
            self._snapshot()
            if self.rng.random() >= self.lost_write_prob:
                self.data[key] = value
        if maybe_timeout:
            await self._time_out()
            raise Timeout(f"node {node} partitioned (op applied)")
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())

    async def cas(self, node: str, key: str, old: Any, new: Any) -> bool:
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        async with self.lock:
            if key not in self.data:
                raise NotFound(key)
            applied = self.data[key] == old
            if applied:
                self._snapshot()
                # Lost-update bug: ack success but drop the update.
                if self.rng.random() >= self.lost_write_prob:
                    self.data[key] = new
            elif self.rng.random() < self.duplicate_cas_prob:
                self._snapshot()
                self.data[key] = new  # bug: acked :fail but applied
        if maybe_timeout:
            await self._time_out()
            raise Timeout(f"node {node} partitioned (op applied)")
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())
        return applied

    async def txn(self, node: str, mops: list) -> list:
        """Atomic multi-key transaction over micro-ops (elle's list-append
        workload; no reference-demo counterpart — the fake cluster stands
        in for a transactional store so the elle checker has an end-to-end
        path). Micro-ops: ("append", k, v) appends v to the list under k;
        ("r", k, None) reads the list. Returns completed micro-ops with
        reads filled in. Injected bugs: lost_write_prob drops an acked
        append; stale_read_prob serves a read from an old snapshot (both
        elle-detectable anomalies)."""
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        out = []
        written: set = set()
        async with self.lock:
            self._snapshot()
            for mop in mops:
                f, k, v = mop
                if f == "append":
                    if self.rng.random() >= self.lost_write_prob:
                        cur = self.data.get(k)
                        cur = () if not isinstance(cur, tuple) else cur
                        self.data[k] = cur + (v,)
                    written.add(k)
                    out.append(("append", k, v))
                elif f == "r":
                    src = self.data
                    # Stale reads never hide the txn's OWN earlier append
                    # (read-your-writes inside a txn is assumed even by
                    # the buggy store, so the checker's :internal anomaly
                    # never fires on fake runs — it is golden-tested).
                    if (k not in written and self.snapshots
                            and self.rng.random() < self.stale_read_prob):
                        src = self.rng.choice(self.snapshots)
                    cur = src.get(k)
                    cur = () if not isinstance(cur, tuple) else cur
                    out.append(("r", k, cur))
                else:
                    raise ValueError(f"unknown micro-op {f!r}")
        if maybe_timeout:
            await self._time_out()
            raise Timeout(f"node {node} partitioned (txn applied)")
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())
        return out

    async def txn_register(self, node: str, mops: list) -> list:
        """Atomic multi-key REGISTER transaction (elle's rw-register
        workload — checkers/elle.py ElleRwChecker). Micro-ops:
        ("w", k, v) writes register k; ("r", k, None) reads it (None =
        the initial nil). Same injected bugs as txn(): lost_write_prob
        drops an acked write, stale_read_prob serves an old snapshot —
        both surface as elle anomalies (G-single-realtime and friends)."""
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        out = []
        overlay: dict = {}   # own writes, so read-your-writes holds even
        #                      when the store LOSES the write (same
        #                      contract as txn(): :internal never fires
        #                      on fake runs, it is golden-tested)
        async with self.lock:
            self._snapshot()
            for mop in mops:
                f, k, v = mop
                if f == "w":
                    if self.rng.random() >= self.lost_write_prob:
                        self.data[k] = v
                    overlay[k] = v
                    out.append(("w", k, v))
                elif f == "r":
                    if k in overlay:
                        out.append(("r", k, overlay[k]))
                        continue
                    src = self.data
                    if (self.snapshots
                            and self.rng.random() < self.stale_read_prob):
                        src = self.rng.choice(self.snapshots)
                    out.append(("r", k, src.get(k)))
                else:
                    raise ValueError(f"unknown register micro-op {f!r}")
        if maybe_timeout:
            await self._time_out()
            raise Timeout(f"node {node} partitioned (txn applied)")
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())
        return out

    # -- queue surface (queue workload; no reference counterpart — the
    # fifo/unordered-queue MODELS mirror knossos's model family) ----------
    async def enqueue(self, node: str, key: str, value: Any) -> None:
        """Append to the queue under `key`. Same indeterminacy model as
        reset(): on a partitioned node the op may land and then time out."""
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        async with self.lock:
            self.queues.setdefault(key, []).append(value)
        if maybe_timeout:
            await self._time_out()
            raise Timeout(f"node {node} partitioned (op applied)")
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())

    async def dequeue(self, node: str, key: str) -> Any:
        """Pop the queue head. Under partition the same indeterminacy
        protocol as the real etcd client (clients/etcd.py): with
        partial_apply_prob the pop HAPPENS and the ack is lost —
        IndeterminateDequeue carrying the claimed element (the one
        encodable indeterminate-dequeue shape, models/queues.py) — else a
        plain Timeout before any effect. Injectable bugs:
          reorder_prob            — pops a random position, not the head
                                    (FIFO violation)
          duplicate_delivery_prob — returns the head without removing it
                                    (element delivered twice)"""
        maybe_timeout = node in self.isolated
        if maybe_timeout and self.rng.random() >= self.partial_apply_prob:
            await self._time_out()
            raise Timeout(f"node {node} partitioned")
        async with self.lock:
            q = self.queues.get(key)
            if not q:
                if maybe_timeout:
                    await self._time_out()
                    raise Timeout(f"node {node} partitioned")
                raise NotFound(key)
            i = (self.rng.randrange(len(q))
                 if self.rng.random() < self.reorder_prob else 0)
            if self.rng.random() < self.duplicate_delivery_prob:
                got = q[i]
            else:
                got = q.pop(i)
        if maybe_timeout:
            raise IndeterminateDequeue(got)
        if self.op_delay_s:
            await asyncio.sleep(self.op_delay_s * self.rng.random())
        return got

    async def swap(self, node: str, key: str, fn) -> Any:
        """Atomic read-modify-write retry loop — verschlimmbesserung's swap!
        (reference set.clj:26-31 uses it for set adds)."""
        for _ in range(64):
            await self._enter(node)
            async with self.lock:
                if key not in self.data:
                    raise NotFound(key)
                cur = self.data[key]
            new = fn(cur)
            try:
                if await self.cas(node, key, cur, new):
                    return new
            except NotFound:
                raise
        raise RetriesExhausted("swap retry budget exhausted: 64 determinate CAS failures")


