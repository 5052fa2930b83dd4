"""Encode a concurrent history into padded int32 event tensors for the checker.

This is the boundary between the host plane (Python op records) and the device
plane (JAX kernels). A register-workload history (reference ops constructed at
src/jepsen/etcdemo.clj:67-69, completed at :83-105) becomes:

  events[E, 6] int32 rows: (kind, slot, f, a1, a2, rv)

    kind: EV_INVOKE — an op becomes pending (its fields are loaded into `slot`)
          EV_RETURN — the op in `slot` returned ok; every surviving
                      linearization must have linearized it by now
          EV_PAD    — padding (no-op)
    f:    F_READ / F_WRITE / F_CAS
    a1,a2: op arguments (write value; cas old/new)
    rv:   observed value for reads (NIL when the key was missing)

Completion-status handling (the correctness-critical part, reference
src/jepsen/etcdemo.clj:100-105):
  ok    -> EV_INVOKE at the invoke's history position, EV_RETURN at the
           completion's position.
  info  -> EV_INVOKE only: the op stays pending forever and may be linearized
           at any later point, but never must be. (Indeterminate reads impose
           no constraint at all and are dropped entirely.)
  fail  -> dropped: the op is known not to have taken effect.

Slots: because per-process ops are sequential, the number of simultaneously
pending ops is bounded by concurrency plus the number of accumulated `info`
ops. Each pending op occupies one of `k_slots` slots for the duration of its
pendingness; a config's "linearized set" is then a fixed-width bitmask over
slots rather than an unbounded set — this is what makes the search frontier a
static-shape tensor. Slot ids are freed on EV_RETURN (at that point every
surviving config has linearized the op, so its bit is cleared everywhere).
"""

from __future__ import annotations

import dataclasses
import heapq
import itertools
import time
from dataclasses import dataclass
from typing import Any, Optional, Sequence

import numpy as np

from ..obs import get_metrics
from .op import Op, INVOKE, OK, FAIL, INFO, keyed_value

# Value encoding. The reference register draws values from (rand-int 5), i.e.
# 0..4 (src/jepsen/etcdemo.clj:68-69); NIL encodes "key missing" observed by a
# read (parse-long of nil at :71-74,87-90). Any int32 value >= 0 is supported.
NIL = -1

# Function codes. F_READ is, BY CONVENTION, the pure-observation code in
# every model's op language (it never mutates model state): the encoder
# relies on this to drop indeterminate observations — an :info op with no
# state effect imposes no constraint (reference :info mapping,
# src/jepsen/etcdemo.clj:100-102) — without consulting the model. Codes
# 3..5 are claimed by the non-register model families (models/gset.py,
# models/queues.py); codes are only meaningful within one model's language,
# so families may reuse them.
F_READ, F_WRITE, F_CAS = 0, 1, 2
F_ADD, F_ENQ, F_DEQ = 3, 4, 5
FUNC_CODES = {"read": F_READ, "write": F_WRITE, "cas": F_CAS}

# Event kinds.
EV_INVOKE, EV_RETURN, EV_PAD = 0, 1, 2

EVENT_WIDTH = 6  # (kind, slot, f, a1, a2, rv)

# Bump on ANY change to the encoder's input->tensor mapping (pairing,
# slot assignment, field layout, value encoding): the content-addressed
# encoded-tensor cache (store/encode_cache.py) keys on it, so a stale
# persisted encoding can never survive an encoder fix.
ENCODING_VERSION = 1


class EncodeError(ValueError):
    pass


class SlotOverflow(EncodeError):
    """More simultaneously-pending ops than k_slots."""


@dataclass
class Invocation:
    """One paired invocation: invoke entry + (optional) completion entry."""

    f: int                 # F_READ / F_WRITE / F_CAS
    a1: int
    a2: int
    rv: int                # observed read value (NIL if none / missing key)
    status: str            # ok | fail | info
    invoke_index: int      # position of the invoke entry in the history
    complete_index: int    # position of the completion entry; -1 if none
    process: Any = None


@dataclass
class EncodedHistory:
    """Padded event tensor plus bookkeeping, ready for the WGL kernels."""

    events: np.ndarray     # [E, 6] int32
    n_events: int          # real (non-pad) events
    n_ops: int             # invocations included (ok + open info)
    k_slots: int
    max_pending: int       # high-water mark of simultaneously pending ops
    max_value: int = 0     # largest encoded value (a1/a2/rv); bounds the
    #                        model state space for packed-key dedup

    def padded_to(self, e_cap: int) -> "EncodedHistory":
        if e_cap < self.events.shape[0]:
            raise EncodeError(
                f"cannot pad events of length {self.events.shape[0]} to {e_cap}"
            )
        ev = np.full((e_cap, EVENT_WIDTH), 0, dtype=np.int32)
        ev[:, 0] = EV_PAD
        ev[: self.events.shape[0]] = self.events
        return EncodedHistory(ev, self.n_events, self.n_ops, self.k_slots,
                              self.max_pending, self.max_value)

    def to_arrays(self) -> dict[str, np.ndarray]:
        """npz-ready dict (trimmed to real events) — ONE serialization
        shape shared by the store's history-tensor artifacts and the
        encoded-tensor cache, so the two cannot drift."""
        return {"events": np.asarray(self.events[: self.n_events]),
                "n_ops": np.asarray(self.n_ops),
                "k_slots": np.asarray(self.k_slots),
                "max_pending": np.asarray(self.max_pending),
                "max_value": np.asarray(self.max_value)}

    @classmethod
    def from_arrays(cls, z) -> "EncodedHistory":
        """Inverse of to_arrays over any mapping of arrays (an open
        np.load handle included)."""
        events = np.asarray(z["events"], dtype=np.int32)
        return cls(events=events, n_events=int(events.shape[0]),
                   n_ops=int(z["n_ops"]), k_slots=int(z["k_slots"]),
                   max_pending=int(z["max_pending"]),
                   max_value=int(z["max_value"]))


def _encode_value(v: Any) -> int:
    if type(v) is int and v >= 0:
        return v                     # the common case, checked first
    if v is None:
        return NIL
    v = int(v)
    if v < 0:
        # NIL (-1) is the reserved "key missing" sentinel; admitting negative
        # payloads would both collide with it and corrupt the packed-key
        # dedup (uint32 wraparound). Reject loudly instead of mis-checking.
        raise EncodeError(
            f"negative history values are unsupported (got {v}); "
            f"-1 is the NIL sentinel")
    return v


def register_fields(f_name: str, invoke_value: Any, ok_value: Any,
                    status: str) -> tuple[int, int, int, int]:
    """The register op language (reference ops at src/jepsen/etcdemo.clj:67-69):
    read -> rv = observed value; write -> a1 = value; cas -> a1,a2 = old,new.
    Default codec for models that don't define their own (models/base.py)."""
    if f_name not in FUNC_CODES:
        raise EncodeError(f"unsupported register op f={f_name!r}")
    f = FUNC_CODES[f_name]
    a1 = a2 = 0
    rv = NIL
    if f == F_READ:
        if status == OK:
            rv = _encode_value(ok_value)
    elif f == F_WRITE:
        a1 = _encode_value(invoke_value)
    elif f == F_CAS:
        old, new = invoke_value
        a1, a2 = _encode_value(old), _encode_value(new)
    return f, a1, a2, rv


def pair_history(history: Sequence[Op], model=None,
                 keyed: bool = False) -> list[Invocation]:
    """Pair invoke entries with their completions by process id.

    Mirrors the framework recorder's pairing [dep]; a process has at most one
    outstanding invocation at a time (jepsen worker model). Invocations whose
    completion never arrives are treated as `info` (crashed mid-op), exactly
    like jepsen treats them when a run ends.

    `model` supplies the op-language codec (Model.encode_invocation); None
    uses the register conventions — the language of the reference demo and
    of every model whose prepare_history translates into it.

    `keyed`: the history is one key's records of an independent-key
    history, values still `(key, v)`; the codec sees them through
    op.keyed_value, as it would see split_by_key's unwrapped copies.
    """
    pending: dict[Any, tuple[int, Op]] = {}
    out: list[Invocation] = []
    for idx, op in enumerate(history):
        if op.type == INVOKE:
            if op.process in pending:
                raise EncodeError(
                    f"process {op.process} invoked twice without completing "
                    f"(history indices {pending[op.process][0]} and {idx})"
                )
            pending[op.process] = (idx, op)
        elif op.type in (OK, FAIL, INFO):
            if op.process not in pending:
                raise EncodeError(
                    f"completion for process {op.process} at history index "
                    f"{idx} has no pending invocation"
                )
            inv_idx, inv = pending.pop(op.process)
            out.append(_make_invocation(inv, op, inv_idx, idx, model, keyed))
        else:
            raise EncodeError(f"unknown op type {op.type!r} at index {idx}")
    # Unfinished invocations: open forever.
    for proc, (inv_idx, inv) in pending.items():
        out.append(_make_invocation(inv, None, inv_idx, -1, model, keyed))
    out.sort(key=lambda i: i.invoke_index)
    return out


def _make_invocation(inv: Op, comp: Optional[Op], inv_idx: int,
                     comp_idx: int, model=None,
                     keyed: bool = False) -> Invocation:
    status = comp.type if comp is not None else INFO
    # The completion value reaches the codec for OK *and* INFO: an
    # indeterminate op may still carry the value it tried to take (e.g. a
    # dequeue whose compare-and-delete response was lost after claiming a
    # known element — clients/etcd.py IndeterminateDequeue), which is what
    # makes it encodable as a pending op.
    comp_value = None
    if comp is not None and comp.type in (OK, INFO):
        comp_value = keyed_value(comp) if keyed else comp.value
    inv_value = keyed_value(inv) if keyed else inv.value
    codec = register_fields if model is None else model.encode_invocation
    f, a1, a2, rv = codec(inv.f, inv_value, comp_value, status)
    return Invocation(f=f, a1=a1, a2=a2, rv=rv, status=status,
                      invoke_index=inv_idx, complete_index=comp_idx,
                      process=inv.process)


def _codec_of(model):
    """`model`'s op-language codec, bound once per history:
    register_fields itself when the model keeps the default (None
    included), so the pass makes no per-op call through the Model."""
    from ..models.base import Model

    if model is None or \
            type(model).encode_invocation is Model.encode_invocation:
        return register_fields
    return model.encode_invocation


def _event_points(history: Sequence[Op], codec, keyed: bool = False,
                  complete: Optional[dict[int, int]] = None) -> list:
    """The event each record gives, in record order: the (f, a1, a2, rv)
    fields of an included invoke, the invoke's record index at the ok
    completion of an included op, None for every other record.

    The one copy of the inclusion and ordering rule: `fail` ops and `info`
    reads give no event (module docstring). Each record gives at most one
    event, at its own position, so record order is event order. Pairs by
    process as pair_history does and raises the same EncodeError on the
    shapes it rejects; `codec` (_codec_of) sees every paired op, `fail`
    ops too, in completion order, then the never-completed invokes as
    `info`, its values read as _make_invocation reads them. `complete`,
    when given, maps each included invoke's index to its completion's."""
    points: list = [None] * len(history)
    pending: dict[Any, int] = {}     # process -> invoke index
    for idx, op in enumerate(history):
        t = op.type
        if t == INVOKE:
            if op.process in pending:
                raise EncodeError(
                    f"process {op.process} invoked twice without completing "
                    f"(history indices {pending[op.process]} and {idx})")
            pending[op.process] = idx
            continue
        if t != OK and t != FAIL and t != INFO:
            raise EncodeError(f"unknown op type {t!r} at index {idx}")
        inv_idx = pending.pop(op.process, -1)
        if inv_idx < 0:
            raise EncodeError(
                f"completion for process {op.process} at history index "
                f"{idx} has no pending invocation")
        inv = history[inv_idx]
        if keyed:
            fields = codec(inv.f, keyed_value(inv),
                           None if t == FAIL else keyed_value(op), t)
        else:
            fields = codec(inv.f, inv.value,
                           None if t == FAIL else op.value, t)
        if t == FAIL or (t == INFO and fields[0] == F_READ):
            continue
        points[inv_idx] = fields
        if t == OK:
            points[idx] = inv_idx
        if complete is not None:
            complete[inv_idx] = idx
    # Never completed: info, pending forever.
    for inv_idx in pending.values():
        inv = history[inv_idx]
        fields = codec(inv.f, keyed_value(inv) if keyed else inv.value,
                       None, INFO)
        if fields[0] != F_READ:
            points[inv_idx] = fields
    return points


def event_sources(history: Sequence[Op], model=None) -> list[Invocation]:
    """The invocation behind each encoded event row, in event order —
    row i of encode_history(history, model).events describes
    event_sources(history, model)[i]. Used by the witness reconstructor
    to map kernel/oracle event indices back to concrete history
    operations."""
    complete: dict[int, int] = {}
    points = _event_points(history, _codec_of(model), complete=complete)
    out: list[Invocation] = []
    made: dict[int, Invocation] = {}  # invoke index -> its invocation
    for idx, p in enumerate(points):
        if p is None:
            continue
        if type(p) is tuple:
            comp_idx = complete.get(idx, -1)
            made[idx] = Invocation(
                *p, status=history[comp_idx].type if comp_idx >= 0 else INFO,
                invoke_index=idx, complete_index=comp_idx,
                process=history[idx].process)
            out.append(made[idx])
        else:
            out.append(made[p])
    return out


class IncrementalEncoder:
    """Streaming counterpart of encode_history (stream/).

    Consumes history entries ONE AT A TIME (in recorded order) and emits
    event rows exactly when they become STABLE — when no future history
    entry can change, remove, or reorder them. The stable-prefix rule:

      * an op's events are determined only once its completion is
        recorded (``fail`` -> dropped entirely, indeterminate read ->
        dropped, ``info`` -> EV_INVOKE only, carrying the completion's
        value, ``ok`` -> EV_INVOKE + EV_RETURN);
      * therefore every event at a history position at or after the
        earliest STILL-OPEN invoke is unstable: that op's eventual
        completion may insert (or not) an EV_INVOKE at that earlier
        position, shifting everything behind it.

    The *watermark* is that earliest-open-invoke position, ordered by
    the recorder's monotonic per-entry sequence (``Op.seq`` /
    append order — never wall clock). An op that will crash pins the
    watermark from its invoke until its ``:info`` completion is
    recorded; after that it is encoded pending-forever (WGL open-op
    semantics — its slot is never freed) and the watermark moves on.
    Ops still open when the run ends are resolved as ``info`` by
    :meth:`finalize`, exactly like pair_history's end-of-run rule.

    The emitted rows are BIT-IDENTICAL to the corresponding prefix of
    ``encode_history(history, model)``: same event order (record order,
    one event per record at most), same slot
    assignment — encode_history pops fresh slots in increasing order and
    reuses freed slots LIFO, which depends only on the event
    interleaving, never on the slot-table capacity, so the unbounded
    stack here reproduces any non-overflowing capacity's ids — and the
    same n_ops / max_pending / max_value bookkeeping
    (tests/test_stream.py pins it on fuzz histories).
    """

    def __init__(self, model=None):
        self.model = model
        self._open: dict[Any, tuple[int, Op]] = {}   # process -> (idx, op)
        self._heap: list = []      # (pos, is_return, tiebreak, Invocation)
        self._tie = itertools.count()
        self._idx = 0              # history entries consumed
        self._free: list[int] = []  # freed slot ids (LIFO stack)
        self._next_slot = 0
        self._slot_of: dict[int, int] = {}           # invoke_index -> slot
        self._cur_pending = 0
        self._row_max: Optional[int] = None
        self._last_seq = -1        # last recorder seq consumed
        self._finalized = False
        self.rows: list[list[int]] = []              # stable event rows
        self.n_ops = 0
        self.max_pending = 0

    @property
    def max_value(self) -> int:
        # Exactly encode_history's bookkeeping: max over the emitted rows'
        # (a1, a2, rv) fields, 0 when no rows were emitted.
        return 0 if self._row_max is None else self._row_max

    def watermark(self) -> int:
        """First UNSTABLE history position: the earliest still-open
        invoke's index (== entries consumed when nothing is open)."""
        if self._finalized or not self._open:
            return self._idx
        return min(idx for idx, _ in self._open.values())

    def lag(self) -> int:
        """History entries consumed but not yet stable (the
        stream.watermark_lag gauge)."""
        return self._idx - self.watermark()

    def append(self, op: Op) -> list[list[int]]:
        """Consume one history entry; returns the newly-STABLE event
        rows (possibly none). Raises EncodeError on the same malformed
        shapes pair_history rejects."""
        if self._finalized:
            raise EncodeError("append after finalize")
        # Recorder-stamped entries must arrive in strictly increasing
        # seq — the total order the watermark's stability argument rests
        # on. A violation means the feed path reordered (or duplicated)
        # entries; encoding on would silently corrupt the prefix.
        if op.seq >= 0:
            if op.seq <= self._last_seq:
                raise EncodeError(
                    f"out-of-order feed: seq {op.seq} after "
                    f"{self._last_seq} (history index {self._idx})")
            self._last_seq = op.seq
        idx = self._idx
        self._idx += 1
        if op.type == INVOKE:
            if op.process in self._open:
                raise EncodeError(
                    f"process {op.process} invoked twice without completing "
                    f"(history indices {self._open[op.process][0]} and {idx})"
                )
            self._open[op.process] = (idx, op)
        elif op.type in (OK, FAIL, INFO):
            if op.process not in self._open:
                raise EncodeError(
                    f"completion for process {op.process} at history index "
                    f"{idx} has no pending invocation"
                )
            inv_idx, inv = self._open.pop(op.process)
            self._resolve(inv, op, inv_idx, idx)
        else:
            raise EncodeError(f"unknown op type {op.type!r} at index {idx}")
        return self._drain()

    def finalize(self) -> list[list[int]]:
        """Resolve every still-open invocation as ``info`` (crashed
        mid-op — pair_history's end-of-run rule) and drain everything;
        returns the remaining rows. Idempotent."""
        if not self._finalized:
            for inv_idx, inv in sorted(self._open.values()):
                self._resolve(inv, None, inv_idx, -1)
            self._open.clear()
            self._finalized = True
        return self._drain()

    def encoded_history(self, k_slots: int = 32) -> EncodedHistory:
        """The stable rows as an EncodedHistory — after finalize, this is
        exactly what ``encode_history(history, model, k_slots)`` under
        the checker's slot-escalation ladder (k doubles past
        max_pending, checkers/linearizable.py) would have produced."""
        k = max(1, int(k_slots))
        while self.max_pending > k:
            k *= 2
        events = (np.asarray(self.rows, dtype=np.int32)
                  .reshape(-1, EVENT_WIDTH))
        return EncodedHistory(events=events, n_events=len(self.rows),
                              n_ops=self.n_ops, k_slots=k,
                              max_pending=self.max_pending,
                              max_value=self.max_value)

    # -- internals --------------------------------------------------------
    def _resolve(self, inv: Op, comp: Optional[Op], inv_idx: int,
                 comp_idx: int) -> None:
        invocation = _make_invocation(inv, comp, inv_idx, comp_idx,
                                      self.model)
        # The _event_points exclusions, applied at resolution time.
        if invocation.status == FAIL:
            return
        if invocation.status == INFO and invocation.f == F_READ:
            return
        heapq.heappush(self._heap,
                       (inv_idx, 0, next(self._tie), invocation))
        if invocation.status == OK:
            heapq.heappush(self._heap,
                           (comp_idx, 1, next(self._tie), invocation))

    def _drain(self) -> list[list[int]]:
        wm = self.watermark()
        new: list[list[int]] = []
        while self._heap and self._heap[0][0] < wm:
            _pos, is_return, _t, inv = heapq.heappop(self._heap)
            if not is_return:
                # encode_history's exact policy: its free list is a stack
                # seeded [k-1..0], so fresh slots come out in increasing
                # order and FREED slots are reused most-recent-first.
                if self._free:
                    slot = self._free.pop()
                else:
                    slot = self._next_slot
                    self._next_slot += 1
                self._slot_of[inv.invoke_index] = slot
                row = [EV_INVOKE, slot, inv.f, inv.a1, inv.a2, inv.rv]
                self.n_ops += 1
                self._cur_pending += 1
                self.max_pending = max(self.max_pending, self._cur_pending)
            else:
                slot = self._slot_of.pop(inv.invoke_index)
                row = [EV_RETURN, slot, inv.f, inv.a1, inv.a2, inv.rv]
                self._free.append(slot)
                self._cur_pending -= 1
            hi = max(row[3], row[4], row[5])
            self._row_max = hi if self._row_max is None \
                else max(self._row_max, hi)
            self.rows.append(row)
            new.append(row)
        return new


def encode_register_history(history: Sequence[Op], k_slots: int = 32
                            ) -> EncodedHistory:
    """History of register ops (read/write/cas) -> padded event tensor."""
    return encode_history(history, None, k_slots=k_slots)


def encode_history(history: Sequence[Op], model, k_slots: int = 32,
                   keyed: bool = False) -> EncodedHistory:
    """History in `model`'s op language -> (kind, slot, f, a1, a2, rv)
    event rows, with slot assignment; `model` None is the register
    language.

    _event_points gives the events in record order; slots come from a
    stack seeded [k_slots-1..0], so fresh slots are taken in increasing
    order and a freed slot is reused first; SlotOverflow past k_slots.

    Does NOT apply model.prepare_history — the checker seam translates once
    (checkers/linearizable.py) so witness reconstruction sees the same op
    language the encoder did. `keyed`: as in pair_history."""
    t_enc = time.monotonic()
    codec = _codec_of(model)
    points = _event_points(history, codec, keyed)
    free = list(range(k_slots - 1, -1, -1))  # pop() yields lowest slot first
    slot_of: dict[int, int] = {}             # invoke index -> slot
    flat: list[int] = []                     # the rows, one after another
    n_ops = pending = max_pending = 0
    for idx, p in enumerate(points):
        if p is None:
            continue
        if type(p) is tuple:
            if not free:
                raise SlotOverflow(
                    f"more than {k_slots} simultaneously pending ops at "
                    f"history index {idx}; raise k_slots")
            slot = free.pop()
            slot_of[idx] = slot
            flat += (EV_INVOKE, slot) + p
            n_ops += 1
            pending += 1
            if pending > max_pending:
                max_pending = pending
        else:
            slot = slot_of.pop(p)
            flat += (EV_RETURN, slot) + points[p]
            free.append(slot)
            pending -= 1

    events = np.fromiter(flat, np.int32, len(flat)).reshape(-1, EVENT_WIDTH)
    n_events = events.shape[0]
    max_value = int(events[:, 3:6].max()) if n_events else 0
    # Telemetry (obs/): host-side encode cost, pairing included, and the
    # event-tensor bytes that will cross the host->device boundary
    # (SURVEY §5.1 — the harness's own hot loop needs a breakdown, not
    # just the op history).
    m = get_metrics()
    m.counter("encode.encode_s").add(time.monotonic() - t_enc)
    m.counter("encode.histories").add(1)
    if codec is register_fields:
        m.counter("encode.inline_codec_histories").add(1)
    m.counter("encode.event_bytes").add(int(events.nbytes))
    return EncodedHistory(events=events, n_events=n_events, n_ops=n_ops,
                          k_slots=k_slots, max_pending=max_pending,
                          max_value=max_value)


def reslot_events(enc: EncodedHistory, k_slots: int) -> EncodedHistory:
    """Remap slot ids into a smaller slot table (k_slots >= max_pending).

    Uses the same greedy lowest-free assignment as encode_history over the
    same event order, so the result is exactly what encoding with the
    smaller k_slots would have produced. Lets the dense lattice kernel
    (wgl3) shrink its 2^K mask axis to the history's REAL concurrency after
    a conservative first encoding."""
    if k_slots < enc.max_pending:
        raise EncodeError(
            f"cannot reslot to {k_slots} slots: history has "
            f"{enc.max_pending} simultaneously pending ops")
    ev = enc.events[: enc.n_events].copy()
    free = list(range(k_slots - 1, -1, -1))
    mapping: dict[int, int] = {}
    for row in ev:
        if row[0] == EV_INVOKE:
            new = free.pop()
            mapping[int(row[1])] = new
            row[1] = new
        elif row[0] == EV_RETURN:
            new = mapping.pop(int(row[1]))
            row[1] = new
            free.append(new)
    return EncodedHistory(events=ev, n_events=enc.n_events, n_ops=enc.n_ops,
                          k_slots=k_slots, max_pending=enc.max_pending,
                          max_value=enc.max_value)


@dataclass
class ReturnSteps:
    """Return-event-major encoding: one row per EV_RETURN, with a full
    pending-slot snapshot.

    The WGL search only does real work at returns (closure + prune); invokes
    are just slot-table bookkeeping. Precomputing the slot table per return
    on the host gives the device kernel a scan whose every step does
    identical work — no invoke/return branching, which matters enormously
    under vmap (a lax.cond over batch-varying event kinds becomes a select
    that executes BOTH branches for every lane).

    slot_tabs[i] is the snapshot just before processing return i: every op
    invoked earlier (in history order) and not yet returned is active,
    including the returning op itself."""

    slot_tabs: np.ndarray    # [R, K, 4] int32 (f, a1, a2, rv)
    slot_active: np.ndarray  # [R, K] bool
    targets: np.ndarray      # [R] int32 slot of the returning op; -1 = pad
    n_steps: int             # real (non-pad) returns
    n_ops: int
    k_slots: int
    max_pending: int
    max_value: int = 0

    def padded_to(self, r_cap: int) -> "ReturnSteps":
        r = self.slot_tabs.shape[0]
        if r_cap < r:
            raise EncodeError(f"cannot pad {r} return steps to {r_cap}")
        tabs = np.zeros((r_cap,) + self.slot_tabs.shape[1:], np.int32)
        act = np.zeros((r_cap, self.k_slots), bool)
        tgt = np.full((r_cap,), -1, np.int32)
        tabs[:r] = self.slot_tabs
        act[:r] = self.slot_active
        tgt[:r] = self.targets
        return ReturnSteps(tabs, act, tgt, self.n_steps, self.n_ops,
                           self.k_slots, self.max_pending, self.max_value)


def encode_return_steps(enc: EncodedHistory) -> ReturnSteps:
    """Derive the return-major encoding from the event encoding.

    Placement routes through ``limits().encode_mode``: mode 2 expands
    the table ON DEVICE (ops/encode_device.py — bit-identical rows, the
    event stream crosses the H2D boundary instead of the packed table);
    modes 0/1 run the host expansion below. Every consumer — post-hoc
    checks AND the streaming IncrementalEncoder prefix (stream/
    engine.py calls this on its stable rows) — funnels through here, so
    the one knob governs both paths."""
    from .limits import limits

    if limits().encode_mode == 2:
        from . import encode_device

        if encode_device.device_encode_feasible(enc):
            return encode_device.encode_return_steps_device(enc)
    return _encode_return_steps_host(enc)


def _encode_return_steps_host(enc: EncodedHistory) -> ReturnSteps:
    """The host expansion, vectorized (no per-return [K,4] snapshot
    loop): for each return event at position p, slot k's table row is
    the fields of the last EV_INVOKE of slot k before p, and slot k is
    active iff its invokes before p outnumber its returns strictly
    before p (the returning op itself counts active)."""
    t_enc = time.monotonic()
    k = enc.k_slots
    n = enc.n_events
    ev = np.asarray(enc.events[:n])
    if n == 0 or not (ev[:, 0] == EV_RETURN).any():
        return ReturnSteps(
            slot_tabs=np.zeros((0, k, 4), np.int32),
            slot_active=np.zeros((0, k), bool),
            targets=np.zeros((0,), np.int32),
            n_steps=0, n_ops=enc.n_ops, k_slots=k,
            max_pending=enc.max_pending, max_value=enc.max_value)
    kinds, slots = ev[:, 0], ev[:, 1]
    slot_ids = np.arange(k)
    inv_onehot = (kinds == EV_INVOKE)[:, None] & (slots[:, None] == slot_ids)
    ret_onehot = (kinds == EV_RETURN)[:, None] & (slots[:, None] == slot_ids)
    inv_cum = np.cumsum(inv_onehot, axis=0)   # invokes in events[0..p]
    ret_cum = np.cumsum(ret_onehot, axis=0)   # returns in events[0..p]
    # Last invoke position of each slot at-or-before each event position.
    last_inv = np.maximum.accumulate(
        np.where(inv_onehot, np.arange(n)[:, None], -1), axis=0)

    ret_pos = np.nonzero(kinds == EV_RETURN)[0]
    # Event p is a return, so "invokes before p" == inv_cum[p]; "returns
    # strictly before p" excludes p's own return.
    active = inv_cum[ret_pos] > (ret_cum[ret_pos] - ret_onehot[ret_pos])
    last = last_inv[ret_pos]                   # [R, K]
    tabs = np.where(last[:, :, None] >= 0,
                    ev[np.maximum(last, 0)][:, :, 2:6], 0).astype(np.int32)
    get_metrics().counter("encode.encode_s").add(time.monotonic() - t_enc)
    return ReturnSteps(
        slot_tabs=tabs,
        slot_active=active,
        targets=slots[ret_pos].astype(np.int32),
        n_steps=len(ret_pos), n_ops=enc.n_ops, k_slots=k,
        max_pending=enc.max_pending, max_value=enc.max_value)


#: The lockstep expansion of stack_return_steps pays a few numpy steps
#: per event position whatever the batch (15-25 us each on a CPU core);
#: the per-history one pays about 80 us a history plus its E x K work.
#: Lockstep is chosen while the bucket's longest history has at most
#: this many events per history in the bucket: below it lockstep won or
#: tied at every measured shape (CPU micro-A/B in PERF.md).
LOCKSTEP_EVENTS_PER_HISTORY = 8


def lockstep_wins(n_histories: int, max_events: int) -> bool:
    """Whether a bucket of `n_histories` whose longest history has
    `max_events` events expands faster in lockstep than per history."""
    return max_events <= LOCKSTEP_EVENTS_PER_HISTORY * n_histories


def _fit_slots(enc: EncodedHistory, k_slots: int) -> EncodedHistory:
    """`enc` at k_slots: its own event rows when every slot id is below
    k_slots, else reslotted. Encoder output takes ids lowest-free, so
    its ids already are what reslot_events gives at any k_slots >=
    max_pending."""
    ids = enc.events[: enc.n_events, 1]
    if not ids.size or int(ids.max()) < k_slots:
        return (enc if enc.k_slots == k_slots
                else dataclasses.replace(enc, k_slots=k_slots))
    get_metrics().counter("sched.pack.reslotted").add(1)
    return reslot_events(enc, k_slots)


def stack_return_steps(encs: Sequence[EncodedHistory], k_slots: int,
                       r_cap: int, batch: Optional[int] = None,
                       order: Optional[Sequence[int]] = None
                       ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """One launch's return-step arrays, expanded straight into the stack:
    tabs [B, r_cap, k_slots, 4] int32, act [B, r_cap, k_slots] bool and
    tgt [B, r_cap] int32, B = `batch` (default len(encs)). Rows past
    len(encs) are all-pad; with `order`, row j holds entry order[j].

    Equal, bit for bit, to stacking each history's
    encode_return_steps(...).padded_to(r_cap) in that order. A history
    whose slot ids all lie below k_slots is expanded as it is (for
    encoder output that is what reslot_events gives); any other is
    reslotted first.

    Buckets where lockstep_wins walk the event positions once for the
    whole bucket: at an invoke the row's fields go into a running
    [n, K, 4] slot table and its slot turns active; at a return the
    table and active mask are copied out as that history's next step,
    then the slot is freed (its fields stay, as in the per-history
    expansion). Other buckets, and encode_mode 2, expand per history."""
    from .limits import limits

    t0 = time.monotonic()
    n = len(encs)
    b = n if batch is None else batch
    k = k_slots
    rows = (np.arange(n) if order is None
            else np.argsort(np.asarray(order))[:n])
    tabs = np.zeros((b, r_cap, k, 4), np.int32)
    act = np.zeros((b, r_cap, k), bool)
    tgt = np.full((b, r_cap), -1, np.int32)
    lens = np.fromiter((e.n_events for e in encs), np.int64, n)
    emax = int(lens.max()) if n else 0
    if limits().encode_mode == 2 or not lockstep_wins(n, emax):
        for i, e in enumerate(encs):
            rs = encode_return_steps(_fit_slots(e, k))
            if rs.n_steps > r_cap:
                raise EncodeError(
                    f"cannot pad {rs.n_steps} return steps to {r_cap}")
            j, r = rows[i], rs.n_steps
            tabs[j, :r] = rs.slot_tabs
            act[j, :r] = rs.slot_active
            tgt[j, :r] = rs.targets
        return tabs, act, tgt

    # Every real event row, history after history, in one array.
    cat = np.concatenate([e.events[: e.n_events] for e in encs]
                         + [np.zeros((0, EVENT_WIDTH), np.int32)])
    starts = np.cumsum(lens) - lens
    nonempty = np.flatnonzero(lens)
    if nonempty.size:
        top = np.maximum.reduceat(cat[:, 1], starts[nonempty])
        for i in nonempty[top >= k]:
            cat[starts[i]: starts[i] + lens[i]] = _fit_slots(encs[i], k).events
    kind, slot = cat[:, 0], cat[:, 1]
    rets = np.concatenate([[0], np.cumsum(kind == EV_RETURN)])
    n_ret = rets[starts + lens] - rets[starts]
    if n and int(n_ret.max()) > r_cap:
        raise EncodeError(
            f"cannot pad {int(n_ret.max())} return steps to {r_cap}")

    # Every event in position order (history order within a position);
    # every index below is a flat row number.
    at_p, of_h = np.nonzero(np.arange(emax)[:, None] < lens)
    at_k = kind[starts[of_h] + at_p]
    is_inv, is_ret = at_k == EV_INVOKE, at_k == EV_RETURN
    inv = starts[of_h[is_inv]] + at_p[is_inv]
    ret = starts[of_h[is_ret]] + at_p[is_ret]
    i_cell, i_fields = of_h[is_inv] * k + slot[inv], cat[inv, 2:6]
    r_hist = of_h[is_ret]
    r_cell = r_hist * k + slot[ret]
    r_out = rows[r_hist] * r_cap + rets[ret] - rets[starts[r_hist]]
    tgt.reshape(-1)[r_out] = slot[ret]
    i_cut = np.searchsorted(at_p[is_inv], np.arange(emax + 1))
    r_cut = np.searchsorted(at_p[is_ret], np.arange(emax + 1))
    run_tab = np.zeros((n * k, 4), np.int32)
    run_act = np.zeros(n * k, bool)
    tab_rows, act_rows = run_tab.reshape(n, k * 4), run_act.reshape(n, k)
    out_tab, out_act = tabs.reshape(-1, k * 4), act.reshape(-1, k)
    for p in range(emax):
        lo, hi = i_cut[p], i_cut[p + 1]
        run_tab[i_cell[lo:hi]] = i_fields[lo:hi]
        run_act[i_cell[lo:hi]] = True
        lo, hi = r_cut[p], r_cut[p + 1]
        at, rh = r_out[lo:hi], r_hist[lo:hi]
        out_tab[at] = tab_rows[rh]
        out_act[at] = act_rows[rh]
        run_act[r_cell[lo:hi]] = False
    m = get_metrics()
    m.counter("sched.pack.batched_histories").add(n)
    m.counter("encode.encode_s").add(time.monotonic() - t0)
    return tabs, act, tgt

