"""Operation records — the atoms of a history.

Mirrors the reference's op shape `{:type, :f, :value, :process, :time, :index,
:error}` (op constructors at reference src/jepsen/etcdemo.clj:67-69; completion
types assigned in Client.invoke! at src/jepsen/etcdemo.clj:83-105).

Completion semantics (load-bearing for the checker, see reference
src/jepsen/etcdemo.clj:100-105):
  ok    — the op definitely took effect.
  fail  — the op definitely did NOT take effect (excluded from linearizability).
  info  — indeterminate: may have taken effect at any point after its invoke,
          arbitrarily far in the future ("open forever").
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field, asdict
from typing import Any, Optional

# Op types.
INVOKE = "invoke"
OK = "ok"
FAIL = "fail"
INFO = "info"

COMPLETION_TYPES = (OK, FAIL, INFO)


@dataclass
class Op:
    """One history entry: either an invocation or its completion."""

    type: str                      # invoke | ok | fail | info
    f: str                         # e.g. read | write | cas | add | start | stop
    value: Any = None              # op-dependent payload (may be a (key, v) tuple)
    process: Any = None            # logical process id (int) or "nemesis"
    time: int = 0                  # nanoseconds relative to test start
    index: int = -1                # position in the recorded history
    error: Optional[Any] = None    # e.g. "timeout", "not-found"
    # Monotonic record sequence number, assigned by the HistoryRecorder
    # at append time from a process-local counter (NOT wall clock): the
    # total order the streaming checker's stable-prefix watermark keys
    # on, stable under thread-scheduling jitter even when monotonic_ns
    # ties. -1 = never recorded (hand-built ops, pre-seq artifacts).
    seq: int = -1
    extra: dict = field(default_factory=dict)

    def is_invoke(self) -> bool:
        return self.type == INVOKE

    def is_completion(self) -> bool:
        return self.type in COMPLETION_TYPES

    def to_json(self) -> str:
        d = asdict(self)
        if not d["extra"]:
            d.pop("extra")
        if d["seq"] < 0:
            d.pop("seq")   # keep pre-seq artifacts byte-stable
        return json.dumps(d, default=_jsonable)

    @staticmethod
    def from_json(line: str) -> "Op":
        d = json.loads(line)
        d.setdefault("extra", {})
        d.setdefault("seq", -1)
        # JSON round-trips tuples as lists; normalize 2-lists back to tuples so
        # (key, value) independent-tuples survive store round trips.
        v = d.get("value")
        if isinstance(v, list) and len(v) == 2:
            d["value"] = tuple(v)
        return Op(**d)


def keyed_value(op: Op) -> Any:
    """The value of a record of an independent-key history, without its
    key: `v` of a `(key, v)` value, else the value as recorded (a
    completion may carry a bare value, e.g. a timeout's :info). An invoke
    always carries the tuple (checkers/independent.route_by_key checks)."""
    v = op.value
    return v[1] if isinstance(v, tuple) and len(v) == 2 else v


def _jsonable(x):
    if isinstance(x, (set, frozenset)):
        return sorted(x)
    if isinstance(x, tuple):
        return list(x)
    return str(x)


def invoke(f: str, value: Any = None, process: Any = 0, time: int = 0) -> Op:
    return Op(type=INVOKE, f=f, value=value, process=process, time=time)


def history_to_jsonl(history: list[Op]) -> str:
    return "\n".join(op.to_json() for op in history) + "\n"


def history_from_jsonl(text: str) -> list[Op]:
    return [Op.from_json(line) for line in text.splitlines() if line.strip()]
